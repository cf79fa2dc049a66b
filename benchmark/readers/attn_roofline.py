"""Roofline share of the Pallas local-attention kernels in a train step:
the least time the chip could take for the attention calls the algorithm
needs (one forward and one backward per layer and micro-batch; the
rematerialized second forward is the program's own cost, not counted)
over the device time of the kernels' custom calls in the trace."""

from benchmark import flops, xplane


def read(run, spec):
    if run.trace is None or not run.trace["devices"] or run.peak is None:
        return None
    dev = run.trace["devices"][0]
    kernel_s, n_calls = xplane.matching_seconds(dev["ops"], spec["match"])
    steps = xplane.matching(dev["modules"], spec["step_match"])
    if not n_calls or not steps:
        return None
    # the traced window cuts the steps at its edges: count steps by device
    # time, in units of the longest (a whole) one
    n_steps = sum(steps) / max(steps)
    c, t = run.config, run.traffic
    cost = flops.local_attention_ops_bytes(
        bh=t["micro_batch"] * c["heads"], n=c["seq_len"], d=c["dim_head"],
        w=c["window_size"],
    )
    calls = c["depth"] * t["grad_accum"]
    least = calls * (
        flops.roofline_seconds(cost["fwd_flops"], cost["fwd_bytes"], run.peak)[0]
        + flops.roofline_seconds(cost["bwd_flops"], cost["bwd_bytes"], run.peak)[0]
    )
    run.notes["attn_roofline"] = {
        "bound": flops.roofline_seconds(
            cost["fwd_flops"] + cost["bwd_flops"],
            cost["fwd_bytes"] + cost["bwd_bytes"], run.peak)[1],
        "kernel_s_per_step": kernel_s / n_steps,
        "least_s_per_step": least, "kernel_calls": n_calls, "steps": n_steps,
    }
    return 100.0 * least / (kernel_s / n_steps)
