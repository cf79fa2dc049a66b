"""Shares of a peak for a served latent-attention, routed-expert model,
counted from the configuration (benchmark/flops_latent_moe.py) and from
the counters the driver took over the window. The spec's ``what`` picks:

  ``mfu``      FLOPs the window's prefilled and decoded tokens need (2 x
               the parameters a token meets, plus its attention over what
               it sees) over window x chips x the bf16 peak
  ``decode``   least time for one decode step — bytes of the routed
               experts the steps touched (the program's counter), of every
               other weight once and of the live slots' latent rows, over
               the HBM peak; FLOPs if they bind — over the median device
               time of the decode program in the trace
  ``prefill``  the same for one prefill block of ``prefill_chunk`` rows,
               over the median device time of the chunk program

A program without the counters, a run without a trace or a device without
published peaks gives nothing to read.
"""

import statistics

from benchmark import flops, flops_latent_moe as lm, xplane


def _median_seconds(run, pattern):
    if run.trace is None or not run.trace["devices"]:
        return None
    runs = xplane.matching(run.trace["devices"][0]["modules"], pattern)
    return statistics.median(runs) if runs else None


def read(run, spec):
    c, k = run.config, run.counters
    if run.peak is None or not k.get("moe_expert_layer_steps"):
        return None
    if spec["what"] == "mfu":
        need = (
            k["decode_tokens"] * 2 * lm.active_params(c, head=True)
            + lm.attention_flops(c, k["decode_context_sum"])
            + k["prefill_tokens"] * 2 * lm.active_params(c, head=False)
            + lm.attention_flops(c, k["prefill_context_sum"])
        )
        return 100.0 * need / (
            k["window_s"] * k["chips"] * run.peak["bf16_flops"]
        )
    if spec["what"] == "decode":
        steps = k["decode_steps"]
        touched = k["moe_experts_touched"] / k["moe_expert_layer_steps"]
        rows = k["decode_context_sum"] / steps
        need_flops = (k["decode_tokens"] * 2 * lm.active_params(c, head=True)
                      + lm.attention_flops(c, k["decode_context_sum"])) / steps
        need_bytes = lm.pass_bytes(c, touched, rows, head=True)
    elif spec["what"] == "prefill":
        blocks = k.get("moe_feed_expert_layer_blocks", 0) / max(
            lm.n_expert_layers(c), 1)
        if not blocks:
            return None
        touched = (k["moe_feed_experts_touched"]
                   / k["moe_feed_expert_layer_blocks"])
        rows = k["prefill_tokens"] / blocks  # positions a block fed
        seen = k["prefill_context_sum"] / max(k["prefill_tokens"], 1)
        need_flops = rows * lm.token_flops(c, seen, head=False)
        need_bytes = lm.pass_bytes(c, touched, seen, head=False)
    else:
        raise ValueError(f"latent_moe_yardsticks: unknown {spec['what']!r}")
    took = _median_seconds(run, spec["match"])
    if took is None:
        return None
    least, bound = flops.roofline_seconds(need_flops, need_bytes, run.peak)
    run.notes[f"roofline.{spec['what']}"] = {
        "bound": bound, "least_s": least, "device_s": took,
        "bytes": need_bytes, "flops": need_flops,
        "experts_touched": touched, "latent_rows": rows,
    }
    return 100.0 * least / took
