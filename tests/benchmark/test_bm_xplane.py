"""The reduction from a profiler trace to device metrics, on a trace
recorded on one v5e chip (benchmark/fixtures/) and on a hand-made
four-chip piece for the collectives."""

import json
from pathlib import Path

import pytest

from benchmark import xplane

FIXTURE = (Path(__file__).resolve().parents[2] / "benchmark" / "fixtures"
           / "trace_v5e_one_chip.json")


@pytest.fixture(scope="module")
def recorded():
    t = json.loads(FIXTURE.read_text())
    win = next(e for e in t["host"] if e[0] == "bench.window")
    lo, hi = win[1], win[1] + win[2]
    clipped = xplane.clip(t, lo, hi)
    clipped["host"] = [e for e in clipped["host"] if e[0] != "bench.window"]
    return clipped, lo, hi


def test_union_merges_overlaps_and_touching_intervals():
    ev = [["a", 0, 10], ["b", 5, 10], ["c", 15, 5], ["d", 30, 5]]
    assert xplane.union(ev) == [[0, 20], [30, 35]]


def test_clip_cuts_events_that_straddle_an_edge():
    t = {"devices": [{"ops": [["x", 0, 10], ["y", 20, 10], ["z", 40, 5]],
                      "modules": []}], "host": [["h", 5, 100]]}
    c = xplane.clip(t, 5, 25)
    assert c["devices"][0]["ops"] == [["x", 5, 5], ["y", 20, 5]]
    assert c["host"] == [["h", 5, 20]]


def test_busy_and_idle_of_the_recorded_trace(recorded):
    trace, lo, hi = recorded
    busy = xplane.busy_seconds(trace)
    window = (hi - lo) / 1e9
    # five chains of 20 matmuls of ~90 us each, and one 96 us kernel, in
    # 268 ms of mostly sleeping host: the chip is idle ~97% of the time
    assert 0.0075 < busy < 0.0095
    assert 0.96 < 1 - busy / window < 0.98
    modules, n = xplane.matching_seconds(trace["devices"][0]["modules"], "^jit_chain")
    assert n == 4 or n == 5  # the first chain began before the window
    assert busy >= modules * 0.99


def test_op_ranking_puts_the_matmuls_first(recorded):
    trace, _, _ = recorded
    rank = xplane.op_ranking(trace, 3)
    assert rank[0][0] == "convolution_multiply_fusion bf16[2048,2048]"
    assert rank[0][1] > 10 * rank[1][1]
    assert [r[1] for r in rank] == sorted((r[1] for r in rank), reverse=True)
    assert len(xplane.op_ranking(trace, 10)) <= 10


def test_idle_gaps_go_to_the_host_span_that_covers_them(recorded):
    trace, lo, hi = recorded
    gaps = dict(xplane.idle_gaps(trace, lo, hi))
    # the host slept 5 x ~50.7 ms between chains; that is where the gaps are
    assert max(gaps, key=gaps.get) == "fixture.sleep"
    assert 0.24 < gaps["fixture.sleep"] < 0.26
    total_idle = (hi - lo) / 1e9 - xplane.busy_seconds(trace)
    assert abs(sum(gaps.values()) - total_idle) < 1e-4


def test_the_innermost_of_equally_covering_spans_gets_the_gap():
    trace = {"devices": [{"ops": [["a", 0, 10], ["b", 50, 10]], "modules": []}],
             "host": [["outer", 0, 100], ["inner", 10, 40]]}
    assert xplane.idle_gaps(trace, 0, 100, bubble_ns=0) == [["inner", 4e-8], ["outer", 4e-8]]
    none = {"devices": [{"ops": [["a", 0, 10]], "modules": []}], "host": []}
    assert xplane.idle_gaps(none, 0, 30, bubble_ns=0) == [["(no span)", 2e-8]]


def test_short_gaps_between_instructions_are_not_laid_at_the_hosts_door():
    trace = {"devices": [{"ops": [["a", 0, 1000], ["b", 1100, 1000], ["c", 90000, 10]],
                          "modules": []}],
             "host": [["sched.step", 0, 100000]]}
    gaps = dict(xplane.idle_gaps(trace, 0, 90010))
    assert gaps == {"sched.step": pytest.approx(87900 / 1e9),
                    "(between ops)": pytest.approx(100 / 1e9)}


def test_the_pallas_kernel_is_found_by_its_custom_call_target(recorded):
    trace, _, _ = recorded
    secs, n = xplane.matching_seconds(trace["devices"][0]["ops"], "tpu_custom_call")
    assert n == 1 and abs(secs - 95.86e-6) < 1e-9


def test_short_name_drops_the_serial_number_and_keeps_the_shape():
    text = ("%multiply_reduce_fusion.14 = bf16[32,14336]{1,0:T(8,128)(2,1)} "
            "fusion(bf16[32,1792]{1,0} %x), kind=kLoop")
    assert xplane.short_name(text) == "multiply_reduce_fusion bf16[32,14336]"
    assert xplane.short_name("%copy-done = bf16[1,8]{1,0} copy-done(%c)") == "copy-done bf16[1,8]"
    assert xplane.short_name("%all-reduce-start.3 = (f32[8]{0}, f32[8]{0}) all-reduce-start(%g)") == "all-reduce-start f32[8]"
    assert xplane.short_name("no equals sign") == "no equals sign"


def test_collective_share_counts_start_done_and_fused_forms_once():
    ops = [
        ["%fusion.1 = f32[8]{0} fusion(%a)", 0, 100],
        ["%all-reduce-start.1 = f32[8]{0} all-reduce-start(%g)", 100, 10],
        ["%fusion.2 = f32[8]{0} fusion(%b)", 110, 50],
        ["%all-reduce-done.1 = f32[8]{0} all-reduce-done(%s)", 160, 40],
        ["%all-gather.7 = f32[16]{0} all-gather(%p)", 200, 30],
        ["%reduce-scatter.2 = f32[4]{0} reduce-scatter(%q)", 220, 30],  # overlaps
        ["%reduce.9 = f32[] reduce(%r)", 250, 50],  # not a collective
    ]
    dev = {"ops": ops, "modules": []}
    assert xplane.collective_seconds(dev) == pytest.approx((10 + 40 + 50) / 1e9)
    assert xplane.collective_seconds({"ops": ops[:1], "modules": []}) == 0.0


def test_busy_is_averaged_over_the_devices_used():
    trace = {"devices": [{"ops": [["a", 0, 2e9]], "modules": []},
                         {"ops": [["a", 0, 1e9]], "modules": []}], "host": []}
    assert xplane.busy_seconds(trace) == 1.5
    assert xplane.busy_seconds({"devices": [], "host": []}) == 0.0


def test_attn_roofline_counts_cut_steps_by_their_device_time():
    from types import SimpleNamespace

    from benchmark import flops
    from benchmark.readers import attn_roofline

    cfg = dict(heads=8, dim_head=64, seq_len=8192, window_size=512, depth=12)
    traffic = dict(micro_batch=16, grad_accum=2)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    cost = flops.local_attention_ops_bytes(bh=128, n=8192, d=64, w=512)
    least = 24 * (cost["fwd_flops"] + cost["bwd_flops"]) / 197e12  # compute-bound
    # one whole step of 2 s and half a step; kernels take a tenth of each
    modules = [["jit_train_step(1)", 0, 1e9], ["jit_train_step(1)", 1e9, 2e9]]
    ops = [["%k.1 = bf16[1]{0} custom-call(%q), custom_call_target=\"tpu_custom_call\"", 0, 1e8],
           ["%k.2 = bf16[1]{0} custom-call(%q), custom_call_target=\"tpu_custom_call\"", 1e9, 2e8],
           ["%fusion.1 = bf16[1]{0} fusion(%q)", 2e9, 1e8]]
    run = SimpleNamespace(
        trace={"devices": [{"ops": ops, "modules": modules}], "host": []},
        peak=peak, config=cfg, traffic=traffic, notes={},
    )
    spec = {"match": "tpu_custom_call", "step_match": "^jit_train_step"}
    share = attn_roofline.read(run, spec)
    assert share == pytest.approx(100 * least / (0.3 / 1.5))
    assert run.notes["attn_roofline"]["bound"] == "compute"
    run.trace = None
    assert attn_roofline.read(run, spec) is None


def test_a_loop_instruction_does_not_count_its_body_twice():
    ops = [["%while.1 = s32[] while(%t)", 0, 100],
           ["%fusion.1 = f32[8]{0} fusion(%a)", 10, 30],
           ["%fusion.2 = f32[8]{0} fusion(%a)", 40, 50],
           ["%copy.1 = f32[8]{0} copy(%a)", 100, 25]]
    assert dict(xplane.self_times(ops)) == {
        ops[0][0]: 20, ops[1][0]: 30, ops[2][0]: 50, ops[3][0]: 25}
    trace = {"devices": [{"ops": ops, "modules": []}], "host": []}
    assert xplane.op_ranking(trace, 2) == [["fusion f32[8]", 8e-8], ["copy f32[8]", 2.5e-8]]
    assert xplane.busy_seconds(trace) == pytest.approx(125 / 1e9)


def test_program_time_is_the_median_execution_over_the_named_parameter():
    from types import SimpleNamespace

    from benchmark.readers import trace_program

    modules = [["jit__prefill_chunk(7)", 0, 40e6], ["jit__prefill_chunk(7)", 1e8, 69e6],
               ["jit__prefill_chunk(7)", 2e8, 69e6], ["jit__decode_step(3)", 3e8, 24.5e6]]
    run = SimpleNamespace(trace={"devices": [{"ops": [], "modules": modules}], "host": []},
                          traffic={"prefill_chunk": 16})
    per_tok = trace_program.read(run, {"match": "^jit__prefill_chunk", "per": "prefill_chunk"})
    assert per_tok == pytest.approx(69.0 / 16)
    assert trace_program.read(run, {"match": "^jit__decode_step"}) == pytest.approx(24.5)
    assert trace_program.read(run, {"match": "^jit_nothing"}) is None
