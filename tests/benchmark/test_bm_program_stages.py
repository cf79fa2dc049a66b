"""The readers of the program's own stages and compile counters
(benchmark/readers/program_stages.py, compile_counters.py): the ring's
arithmetic on a hand-made ring, the trace's on the recorded one-chip
trace and on a hand-made step, and nothing to read where the data is
missing."""

import json
import statistics
import types
from pathlib import Path

import pytest

import bm_floor
from benchmark import manifest, xplane
from benchmark.readers import compile_counters, program_stages as ps

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = ROOT / "benchmark" / "fixtures" / "trace_v5e_one_chip.json"
NEW = list(bm_floor.NEW)  # PR 26's metrics, less the one PR 32 retired
RING = [n for n in NEW if n.split(".")[0] != "env"]  # read by program_stages
PER_LAYER = [m["name"] for m in bm_floor.load(ROOT)["per_layer"]]


def spec(name):
    return json.loads((ROOT / "benchmark" / "metrics" / f"{name}.json").read_text())


def ring():
    """Two steps as the scheduler's ring holds them: (seq, parent, name,
    t0, dur, thread). Step 1 admits a request and feeds a chunk; step 2
    is a plain decode step with an idle admission."""
    return [
        # step 1: seq 0, 10.000 .. 10.100
        (2, 1, "serve/prepare", 10.001, 0.010, 7),
        (1, 0, "serve/admit", 10.000, 0.012, 7),
        (4, 3, "serve/prefill_dispatch", 10.013, 0.008, 7),
        (5, 3, "serve/prefill_finish", 10.021, 0.004, 7),
        (3, 0, "serve/prefill_chunk", 10.012, 0.013, 7),
        (6, 0, "serve/admit", 10.025, 0.001, 7),
        (8, 7, "serve/decode_dispatch", 10.026, 0.002, 7),
        (9, 7, "serve/decode_fetch", 10.028, 0.065, 7),
        (7, 0, "serve/decode", 10.026, 0.068, 7),
        (10, 0, "serve/emit", 10.094, 0.002, 7),
        (11, 0, "serve/journal", 10.096, 0.003, 7),
        (0, None, "serve/step", 10.000, 0.100, 7),
        # step 2: seq 20, 10.101 .. 10.131
        (21, 20, "serve/admit", 10.101, 0.001, 7),
        (23, 22, "serve/decode_dispatch", 10.102, 0.002, 7),
        (24, 22, "serve/decode_fetch", 10.104, 0.023, 7),
        (22, 20, "serve/decode", 10.102, 0.025, 7),
        (25, 20, "serve/emit", 10.127, 0.001, 7),
        (26, 20, "serve/journal", 10.128, 0.002, 7),
        (20, None, "serve/step", 10.101, 0.030, 7),
    ]


def fake_run(records=None, trace=None, window=None, tmp_path=None):
    data = {"records": records or [], "table": ps.ring_table(records or []),
            "trace": trace, "idle": {}}
    if trace is not None:
        data["idle"] = ps.idle_by_stage(trace, *window)
    return types.SimpleNamespace(_program_stages=data, notes={},
                                 trace_window=window)


def test_ring_table_counts_calls_and_takes_children_off_self_time():
    table = ps.ring_table(ring())
    assert table["serve/step"]["calls"] == 2
    assert table["serve/step"]["total_s"] == pytest.approx(0.130)
    # step 1: 0.100 - admit 0.012 - chunk 0.013 - admit 0.001 - decode 0.068
    #         - emit 0.002 - journal 0.003 = 0.001; step 2: 0.030 - 0.029
    assert table["serve/step"]["self_s"] == pytest.approx(0.002)
    assert table["serve/admit"]["calls"] == 3
    assert table["serve/admit"]["self_s"] == pytest.approx(0.002 + 0.001 + 0.001)
    assert table["serve/decode"]["self_s"] == pytest.approx(0.001)
    assert table["serve/prefill_chunk"]["self_s"] == pytest.approx(0.001)
    assert table["serve/emit"]["self_s"] == table["serve/emit"]["total_s"]


def test_the_ring_metrics_divide_by_steps_or_reduce_over_the_calls():
    run = fake_run(ring())
    assert ps.read(run, spec("sched.emit_ms_per_step")) == pytest.approx(1.5)
    assert ps.read(run, spec("sched.journal_ms_per_step")) == pytest.approx(2.5)
    # one admission of three started a prefill: 12 ms, 10 of them the
    # engine's serve/prepare, which has a metric of its own
    assert ps.read(run, spec("sched.admit_ms")) == pytest.approx(2.0)
    assert ps.read(run, spec("engine.prepare_ms")) == pytest.approx(10.0)
    # each engine stage is read from itself, not from the span around it
    assert ps.read(run, spec("engine.prefill_dispatch_ms")) == pytest.approx(8.0)
    assert ps.read(run, spec("engine.prefill_finish_ms")) == pytest.approx(4.0)


def test_calls_take_the_stat_the_child_and_the_self_time_the_spec_names():
    records = ring() + [
        (31, 30, "serve/prefill_dispatch", 10.2, 0.001, 7),
        (30, None, "serve/prefill_chunk", 10.2, 0.003, 7),
        (33, 32, "serve/prefill_dispatch", 10.3, 0.003, 7),
        (32, None, "serve/prefill_chunk", 10.3, 0.005, 7),
        (34, None, "serve/prefill_chunk", 10.4, 0.500, 7),  # fed nothing
    ]
    run = fake_run(records)
    sp = spec("engine.prefill_dispatch_ms")
    assert ps.read(run, sp) == pytest.approx(1000 * statistics.median([0.008, 0.001, 0.003]))
    assert ps.read(run, {**sp, "stat": "mean"}) == pytest.approx(4.0)
    chunk = {**sp, "stage": "serve/prefill_chunk", "stat": "mean"}
    assert ps.read(run, chunk) == pytest.approx(1000 * (0.013 + 0.003 + 0.005 + 0.5) / 4)
    fed = {**chunk, "child": "serve/prefill_dispatch"}
    assert ps.read(run, fed) == pytest.approx(7.0)
    assert ps.read(run, {**fed, "self": True}) == pytest.approx(1000 * (0.001 + 0.002 + 0.002) / 3)


def test_the_ring_is_read_by_position_so_a_longer_tuple_reads_the_same():
    longer = [r + ("some-later-field",) for r in ring()]
    for name in ("sched.emit_ms_per_step", "sched.admit_ms", "engine.prepare_ms"):
        assert ps.read(fake_run(longer), spec(name)) == ps.read(fake_run(ring()), spec(name))


def test_score_batches_count_collate_write_and_the_journal_line():
    records = []
    for b in range(3):
        t = 5.0 + 2 * b
        records += [(5 * b, None, "score/collate", t, 0.002, 1),
                    (5 * b + 1, None, "score/step", t + 0.002, 0.010, 1),
                    (5 * b + 2, None, "score/fetch", t + 0.012, 1.9, 1),
                    (5 * b + 3, None, "score/write", t + 1.912, 0.027, 1),
                    (5 * b + 4, None, "score/journal", t + 1.939, 0.001, 1)]
    got = ps.read(fake_run(records), spec("score.host_ms_per_batch"))
    assert got == pytest.approx(30.0)


def test_nothing_to_read_gives_none_not_an_error():
    empty = fake_run([])
    for name in RING:
        assert ps.read(empty, spec(name)) is None, name
    # a ring but no trace (a CPU rehearsal): the trace metrics read nothing
    ringed = fake_run(ring())
    for name in ("sched.idle_ms_per_step", "engine.idle_ms_per_step"):
        assert ps.read(ringed, spec(name)) is None
    # a trace without a device plane, likewise
    hostonly = fake_run(ring(), {"devices": [], "host": [["serve/step", 0, 10]]}, (0, 10))
    assert ps.read(hostonly, spec("sched.idle_ms_per_step")) is None
    with pytest.raises(ValueError):
        ps.read(ringed, {"what": "no-such-reduction"})


def test_collect_reads_nothing_from_a_program_without_the_ring(monkeypatch, tmp_path):
    """The parent of this PR has no ``Telemetry.stages``: every metric is
    left out, nothing raises, no table is written."""
    from progen_tpu.telemetry import spans

    monkeypatch.delattr(spans.Telemetry, "stages")
    run = types.SimpleNamespace(t_open=1.0, t_close=2.0, trace_window=None,
                                out_dir=tmp_path, notes={})
    data = ps.collect(run)
    assert data["records"] == [] and data["table"] == {}
    assert ps.read(run, spec("sched.emit_ms_per_step")) is None
    assert not (tmp_path / "program_stages.json").exists()


def test_collect_takes_the_window_from_the_ring_and_writes_the_table(tmp_path):
    import time

    from progen_tpu.telemetry.spans import stage

    with stage("serve/step"):  # before the window: not counted
        pass
    t_open = time.perf_counter()
    for _ in range(3):
        with stage("serve/step"):
            with stage("serve/emit"):
                pass
    t_close = time.perf_counter()
    with stage("serve/step"):  # after it
        pass
    run = types.SimpleNamespace(
        t_open=t_open, t_close=t_close, trace_window=None, out_dir=tmp_path,
        notes={}, seed=5, cell={"name": "large.gen-closed"},
    )
    assert ps.read(run, spec("sched.emit_ms_per_step")) > 0
    assert run._program_stages["table"]["serve/step"]["calls"] == 3
    out = json.loads((tmp_path / "program_stages.json").read_text())
    assert out["stages"]["serve/emit"]["calls"] == 3
    assert out["stages"]["serve/step"]["self_s"] <= out["stages"]["serve/step"]["total_s"]
    assert out["traced_window_s"] is None and out["idle_total_s"] == 0
    assert ps.collect(run) is run._program_stages  # gathered once a run


# ----- the trace's arithmetic ------------------------------------------


def test_innermost_segments_name_each_instant_by_the_latest_started():
    host = [["serve/step", 0, 100], ["serve/decode", 10, 60],
            ["serve/decode_fetch", 30, 40], ["serve/emit", 75, 10],
            ["serve/step", 120, 30]]
    assert ps.innermost_segments(host) == [
        (0, 10, "serve/step"), (10, 30, "serve/decode"),
        (30, 70, "serve/decode_fetch"), (70, 75, "serve/step"),
        (75, 85, "serve/emit"), (85, 100, "serve/step"),
        (120, 150, "serve/step"),
    ]


def step_trace():
    """One decode step by hand, in ns: the device runs 20_000..80_000 and
    again from 130_000; the host is in decode_fetch until 90_000, emits,
    journals, leaves the step at 110_000, and the next step dispatches at
    115_000."""
    k = 1000.0
    return {
        "devices": [{
            "ops": [["%fusion.1 = f32[8]", 20 * k, 30 * k],
                    ["%fusion.2 = f32[8]", 50 * k + 1000, 29 * k],  # a 1 us bubble
                    ["%fusion.1 = f32[8]", 130 * k, 20 * k]],
            "modules": [["jit__decode_step(1)", 20 * k, 60 * k],
                        ["jit__decode_step(1)", 130 * k, 20 * k]],
        }],
        "host": [["serve/step", 10 * k, 100 * k],
                 ["serve/decode", 12 * k, 78 * k],
                 ["serve/decode_dispatch", 12 * k, 10 * k],
                 ["serve/decode_fetch", 22 * k, 68 * k],
                 ["serve/emit", 91 * k, 8 * k],
                 ["serve/journal", 100 * k, 8 * k],
                 ["serve/step", 115 * k, 35 * k],
                 ["serve/decode", 116 * k, 34 * k],
                 ["serve/decode_dispatch", 116 * k, 12 * k]],
    }


def test_idle_is_split_among_the_innermost_stages_and_adds_up():
    trace, lo, hi = step_trace(), 10_000.0, 150_000.0
    idle = ps.idle_by_stage(trace, lo, hi)
    assert idle["(between ops)"] == pytest.approx(1e-6)
    # before the device starts: 2 us of step, 8 us of decode_dispatch
    # after it stops at 80: fetch to 90, step 90-91, emit 91-99, step
    # 99-100, journal 100-108, step 108-110, nobody 110-115, step
    # 115-116, dispatch 116-128, decode 128-130
    assert idle["serve/decode_dispatch"] == pytest.approx((8 + 12) * 1e-6)
    assert idle["serve/decode_fetch"] == pytest.approx(10e-6)
    assert idle["serve/emit"] == pytest.approx(8e-6)
    assert idle["serve/journal"] == pytest.approx(8e-6)
    assert idle["serve/step"] == pytest.approx((2 + 1 + 1 + 2 + 1) * 1e-6)
    assert idle["serve/decode"] == pytest.approx(2e-6)
    assert idle["(no span)"] == pytest.approx(5e-6)
    busy = sum(e - s for s, e in xplane.union(trace["devices"][0]["ops"]))
    assert sum(idle.values()) == pytest.approx((hi - lo - busy) / 1e9)

    run = fake_run(ring(), trace, (lo, hi))
    sched = ps.read(run, spec("sched.idle_ms_per_step"))
    engine = ps.read(run, spec("engine.idle_ms_per_step"))
    # two serve/step events in the traced part
    assert sched == pytest.approx(1000 * (7 + 8 + 8) * 1e-6 / 2)
    assert engine == pytest.approx(1000 * (20 + 10 + 2) * 1e-6 / 2)
    rest = idle["(no span)"] + idle["(between ops)"]
    assert (sched + engine) * 2 / 1000 + rest == pytest.approx(sum(idle.values()))


def test_decode_overhead_pairs_each_stage_with_its_execution():
    trace = step_trace()
    over = ps.overheads(trace, "serve/decode", "^jit__decode_step")
    # 78 us of host around 60 us of device; 34 around 20
    assert over["seconds"] == pytest.approx([18e-6, 14e-6])
    # no execution starts less than 8 us after its stage, and the second
    # ends with its stage
    assert over["clock_bounds_ms"] == pytest.approx([-0.008, 0.0])
    # what the retired engine.decode_overhead_ms read: their median, in ms
    assert 1000.0 * statistics.median(over["seconds"]) == pytest.approx(0.016)
    assert ps.overheads(trace, "serve/decode", "^jit_nothing")["seconds"] == []
    # no metric names the reduction any more
    with pytest.raises(ValueError):
        ps.read(fake_run(ring(), trace, (10_000.0, 150_000.0)),
                {"what": "overhead", "stage": "serve/decode",
                 "program": "^jit__decode_step"})
    assert not (ROOT / "benchmark/metrics/engine.decode_overhead_ms.json").exists()


def test_on_the_recorded_trace_the_idle_goes_to_the_sleeping_host():
    t = json.loads(FIXTURE.read_text())
    win = next(e for e in t["host"] if e[0] == "bench.window")
    lo, hi = win[1], win[1] + win[2]
    trace = xplane.clip(t, lo, hi)
    trace["host"] = [e for e in trace["host"] if e[0] != "bench.window"]
    idle = ps.idle_by_stage(trace, lo, hi)
    window = (hi - lo) / 1e9
    assert sum(idle.values()) == pytest.approx(window - xplane.busy_seconds(trace))
    assert idle["fixture.sleep"] > 0.9 * sum(idle.values())
    # the same gaps, whole, as the harness's own attribution names them
    whole = dict(xplane.idle_gaps(trace, lo, hi))
    assert whole["fixture.sleep"] == pytest.approx(idle["fixture.sleep"], rel=0.05)
    # each host span around a chain is ~1 ms longer than the chain on the
    # device, and the device's clock runs 1.0-1.8 ms behind the host's
    # (on the whole recording: the window cuts the first chain)
    over = ps.overheads(t, "fixture.busy", "^jit_chain")
    assert len(over["seconds"]) == 5
    assert all(0.0005 < s < 0.0015 for s in over["seconds"])
    assert 0.9 < over["clock_bounds_ms"][0] < over["clock_bounds_ms"][1] < 1.9


# ----- compile counters, and the manifest ------------------------------


def test_compile_counters_read_what_came_before_the_window():
    import time

    import jax
    import jax.numpy as jnp

    from progen_tpu.telemetry import compiles

    compiles.install()
    # progen: ignore[PGL004] - a fresh compile is the point
    jax.jit(lambda x: x - 41.5)(jnp.ones((3,))).block_until_ready()
    t_open = time.perf_counter()
    # progen: ignore[PGL004] - a fresh compile is the point
    jax.jit(lambda x: x - 42.5)(jnp.ones((3,))).block_until_ready()
    run = types.SimpleNamespace(t_open=t_open)
    seconds = compile_counters.read(run, spec("env.compile_load_s"))
    want = compiles.snapshot(until=t_open)["backend_compile"]
    assert seconds == want["seconds"] > 0
    assert want["count"] < compiles.snapshot()["backend_compile"]["count"]
    assert compile_counters.read(run, spec("env.cache_misses")) == \
        compiles.snapshot(until=t_open)["cache_misses"]["count"]
    assert compile_counters.read(types.SimpleNamespace(t_open=None),
                                 spec("env.cache_misses")) is None


def test_compile_counters_that_never_listened_read_nothing_not_zero(monkeypatch):
    from progen_tpu.telemetry import compiles

    monkeypatch.setattr(compiles, "_installed", False)
    run = types.SimpleNamespace(t_open=1e18)
    for name in ("env.compile_load_s", "env.cache_misses"):
        assert compile_counters.read(run, spec(name)) is None


def test_compile_counters_read_nothing_from_a_program_without_them(monkeypatch):
    import sys

    import progen_tpu.telemetry

    monkeypatch.delattr(progen_tpu.telemetry, "compiles")
    monkeypatch.setitem(sys.modules, "progen_tpu.telemetry.compiles", None)
    run = types.SimpleNamespace(t_open=1.0)
    assert compile_counters.read(run, spec("env.compile_load_s")) is None


def test_the_manifest_is_clean_and_holds_the_accepted_metrics_by_name():
    """PR 26's metrics (eleven since PR 32 retired engine.decode_overhead_ms:
    ``bm_floor.NEW`` says why) and PR 28's five are present by name, each
    with the source, layer, moves and better it was accepted with, in
    the order they were accepted in; how many others stand before,
    between or after them is not this test's business."""
    assert manifest.check(ROOT) == []
    assert bm_floor.accepted_metrics(ROOT) == []
    assert len(NEW) == 11 and bm_floor.RETIRED == ["engine.decode_overhead_ms"]
    by = {m["name"]: m for m in bm_floor.load(ROOT)["per_layer"]}
    assert "engine.decode_overhead_ms" not in by
    for name in RING:
        first = "large.score-batch" if name.startswith("score.") else "large.gen-closed"
        assert by[name]["workloads"][0] == first  # more may follow
        assert by[name]["source"] == "program_span"
    for name in ("env.compile_load_s", "env.cache_misses"):
        assert "workloads" not in by[name] and by[name]["moves"] == "setup_s"
        assert by[name]["source"] == "program_counter"
    assert all(by[n]["better"] == "lower" for n in NEW)
    # a metric is filed under the layer whose code its stage times
    assert {n: by[n]["layer"] for n in ("sched.admit_ms", "engine.prepare_ms")} == {
        "sched.admit_ms": "scheduler", "engine.prepare_ms": "engine"}


@pytest.mark.parametrize("name", PER_LAYER)  # whatever the manifest holds
def test_each_new_metric_has_a_file_that_names_its_reader(name):
    sp = spec(name)
    assert sp["name"] == name and sp["kind"] == "per_layer"
    assert (ROOT / "benchmark" / "readers" / f"{sp['reader']}.py").is_file()
    if sp["reader"] != "program_stages":
        return  # the harness's own spans, counters or the trace
    taken = {"bm.window", "sched.step", "sched.submit", "engine.decode_step",
             "engine.prefill", "journal", "score.step", "score.write",
             "train.feed", "train.step", "train.fence"}
    used = set(sp.get("num", [])) | set(sp.get("stages", [])) | {
        sp.get("den"), sp.get("stage"), sp.get("child")} - {None}
    assert not used & taken  # program names never collide with the harness's
