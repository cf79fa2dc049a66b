"""Telemetry layer: spans, goodput ledger, stall watchdog, HBM gauges,
Prometheus exposition — plus the crash-safety contract of the jsonl
sinks (a SIGKILL'd run leaves fully parseable files) and the end-to-end
acceptance: a CPU train run emits span events and a goodput record
whose buckets sum to wall clock with >=95% attributed."""

import io
import json
import os
import signal
import subprocess
import sys
import textwrap
import threading
import time
import urllib.request
from pathlib import Path

import pytest

from progen_tpu.telemetry import (
    BUCKETS,
    EventLog,
    GoodputLedger,
    StallWatchdog,
    Telemetry,
    hbm_gauges,
    prometheus_text,
    start_prometheus_server,
    step_print,
    write_prometheus,
)


# ---------------------------------------------------------------- spans


def test_span_emits_begin_end_records(tmp_path):
    log = EventLog(tmp_path / "events.jsonl")
    tel = Telemetry(sink=log.emit)
    with tel.span("ckpt/save", step=7):
        pass
    log.close()
    recs = [
        json.loads(line) for line in (tmp_path / "events.jsonl").read_text().splitlines()
    ]
    assert [r["ev"] for r in recs] == ["B", "E"]
    assert all(r["span"] == "ckpt/save" and r["step"] == 7 for r in recs)
    assert recs[0]["id"] == recs[1]["id"]
    assert recs[1]["dur_s"] >= 0.0


def test_open_span_visible_until_exit():
    tel = Telemetry()
    with tel.span("outer"):
        with tel.span("inner"):
            names = [r["span"] for r in tel.open_spans()]
            assert names == ["outer", "inner"]
        assert [r["span"] for r in tel.open_spans()] == ["outer"]
    assert tel.open_spans() == []
    assert [r["span"] for r in tel.recent_spans()] == ["inner", "outer"]


def test_span_closes_on_exception():
    tel = Telemetry()
    with pytest.raises(RuntimeError):
        with tel.span("doomed"):
            raise RuntimeError("boom")
    assert tel.open_spans() == []
    assert tel.recent_spans()[-1]["span"] == "doomed"


def test_broken_sink_detaches_instead_of_raising(tmp_path):
    log = EventLog(tmp_path / "ev.jsonl")
    tel = Telemetry(sink=log.emit)
    log._f.close()  # simulate the fd dying under the sink
    with tel.span("survives"):  # must not raise
        pass
    assert tel.recent_spans()[-1]["span"] == "survives"


def test_step_print_format(capsys):
    step_print(42, "loss: 1.2345")
    out = capsys.readouterr().out
    assert "step 42]" in out and "loss: 1.2345" in out


# -------------------------------------------------------------- goodput


def test_goodput_buckets_sum_to_wallclock():
    t = {"now": 0.0}
    ledger = GoodputLedger(clock=lambda: t["now"])
    for bucket, dur in (
        ("compile", 5.0), ("step", 30.0), ("data", 2.0),
        ("checkpoint", 3.0), ("eval", 1.5), ("sample", 1.0), ("log", 0.5),
    ):
        with ledger.track(bucket):
            t["now"] += dur
    t["now"] += 2.0  # unattributed tail
    rep = ledger.report()
    total = sum(v for k, v in rep.items() if k.startswith("bucket_s/"))
    assert total == pytest.approx(rep["wall_s"], abs=1e-6)
    assert rep["bucket_s/other"] == pytest.approx(2.0)
    assert rep["goodput_pct"] == pytest.approx(100 * 30.0 / 45.0, abs=0.01)
    assert rep["coverage_pct"] == pytest.approx(100 * 43.0 / 45.0, abs=0.01)
    assert set(BUCKETS) == {
        "compile", "step", "data", "checkpoint", "eval", "sample", "log"
    }


def test_goodput_track_handle_reports_seconds():
    t = {"now": 0.0}
    ledger = GoodputLedger(clock=lambda: t["now"])
    with ledger.track("checkpoint") as tr:
        t["now"] += 4.0
    assert tr.seconds == pytest.approx(4.0)


# ------------------------------------------------------------- watchdog


def test_watchdog_fires_with_stack_dump_and_spans():
    buf = io.StringIO()
    tel = Telemetry()
    reports = []
    with tel.span("train/step"):
        wd = StallWatchdog(
            0.2, file=buf, telemetry=tel, on_stall=reports.append,
            poll_s=0.05,
        )
        with wd:
            deadline = time.time() + 5.0
            while not wd.fired and time.time() < deadline:
                time.sleep(0.05)
    assert wd.fired and wd.fire_count == 1  # once per stall, not per poll
    out = buf.getvalue()
    assert "stall-watchdog" in out
    assert "train/step" in out
    # faulthandler's all-thread dump names this (the main) thread
    assert "Current thread" in out or "Thread" in out
    assert reports and reports[0]["open_spans"][0]["span"] == "train/step"


def test_watchdog_does_not_fire_while_beaten():
    buf = io.StringIO()
    wd = StallWatchdog(0.4, file=buf, telemetry=Telemetry(), poll_s=0.05)
    with wd:
        for _ in range(12):  # 0.6s of steady heartbeats < deadline apart
            wd.beat()
            time.sleep(0.05)
    assert not wd.fired
    assert buf.getvalue() == ""


def test_watchdog_rejects_nonpositive_deadline():
    with pytest.raises(ValueError):
        StallWatchdog(0)


# ------------------------------------------------------------------ hbm


def test_hbm_gauges_degrade_to_empty_or_gb_floats():
    g = hbm_gauges()  # CPU backend in-suite: usually {}
    assert isinstance(g, dict)
    for k, v in g.items():
        assert k.startswith("hbm/") and isinstance(v, float)


def test_hbm_gauges_from_fake_device():
    class Dev:
        def memory_stats(self):
            return {
                "bytes_in_use": 2**30,
                "peak_bytes_in_use": 2 * 2**30,
                "bytes_limit": 4 * 2**30,
            }

    g = hbm_gauges(Dev())
    assert g["hbm/in_use_gb"] == 1.0
    assert g["hbm/peak_gb"] == 2.0
    assert g["hbm/limit_gb"] == 4.0
    assert g["hbm/used_pct"] == 25.0


# ----------------------------------------------------------- prometheus


def _metrics_with_tail():
    from progen_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.inc("requests_completed", 100)
    m.set_gauge("queue_depth", 3)
    for i in range(100):
        m.observe("ttft_s", 0.01 * (i + 1))
    m.add_time("decode_time_s", 2.0)
    m.inc("decode_tokens", 500)
    return m


def test_prometheus_text_format():
    text = prometheus_text(_metrics_with_tail())
    assert "# TYPE progen_serve_requests_completed_total counter" in text
    assert "# TYPE progen_serve_queue_depth gauge" in text
    assert "# TYPE progen_serve_ttft_seconds summary" in text
    assert 'progen_serve_ttft_seconds{quantile="0.99"}' in text
    assert "progen_serve_ttft_seconds_count 100" in text
    assert "progen_serve_decode_tokens_per_s 250" in text
    assert text.endswith("\n")


def test_write_prometheus_atomic(tmp_path):
    p = tmp_path / "metrics" / "serve.prom"
    write_prometheus(p, "a 1\n")
    write_prometheus(p, "a 2\n")
    assert p.read_text() == "a 2\n"
    assert not p.with_name(p.name + ".tmp").exists()


def test_prometheus_http_server():
    m = _metrics_with_tail()
    srv = start_prometheus_server(lambda: prometheus_text(m), port=0)
    try:
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        ).read().decode()
        assert 'progen_serve_ttft_seconds{quantile="0.99"}' in body
    finally:
        srv.shutdown()


# --------------------------------------------- serving metrics quantiles


def test_timing_reservoir_quantiles():
    from progen_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    for i in range(1000):
        m.observe("lat_s", float(i))  # > reservoir cap: sampled tail
    s = m.snapshot()
    assert s["lat_s_p50_s"] == pytest.approx(500, abs=100)
    assert s["lat_s_p95_s"] == pytest.approx(950, abs=60)
    assert s["lat_s_p99_s"] == pytest.approx(990, abs=40)
    assert s["lat_s_mean_s"] == pytest.approx(499.5)
    # pre-existing snapshot keys stay intact
    assert {"lat_s_min_s", "lat_s_max_s", "lat_s_count"} <= set(s)


def test_timing_quantiles_deterministic():
    from progen_tpu.serving.metrics import _Timing

    a, b = _Timing(), _Timing()
    for i in range(2000):
        a.observe(float(i))
        b.observe(float(i))
    assert a.quantile(0.99) == b.quantile(0.99)


def test_timing_stats_carry_mergeable_sum():
    # fleet averages are only mergeable from (sum, count) pairs — the
    # collector's aggregation depends on this key (see prometheus.py's
    # exposition contract)
    from progen_tpu.serving.metrics import _Timing

    t = _Timing()
    for v in (0.1, 0.2, 0.3):
        t.observe(v)
    s = t.stats()
    assert s["sum"] == pytest.approx(0.6)
    assert s["mean_s"] == pytest.approx(s["sum"] / s["count"])
    assert _Timing().stats()["sum"] == 0.0


def test_timing_merged_exact_moments_and_close_quantiles():
    from progen_tpu.serving.metrics import _Timing

    a, b, ref = _Timing(), _Timing(), _Timing()
    for i in range(1500):
        v = i / 1500.0  # fast source: [0, 1)
        a.observe(v)
        ref.observe(v)
    for i in range(500):
        v = 2.0 + i / 500.0  # slow source: [2, 3)
        b.observe(v)
        ref.observe(v)
    m = _Timing.merged([a, b])
    # moments merge exactly regardless of reservoir sampling
    assert m.count == ref.count == 2000
    assert m.sum == pytest.approx(ref.sum)
    assert m.min == ref.min and m.max == ref.max
    # quantiles merge approximately, tracking the combined stream: the
    # 3:1 count weighting must place p50 in the fast source's range
    # even though both reservoirs hold the same number of slots
    assert m.quantile(0.5) == pytest.approx(ref.quantile(0.5), abs=0.2)
    assert m.quantile(0.5) < 1.0
    assert m.quantile(0.95) == pytest.approx(ref.quantile(0.95), abs=0.25)
    assert m.quantile(0.95) > 2.0


def test_timing_merged_edge_cases():
    from progen_tpu.serving.metrics import _Timing

    assert _Timing.merged([]).count == 0
    empty = _Timing()
    solo = _Timing()
    for v in (0.5, 1.5):
        solo.observe(v)
    m = _Timing.merged([solo, empty])
    assert m.count == 2 and m.sum == pytest.approx(2.0)
    assert m.quantile(0.99) == solo.quantile(0.99)
    # merging is deterministic (seeded subsampling)
    big = [_Timing() for _ in range(3)]
    for j, t in enumerate(big):
        for i in range(400):
            t.observe(j + i / 400.0)
    q1 = _Timing.merged(big).quantile(0.95)
    q2 = _Timing.merged(big).quantile(0.95)
    assert q1 == q2


# ------------------------------------------------------ StepTimer fixes


def test_step_timer_exclude_removes_cadence_time(monkeypatch):
    from progen_tpu import profiling

    t = {"now": 0.0}
    monkeypatch.setattr(profiling.time, "perf_counter", lambda: t["now"])
    timer = profiling.StepTimer(
        n_chips=1, flops_per_tok=1, peak=1.0, warmup=0
    )
    timer.tick(10)  # arm
    t["now"] += 1.0
    assert timer.tick(10)["step_ms"] == pytest.approx(1000.0)
    # a 5s checkpoint between ticks must NOT count as step time
    t["now"] += 5.0
    timer.exclude(5.0)
    t["now"] += 1.0
    assert timer.tick(10)["step_ms"] == pytest.approx(1000.0)
    # exclusion is consumed; the next tick is unaffected
    t["now"] += 2.0
    assert timer.tick(10)["step_ms"] == pytest.approx(2000.0)


# ------------------------------------------------- jsonl crash-safety


_KILL_SCRIPT = textwrap.dedent("""
    import os, sys
    sys.path.insert(0, {repo!r})
    from progen_tpu.tracking import JsonlTracker
    from progen_tpu import telemetry

    tr = JsonlTracker("proj", "runA", {dir!r})
    telemetry.configure(sink=tr.log_event)
    i = 0
    while True:
        tr.log({{"loss": 1.0, "i": i}}, step=i)
        with telemetry.span("work", i=i):
            pass
        i += 1
        if i == 50:
            print("GO", flush=True)
""")


def test_sigkill_leaves_parseable_jsonl(tmp_path):
    """SIGKILL mid-write may truncate the LAST line of each file; every
    complete line must parse and earlier records must all be present."""
    repo = str(Path(__file__).resolve().parent.parent)
    proc = subprocess.Popen(
        [sys.executable, "-c",
         _KILL_SCRIPT.format(repo=repo, dir=str(tmp_path))],
        stdout=subprocess.PIPE,
    )
    assert proc.stdout.readline().strip() == b"GO"  # >=50 records written
    time.sleep(0.05)  # let it keep writing so the kill lands mid-stream
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait(timeout=10)

    for name, min_recs in (("metrics.jsonl", 50), ("events.jsonl", 100)):
        raw = (tmp_path / "proj" / "runA" / name).read_bytes()
        lines = raw.split(b"\n")
        complete, last = lines[:-1], lines[-1]
        recs = [json.loads(line) for line in complete if line.strip()]
        assert len(recs) >= min_recs, f"{name}: lost flushed records"
        # only the final (killed mid-write) line may be partial
        if last:
            with pytest.raises(json.JSONDecodeError):
                json.loads(last)


def test_tracker_log_event_writes_events_jsonl(tmp_path):
    from progen_tpu.tracking import JsonlTracker

    tr = JsonlTracker("proj", "runB", str(tmp_path))
    tr.log_event({"ev": "B", "span": "x"})
    tr.finish()
    recs = [
        json.loads(line)
        for line in (tmp_path / "proj" / "runB" / "events.jsonl")
        .read_text().splitlines()
    ]
    assert recs == [{"ev": "B", "span": "x"}]
    with pytest.raises(ValueError):
        tr.log_event({"ev": "E"})  # after finish: sink contract = raise


# -------------------------------------------- concurrent jsonl writers


def _hammer_jsonl(emit, n_threads=8, n_records=200):
    """N threads emit distinctive records concurrently; returns the
    barrier-released threads after joining them."""
    barrier = threading.Barrier(n_threads)

    def work(tid):
        barrier.wait()  # maximize interleaving pressure
        for i in range(n_records):
            emit({"ev": "x", "tid_": tid, "i": i, "pad": "p" * 64})

    threads = [
        threading.Thread(target=work, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()


def _assert_whole_lines(path, n_threads=8, n_records=200):
    recs = [json.loads(line) for line in path.read_text().splitlines()]
    assert len(recs) == n_threads * n_records  # nothing torn, nothing lost
    for t in range(n_threads):
        mine = [r["i"] for r in recs if r["tid_"] == t]
        assert mine == sorted(mine) and len(mine) == n_records


def test_eventlog_concurrent_emit_never_tears(tmp_path):
    log = EventLog(tmp_path / "ev.jsonl")
    _hammer_jsonl(log.emit)
    log.close()
    _assert_whole_lines(tmp_path / "ev.jsonl")


def test_tracker_log_event_concurrent_never_tears(tmp_path):
    """The watchdog thread, async-checkpoint paths, and retry hooks all
    emit through JsonlTracker.log_event while the train loop logs —
    every JSONL line must come out whole (satellite: concurrent-writer
    audit; JsonlTracker was the unlocked sink)."""
    from progen_tpu.tracking import JsonlTracker

    tr = JsonlTracker("proj", "runC", str(tmp_path))
    _hammer_jsonl(tr.log_event)
    tr.finish()
    _assert_whole_lines(tmp_path / "proj" / "runC" / "events.jsonl")


def test_tracker_log_concurrent_with_log_event(tmp_path):
    """metrics.jsonl and events.jsonl written simultaneously from
    different threads through one tracker: both files stay parseable."""
    from progen_tpu.tracking import JsonlTracker

    tr = JsonlTracker("proj", "runD", str(tmp_path))
    stop = threading.Event()

    def metrics_loop():
        i = 0
        while not stop.is_set():
            tr.log({"loss": 1.0, "i": i}, step=i)
            i += 1

    t = threading.Thread(target=metrics_loop)
    t.start()
    _hammer_jsonl(tr.log_event, n_threads=4, n_records=100)
    stop.set()
    t.join()
    tr.finish()
    _assert_whole_lines(
        tmp_path / "proj" / "runD" / "events.jsonl",
        n_threads=4, n_records=100,
    )
    for line in (
        (tmp_path / "proj" / "runD" / "metrics.jsonl")
        .read_text().splitlines()
    ):
        json.loads(line)


# --------------------------------------------- host/thread span tagging


def test_span_records_carry_pid_tid_thread(tmp_path):
    log = EventLog(tmp_path / "ev.jsonl")
    tel = Telemetry(sink=log.emit)
    with tel.span("tagged"):
        pass
    tel.emit({"ev": "retry", "label": "io"})
    log.close()
    recs = [
        json.loads(line)
        for line in (tmp_path / "ev.jsonl").read_text().splitlines()
    ]
    b, e, retry = recs
    assert b["pid"] == e["pid"] == retry["pid"] == 0  # single process
    assert b["tid"] == e["tid"] == threading.get_ident()
    assert b["thread"] == threading.current_thread().name
    # non-span records get the host tag without span structure
    assert "tid" not in retry


def test_host_index_is_zero_without_initialized_backend():
    from progen_tpu.telemetry import host_index

    assert host_index() == 0


# --------------------------------- prometheus formatting edge cases


def test_prometheus_fmt_nan_inf_gauges():
    """Prometheus text format spells non-finite floats NaN/+Inf/-Inf;
    the int-collapse fast path must not crash on them (satellite:
    float-formatting edge cases — an inf HBM limit or NaN loss gauge
    took the old renderer down with OverflowError/ValueError)."""
    text = prometheus_text({
        "counters": {},
        "gauges": {
            "bad_loss": float("nan"),
            "hbm_limit": float("inf"),
            "neg": float("-inf"),
        },
        "derived": {},
        "timings": {},
    })
    assert "progen_serve_bad_loss NaN" in text
    assert "progen_serve_hbm_limit +Inf" in text
    assert "progen_serve_neg -Inf" in text


def test_prometheus_name_sanitization():
    text = prometheus_text({
        "counters": {"hbm/in use(gb)": 1},
        "gauges": {"weird-name.pct": 2.5},
        "derived": {},
        "timings": {},
    })
    # every invalid char ([^a-zA-Z0-9_:]) collapses to _
    assert "progen_serve_hbm_in_use_gb__total 1" in text
    assert "progen_serve_weird_name_pct 2.5" in text
    # a name that would start with a digit (empty prefix) gets a _ guard
    bare = prometheus_text(
        {"counters": {}, "gauges": {"9lives": 1}, "derived": {},
         "timings": {}},
        prefix="",
    )
    assert "_9lives 1" in bare


def test_metrics_registry_counters_gauges_timings():
    from progen_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    reg.inc("retries", 0)  # declaration: present at zero
    reg.inc("retries")
    reg.set_gauge("goodput_pct", 87.5)
    for i in range(100):
        reg.observe("step_s", 0.01 * (i + 1))
    text = prometheus_text(reg, prefix="progen_train_")
    assert "progen_train_retries_total 1" in text
    assert "progen_train_goodput_pct 87.5" in text
    assert 'progen_train_step_seconds{quantile="0.99"}' in text
    assert "progen_train_step_seconds_count 100" in text
    snap = reg.snapshot()
    assert snap["retries"] == 1 and snap["step_s_count"] == 100
    reg.reset()
    assert reg.snapshot() == {}


def test_metrics_registry_thread_safe_inc():
    from progen_tpu.telemetry import MetricsRegistry

    reg = MetricsRegistry()
    threads = [
        threading.Thread(
            target=lambda: [reg.inc("n") for _ in range(1000)]
        )
        for _ in range(8)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.snapshot()["n"] == 8000


# --------------------------------------------------------------- stages


def test_stage_appends_one_tuple_with_its_parent_under_nesting():
    tel = Telemetry()
    t_before = time.perf_counter()
    with tel.span("serve/prefill_chunk", request_id="r7"):
        with tel.stage("serve/prefill_dispatch"):
            with tel.stage("serve/prefix_insert"):
                pass
        with tel.stage("serve/prefill_finish"):
            pass
    t_after = time.perf_counter()
    recs = tel.stages()
    # one tuple each, in order of completion: a child before its parent
    assert [r[2] for r in recs] == [
        "serve/prefix_insert", "serve/prefill_dispatch",
        "serve/prefill_finish", "serve/prefill_chunk",
    ]
    by_name = {r[2]: r for r in recs}
    chunk = by_name["serve/prefill_chunk"]
    assert chunk[1] is None and len(chunk) == 6  # attrs stay in the records
    assert by_name["serve/prefill_dispatch"][1] == chunk[0]
    assert by_name["serve/prefill_finish"][1] == chunk[0]
    assert by_name["serve/prefix_insert"][1] == by_name["serve/prefill_dispatch"][0]
    for seq, parent, name, t0, dur, tid in recs:
        assert t_before <= t0 <= t0 + dur <= t_after
        assert tid == threading.get_ident()
    assert len({r[0] for r in recs}) == 4  # seqs are unique


def test_stage_dur_can_be_read_once_it_has_exited():
    tel = Telemetry()
    with tel.stage("serve/decode") as st:
        assert not hasattr(st, "dur")  # no reading while it is open
    assert st.dur == tel.stages()[-1][4] >= 0.0


def test_stage_parents_are_per_thread():
    tel = Telemetry()
    inside = threading.Event()
    release = threading.Event()

    def other():
        with tel.stage("bg/outer"):
            inside.set()
            assert release.wait(timeout=10)
            with tel.stage("bg/inner"):
                pass

    t = threading.Thread(target=other)
    t.start()
    assert inside.wait(timeout=10)
    with tel.stage("main/outer"):  # opened while bg/outer is open
        with tel.stage("main/inner"):
            pass
    release.set()
    t.join(timeout=10)
    assert not t.is_alive()
    by_name = {r[2]: r for r in tel.stages()}
    assert by_name["main/outer"][1] is None and by_name["bg/outer"][1] is None
    assert by_name["main/inner"][1] == by_name["main/outer"][0]
    assert by_name["bg/inner"][1] == by_name["bg/outer"][0]
    assert by_name["bg/inner"][5] == by_name["bg/outer"][5] != by_name["main/outer"][5]


def test_stage_is_recorded_and_unwound_under_an_exception():
    tel = Telemetry()
    with pytest.raises(RuntimeError):
        with tel.stage("serve/step"):
            with tel.stage("serve/decode"):
                raise RuntimeError("boom")
    assert [r[2] for r in tel.stages()] == ["serve/decode", "serve/step"]
    with tel.stage("serve/step"):  # the stack unwound: a root again
        pass
    assert tel.stages()[-1][1] is None


def test_stage_writes_nothing_to_a_sink_or_a_tap():
    from progen_tpu.telemetry import spans

    seen, tapped = [], []
    tel = Telemetry(sink=seen.append)
    tap = tapped.append
    spans.EMIT_TAPS.append(tap)
    try:
        with tel.stage("serve/decode"):
            pass
    finally:
        spans.EMIT_TAPS.remove(tap)
    assert seen == [] and tapped == []
    assert tel.open_spans() == [] and tel.recent_spans() == []
    assert [r[2] for r in tel.stages()] == ["serve/decode"]


def test_span_still_writes_its_pair_and_now_lands_in_the_ring():
    seen = []
    tel = Telemetry(sink=seen.append)
    with tel.span("ckpt/save", step=3):
        with tel.stage("ckpt/write"):
            pass
    assert [(r["ev"], r["span"]) for r in seen] == [("B", "ckpt/save"),
                                                   ("E", "ckpt/save")]
    assert seen[0]["step"] == 3 and "dur_s" in seen[1]
    ring = {r[2]: r for r in tel.stages()}
    assert ring["ckpt/save"][0] == seen[0]["id"]  # one id in both places
    assert ring["ckpt/write"][1] == seen[0]["id"]


def test_stages_filter_on_the_perf_counter_clock():
    tel = Telemetry()
    with tel.stage("a/first"):
        pass
    cut = time.perf_counter()
    with tel.stage("a/second"):
        pass
    end = time.perf_counter()
    assert [r[2] for r in tel.stages(until=cut)] == ["a/first"]
    assert [r[2] for r in tel.stages(since=cut)] == ["a/second"]
    assert [r[2] for r in tel.stages(since=cut, until=end)] == ["a/second"]
    assert tel.stages(since=end) == []
    assert len(tel.stages()) == 2


def test_stage_ring_is_bounded(monkeypatch):
    from progen_tpu.telemetry import spans

    monkeypatch.setattr(spans, "MAX_STAGES", 8)
    tel = Telemetry()
    for _ in range(20):
        with tel.stage("hot/loop"):
            pass
    recs = tel.stages()
    assert len(recs) == 8
    assert [r[0] for r in recs] == list(range(12, 20))  # the newest survive
    from progen_tpu.telemetry.spans import get_telemetry

    assert get_telemetry()._stages.maxlen == 65536


def test_module_level_stage_uses_the_process_ring():
    from progen_tpu.telemetry.spans import get_telemetry, stage

    t0 = time.perf_counter()
    with stage("test/module_level"):
        pass
    mine = [r for r in get_telemetry().stages(since=t0)
            if r[2] == "test/module_level"]
    assert len(mine) == 1 and mine[0][5] == threading.get_ident()


def test_recording_is_true_only_with_a_sink_or_a_tap():
    from progen_tpu.telemetry import spans

    tel = Telemetry()
    taps, spans.EMIT_TAPS[:] = list(spans.EMIT_TAPS), []
    try:
        assert not tel.recording
        spans.EMIT_TAPS.append(lambda rec: None)
        assert tel.recording
        spans.EMIT_TAPS.clear()
        tel.set_sink(lambda rec: None)
        assert tel.recording
    finally:
        spans.EMIT_TAPS[:] = taps


def test_stage_and_span_change_no_traced_program():
    """Neither a stage nor a span enters a named_scope (since PR 38 a
    span is a host span only): ops traced under either carry the same
    name stacks as without them."""
    import contextlib
    import re

    import jax
    import jax.numpy as jnp

    tel = Telemetry()

    def op_names(region):
        def f(x):  # a fresh function each time: jit caches traces by it
            with region():
                return jnp.tanh(x) * 2.0

        text = jax.jit(f).lower(jnp.ones((4,))).as_text(debug_info=True)
        # the ops' name stacks; file:line locations differ by call site
        return re.findall(r'loc\("(jit\(f\)[^"]*)"', text)

    plain = op_names(contextlib.nullcontext)
    staged = op_names(lambda: tel.stage("serve/decode_dispatch"))
    spanned = op_names(lambda: tel.span("serve/prefill"))
    assert plain and staged == plain and spanned == plain
    assert not any("serve/" in n for n in plain)


def test_stages_and_spans_show_in_a_profiler_trace(tmp_path):
    """A few Scheduler.step()s of the tiny model under jax.profiler: the
    program's stages stand on the host plane under their literal names,
    where the benchmark's trace reader finds them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from benchmark import xplane
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen
    from progen_tpu.serving import Request, Scheduler, ServeEngine

    cfg = ProGenConfig(num_tokens=32, dim=32, seq_len=32, depth=2,
                       window_size=8, global_mlp_depth=1, heads=2,
                       dim_head=16, ff_mult=2, dtype="float32")
    model = ProGen(cfg)
    params = meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.seq_len), jnp.int32)
    ))["params"]
    engine = ServeEngine(model, params, max_slots=2, max_len=32)
    sched = Scheduler(engine, max_queue=4, prefill_chunk=2)
    for i in range(2):
        assert sched.submit(Request(id=f"p{i}", prime=np.array([3, 4, 5]),
                                    length=12, key=jax.random.PRNGKey(i)))[0]
    sched.step()  # compiles outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        for _ in range(4):
            sched.step()
    finally:
        jax.profiler.stop_trace()
    sched.run_to_completion(max_steps=200)
    path = xplane.find_xplane(str(tmp_path / "trace"))
    assert path is not None
    names = {"serve/step", "serve/decode", "serve/decode_fetch",
             "serve/emit", "serve/prefill_chunk"}
    host = xplane.load(path, names)["host"]
    count = {n: sum(1 for e in host if e[0] == n) for n in names}
    assert count["serve/step"] == count["serve/decode"] == 4
    assert count["serve/decode_fetch"] == count["serve/emit"] == 4
    assert count["serve/prefill_chunk"] >= 1  # the span is there too
    # children inside parents on the profiler's clock
    steps = [e for e in host if e[0] == "serve/step"]
    for name, s, d in host:
        if name == "serve/decode_fetch":
            assert any(ps <= s and s + d <= ps + pd for _, ps, pd in steps)


# ------------------------------------------------------------- compiles


def test_compiles_install_is_idempotent_and_counts_a_fresh_jit():
    import jax
    import jax.numpy as jnp
    from jax._src import monitoring

    from progen_tpu.telemetry import compiles

    compiles.install()
    n_dur = len(monitoring.get_event_duration_listeners())
    n_ev = len(monitoring.get_event_listeners())
    compiles.install()
    assert len(monitoring.get_event_duration_listeners()) == n_dur
    assert len(monitoring.get_event_listeners()) == n_ev
    assert monitoring.get_event_duration_listeners().count(compiles._on_duration) == 1

    before = compiles.snapshot()["backend_compile"]
    t_mid = time.perf_counter()
    # progen: ignore[PGL004] - a fresh compile is the point
    jax.jit(lambda x: x * 3.0 + 1.25)(jnp.ones((5,))).block_until_ready()
    after = compiles.snapshot()["backend_compile"]
    assert after["count"] > before["count"]
    assert after["seconds"] > before["seconds"]
    assert compiles.backend_compiles() == after["count"]
    assert set(compiles.snapshot()) == {"backend_compile", "cache_misses"}
    # by time: the fresh compile lies after t_mid, so a reading up to
    # t_mid leaves it out
    early = compiles.snapshot(until=t_mid)["backend_compile"]
    assert early["count"] == before["count"]
    assert abs(early["seconds"] - before["seconds"]) < 1e-9
    assert all(t < t_mid for t, _ in early["recent"])


def test_load_env_file_starts_the_compile_counters_unless_told_not_to(tmp_path):
    import subprocess
    import sys

    env_file = tmp_path / ".env"
    env_file.write_text("PROGEN_TEST_ENV_KEY=1\n")
    code = (
        "import sys\n"
        "from progen_tpu.utils.env import load_env_file\n"
        f"load_env_file({str(env_file)!r}, compile_counters=False)\n"
        "assert 'jax' not in sys.modules\n"
        "assert 'progen_tpu.telemetry.compiles' not in sys.modules\n"
        f"load_env_file({str(env_file)!r})\n"
        "from progen_tpu.telemetry import compiles\n"
        "assert compiles._installed\n"
        "from jax._src import monitoring, xla_bridge\n"
        "assert compiles._on_duration in monitoring.get_event_duration_listeners()\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True)
    assert proc.returncode == 0, proc.stderr.decode()


def test_compiles_ignores_other_events():
    from progen_tpu.telemetry import compiles

    before = compiles.snapshot()
    compiles._on_duration("/jax/core/compile/jaxpr_trace_duration", 1.0)
    compiles._on_event("/jax/some/other/event")
    after = compiles.snapshot()
    assert {k: v["count"] for k, v in after.items()} == {
        k: v["count"] for k, v in before.items()
    }


def test_prometheus_help_says_what_prefill_time_measures():
    from progen_tpu.serving.metrics import ServingMetrics

    m = ServingMetrics()
    m.add_time("prefill_time_s", 0.25)
    m.inc("prefill_tokens", 16)
    m.set_gauge("xla_compile_count", 3)
    m.set_gauge("queue_depth", 0)
    lines = prometheus_text(m).splitlines()
    i = lines.index("# TYPE progen_serve_prefill_time_s_total counter")
    assert lines[i - 1].startswith("# HELP progen_serve_prefill_time_s_total Host seconds")
    assert "not the device" in lines[i - 1]
    assert any(l.startswith("# HELP progen_serve_prefill_tokens_per_s ") for l in lines)
    assert any(l.startswith("# HELP progen_serve_xla_compile_count ") for l in lines)
    # names without help text keep the bare TYPE line, and samples parse
    assert not any(l.startswith("# HELP progen_serve_queue_depth") for l in lines)
    from progen_tpu.telemetry.slo import parse_prom_text

    assert parse_prom_text("\n".join(lines))["prefill_time_s"] == 0.25
