"""Flight recorder, on-demand profiling, and trace exemplars.

Covers the forensics contract end to end:

  1. dump atomicity + the digest seal (a torn or forged dump never
     verifies; a chaos SIGKILL at the dump site leaves no file or a
     complete one — and must not deadlock the tap);
  2. the EMIT_TAPS auto-dump edges (chaos kill, stall escalation,
     anomaly rollback, SLO burning) and the installed excepthook;
  3. worst-K trace exemplars surviving the full pipeline: registry →
     Prometheus exposition → parse → collector sample → fleet merge;
  4. the profile.pin seam: ack/reject/rate-limit without retry-loops;
  5. ``trace_timeline`` / ``query --trace``: one request's journey
     joined across events.jsonl, a flight dump, the serving journal,
     TSDB exemplars and alert ledgers — including across a kill.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from progen_tpu import telemetry
from progen_tpu.telemetry import flight
from progen_tpu.telemetry.flight import (
    FlightRecorder,
    ProfilePinWatcher,
    dump_records,
    find_dumps,
    is_dump_path,
    request_profile,
    seal,
    trace_timeline,
    verify_dump,
)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture(autouse=True)
def _clean_global_recorder():
    """Every test leaves the process-global recorder and the telemetry
    sink exactly as it found them (armed taps would leak into the rest
    of the suite)."""
    yield
    flight.disarm()
    telemetry.configure()


# ------------------------------------------------------------- the seal


def test_seal_verify_roundtrip(tmp_path):
    payload = {"flight": 1, "records": [{"ev": "step", "ts": 1.0}]}
    path = tmp_path / "flight-0-123.json"
    path.write_text(json.dumps(seal(payload)))
    assert verify_dump(path) == payload
    assert is_dump_path(path)
    assert not is_dump_path(tmp_path / "events.jsonl")


def test_verify_rejects_tampered_and_torn(tmp_path):
    doc = seal({"flight": 1, "records": [{"ev": "step", "ts": 1.0}]})
    forged = tmp_path / "flight-0-1.json"
    doc["payload"]["records"].append({"ev": "step", "ts": 2.0})
    forged.write_text(json.dumps(doc))
    with pytest.raises(ValueError, match="digest mismatch"):
        verify_dump(forged)
    torn = tmp_path / "flight-0-2.json"
    torn.write_text(json.dumps(doc)[:40])
    with pytest.raises(ValueError, match="unreadable"):
        verify_dump(torn)
    not_a_dump = tmp_path / "flight-0-3.json"
    not_a_dump.write_text("{}")
    with pytest.raises(ValueError, match="not a flight dump"):
        verify_dump(not_a_dump)


# ------------------------------------------------------- recorder + ring


def test_ring_bound_and_truncation_accounting(tmp_path):
    rec = FlightRecorder(tmp_path, ring=4, clock=lambda: 42.0)
    for i in range(10):
        rec.tap({"ev": "step", "ts": float(i), "i": i})
    path = rec.dump("test")
    assert path is not None and path.name.startswith("flight-")
    payload = verify_dump(path)
    assert payload["reason"] == "test"
    assert payload["truncated"] == 6
    assert [r["i"] for r in payload["records"]] == [6, 7, 8, 9]
    assert "stacks" in payload and payload["stacks"]
    assert dump_records(path) == payload["records"]
    assert find_dumps(tmp_path) == [path]


def test_same_ms_dumps_never_clobber(tmp_path):
    rec = FlightRecorder(tmp_path, clock=lambda: 42.0)
    p1 = rec.dump("first")
    p2 = rec.dump("second")
    assert p1 != p2 and p1.exists() and p2.exists()
    assert verify_dump(p1)["reason"] == "first"
    assert verify_dump(p2)["reason"] == "second"


def test_auto_dump_edges_via_emit_tap(tmp_path):
    flight.arm(tmp_path)
    tel = telemetry.get_telemetry()
    tel.emit({"ev": "stall_escalation", "ts": 1.0, "stalled_s": 99.0})
    tel.emit({"ev": "anomaly_rollback", "ts": 2.0, "step": 7})
    # SLO edges come from the watchtower's own state machine; only a
    # `burning` transition is a dump edge — warn is not, and neither
    # is a non-kill chaos fault
    from progen_tpu.telemetry import slo as slo_mod
    watch = slo_mod.SloWatch(cfg=None, emit=tel.emit)
    watch.observe([slo_mod.SloResult(
        "ttft", "latency", slo_mod.STATE_BURNING, 3.0, 3.0, 1.0,
    )], now=3.0)
    watch.observe([slo_mod.SloResult(
        "avail", "availability", slo_mod.STATE_WARN, 1.5, 0.5, 0.9,
    )], now=4.0)
    tel.emit({"ev": "chaos", "ts": 5.0, "kind": "fail", "site": "x"})
    tel.emit({"ev": "chaos", "ts": 6.0, "kind": "kill",
              "site": "serve/decode"})
    reasons = [verify_dump(p)["reason"] for p in find_dumps(tmp_path)]
    assert sorted(reasons) == [
        "anomaly_rollback", "chaos_kill", "slo_burning",
        "stall_escalation",
    ]
    # the ring itself carries the trigger records
    chaos_dump = next(
        p for p in find_dumps(tmp_path)
        if verify_dump(p)["reason"] == "chaos_kill"
    )
    assert any(
        r.get("ev") == "chaos" and r.get("kind") == "kill"
        for r in dump_records(chaos_dump)
    )


def test_excepthook_dumps_then_chains(tmp_path):
    calls = []
    old_hook = sys.excepthook
    sys.excepthook = lambda *a: calls.append(a)
    try:
        flight.arm(tmp_path)
        err = ValueError("boom")
        sys.excepthook(ValueError, err, None)
        reasons = [verify_dump(p)["reason"] for p in find_dumps(tmp_path)]
        assert reasons == ["unhandled_exception"]
        assert calls and calls[0][1] is err  # prior hook still ran
        flight.disarm()
        assert sys.excepthook is not None
    finally:
        sys.excepthook = old_hook


def test_dump_now_without_arm_is_noop(tmp_path):
    flight.disarm()
    assert flight.dump_now("killed") is None
    assert flight.get_recorder() is None


def test_metrics_fn_failure_never_breaks_dump(tmp_path):
    def bad_metrics():
        raise RuntimeError("snapshot torn")

    rec = FlightRecorder(tmp_path, metrics_fn=bad_metrics)
    payload = verify_dump(rec.dump("test"))
    assert payload["metrics"] is None


def test_router_process_and_its_flight_dump_never_claim_the_chip(tmp_path):
    """A chip belongs to one process: the router (and every other
    jax-free process — collector, deploy controller, top, lint) must not
    initialise a JAX backend, not even at the moment of a crash dump,
    which snapshots device memory, or when it opens its tracker."""
    script = textwrap.dedent("""
        import sys

        import progen_tpu.cli.router  # noqa: F401
        from progen_tpu.telemetry import flight, hbm_gauges
        from progen_tpu.tracking import make_tracker

        tracker = make_tracker("progen-router", dir=sys.argv[1])
        assert type(tracker).__name__ == "JsonlTracker", tracker
        flight.arm(sys.argv[1])
        path = flight.dump_now("killed", note="test")
        assert flight.verify_dump(path)["memory_stats"] == []
        assert hbm_gauges() == {}
        if "jax" in sys.modules:
            from jax._src import xla_bridge

            assert not xla_bridge.backends_are_initialized()
        print("jax-free ok")
    """)
    out = subprocess.run(
        [sys.executable, "-c", script, str(tmp_path)],
        env={**os.environ, "PYTHONPATH": str(REPO)},
        capture_output=True, text=True, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert "jax-free ok" in out.stdout


# --------------------------------------------------- chaos: flight/dump


def test_chaos_targets_registered():
    from progen_tpu.resilience import chaos

    assert "flight/dump" in chaos.KNOWN_TARGETS
    assert "profile/window" in chaos.KNOWN_TARGETS


_DUMP_KILL_SCRIPT = textwrap.dedent("""
    import sys

    from progen_tpu.resilience.chaos import install_from_env
    install_from_env()
    from progen_tpu import telemetry
    from progen_tpu.telemetry import flight

    flight.arm(sys.argv[1])
    tel = telemetry.get_telemetry()
    for i in range(5):
        tel.emit({"ev": "step", "ts": float(i), "i": i})
    for n in range(int(sys.argv[2])):
        flight.dump_now("test%d" % n)
    print("survived")  # unreachable when the kill rule fires
""")


def _run_dump_kill(tmp_path, chaos, n_dumps):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PROGEN_CHAOS"] = chaos
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get("PYTHONPATH", "")
    return subprocess.run(
        [sys.executable, "-c", _DUMP_KILL_SCRIPT,
         str(tmp_path), str(n_dumps)],
        capture_output=True, text=True, timeout=120, env=env,
    )


def test_kill_at_dump_site_leaves_no_torn_file(tmp_path):
    """SIGKILL at the flight/dump span entry: the atomic discipline
    means no flight-*.json at all — and the injector's own ev:"chaos"
    emit re-enters the tap MID-DUMP, which must skip (non-blocking
    lock), not deadlock; a hang here is the bug."""
    r = _run_dump_kill(tmp_path, "flight/dump:kill@1", 1)
    assert r.returncode == -9, (r.stdout, r.stderr)
    assert find_dumps(tmp_path) == []
    assert list(tmp_path.glob("*.tmp")) == []


def test_kill_at_second_dump_keeps_first_valid(tmp_path):
    r = _run_dump_kill(tmp_path, "flight/dump:kill@2", 2)
    assert r.returncode == -9, (r.stdout, r.stderr)
    dumps = find_dumps(tmp_path)
    assert len(dumps) == 1
    assert verify_dump(dumps[0])["reason"] == "test0"
    assert list(tmp_path.glob("*.tmp")) == []


# ------------------------------------------------------ trace exemplars


def test_exemplar_roundtrip_through_prometheus():
    """registry observe(trace_id=) → exposition → parse → collector
    sample shape: the worst trace survives with its family name
    joining split_prom_values' timing keys."""
    from progen_tpu.serving.metrics import ServingMetrics
    from progen_tpu.telemetry import prometheus_text
    from progen_tpu.telemetry.collector import (
        prom_families,
        split_prom_values,
    )
    from progen_tpu.telemetry.slo import (
        parse_prom_exemplars,
        parse_prom_text,
    )

    m = ServingMetrics()
    for i in range(20):
        m.observe("ttft_s", 0.01 * (i + 1), trace_id=f"t{i}")
    m.observe("ttft_s", 9.0, trace_id="worst")
    m.observe("latency_s", 1.5, trace_id="worst")
    m.observe("itl_s", 0.002)  # no trace: family renders, no exemplar

    text = prometheus_text(m)
    exs = parse_prom_exemplars(text)
    assert exs["ttft_s"][0]["trace_id"] == "worst"
    assert exs["ttft_s"][0]["value"] == 9.0
    assert exs["latency_s"][0]["trace_id"] == "worst"
    assert "itl_s" not in exs

    # the exemplar keys join the timing families split_prom_values sees
    vals = parse_prom_text(text)
    fams = prom_families(text)
    _, _, timings = split_prom_values(vals, fams)
    assert set(exs) <= set(timings)


def test_exemplar_label_escaping_roundtrip():
    from progen_tpu.serving.metrics import ServingMetrics
    from progen_tpu.telemetry import prometheus_text
    from progen_tpu.telemetry.slo import parse_prom_exemplars

    hostile = 'req "7"\\n\\end'
    m = ServingMetrics()
    m.observe("ttft_s", 1.0, trace_id=hostile)
    exs = parse_prom_exemplars(prometheus_text(m))
    assert exs["ttft_s"][0]["trace_id"] == hostile


def test_exemplar_fleet_merge_is_worst_k_union():
    from progen_tpu.telemetry.collector import (
        fleet_exemplars,
        make_sample,
    )
    from progen_tpu.telemetry.registry import _EXEMPLAR_CAP, _Timing

    a, b = _Timing(), _Timing()
    for i in range(6):
        a.observe(float(i), trace_id=f"a{i}")
        b.observe(float(i) + 0.5, trace_id=f"b{i}")
    merged = _Timing.merged([a, b])
    got = merged.exemplars()
    assert len(got) == _EXEMPLAR_CAP
    # worst-of-worst-Ks: the union's top values, order-insensitive
    assert [e["trace_id"] for e in got] == ["b5", "a5", "b4", "a4"]

    # the collector-side rollup agrees (latest sample per source)
    samples = [
        make_sample(1.0, "r0", "replica", True, 0.1,
                    timings={"ttft_s": {"count": 6,
                                        "exemplars": a.exemplars()}}),
        make_sample(1.0, "r1", "replica", True, 0.1,
                    timings={"ttft_s": {"count": 6,
                                        "exemplars": b.exemplars()}}),
    ]
    fleet = fleet_exemplars(samples)
    assert [e["trace_id"] for e in fleet["ttft_s"]] == \
        [e["trace_id"] for e in got]


# ------------------------------------------------------ the profile pin


class _FakeProfiler:
    def __init__(self, fail_start=False):
        self.fail_start = fail_start
        self.calls = []

    def start_trace(self, d):
        if self.fail_start:
            raise RuntimeError("no backend")
        self.calls.append(("start", d))

    def stop_trace(self):
        self.calls.append(("stop",))


class _Clock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


def _read_ack(pin_path):
    return json.loads(
        Path(str(pin_path) + ".ack").read_text()
    )


def test_profile_pin_start_stop_ack(tmp_path):
    pin = tmp_path / "profile.pin"
    prof = _FakeProfiler()
    clock = _Clock()
    w = ProfilePinWatcher(pin, tmp_path / "profiles", max_window_s=5.0,
                          min_interval_s=30.0, clock=clock,
                          profiler=prof)
    token = request_profile(pin, duration_s=2.0)
    assert pin.read_text() == f"{token} 2"

    clock.t += 3.0  # past the poll throttle
    assert w.poll_watch() is True
    assert w.active
    assert _read_ack(pin) == pytest.approx(
        {"pin": token, "status": "started", "ts": _read_ack(pin)["ts"]}
    )
    assert prof.calls[0][0] == "start"

    # window still open before its deadline; closed at it
    clock.t += 1.0
    assert w.poll_watch() is False and w.active
    clock.t += 1.5
    w.poll_watch()
    assert not w.active
    assert _read_ack(pin)["status"] == "stopped"
    assert prof.calls[-1] == ("stop",)
    assert w.window_count == 1

    # the handled pin is not re-run on later polls
    clock.t += 10.0
    assert w.poll_watch() is False


def test_profile_pin_rate_limit_rejects(tmp_path):
    pin = tmp_path / "profile.pin"
    prof = _FakeProfiler()
    clock = _Clock()
    w = ProfilePinWatcher(pin, tmp_path / "profiles", max_window_s=1.0,
                          min_interval_s=300.0, clock=clock,
                          profiler=prof)
    t1 = request_profile(pin, duration_s=1.0, token="first")
    clock.t += 3.0
    assert w.poll_watch() is True
    clock.t += 2.0
    w.poll_watch()  # closes the window
    request_profile(pin, duration_s=1.0, token="second")
    clock.t += 3.0
    assert w.poll_watch() is False
    ack = _read_ack(pin)
    assert ack == {"pin": "second", "status": "rejected",
                   "reason": "rate_limited", "ts": ack["ts"]}
    # the rejected content is not retried until it changes
    clock.t += 3.0
    assert w.poll_watch() is False
    assert prof.calls.count(("stop",)) == 1
    assert t1 == "first"


def test_profile_pin_profiler_unavailable_rejects(tmp_path):
    pin = tmp_path / "profile.pin"
    clock = _Clock()
    w = ProfilePinWatcher(pin, tmp_path / "profiles", clock=clock,
                          profiler=_FakeProfiler(fail_start=True))
    request_profile(pin, token="p1")
    clock.t += 3.0
    assert w.poll_watch() is False
    assert not w.active
    ack = _read_ack(pin)
    assert ack["status"] == "rejected"
    assert "profiler_unavailable" in ack["reason"]


def test_profile_pin_window_clamps_to_max(tmp_path):
    pin = tmp_path / "profile.pin"
    clock = _Clock()
    w = ProfilePinWatcher(pin, tmp_path / "profiles", max_window_s=2.0,
                          clock=clock, profiler=_FakeProfiler())
    request_profile(pin, duration_s=9999.0, token="big")
    clock.t += 3.0
    assert w.poll_watch() is True
    clock.t += 2.1  # the 9999s ask was clamped to max_window_s
    w.poll_watch()
    assert not w.active


def test_profile_close_flushes_inflight_window(tmp_path):
    pin = tmp_path / "profile.pin"
    prof = _FakeProfiler()
    clock = _Clock()
    w = ProfilePinWatcher(pin, tmp_path / "profiles", clock=clock,
                          profiler=prof)
    request_profile(pin, token="p1")
    clock.t += 3.0
    w.poll_watch()
    assert w.active
    w.close()
    assert not w.active and prof.calls[-1] == ("stop",)
    assert _read_ack(pin)["status"] == "stopped"


# ------------------------------------------------------- trace_timeline


def _write_jsonl(path, records):
    path.parent.mkdir(parents=True, exist_ok=True)
    with open(path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    return path


def test_trace_timeline_joins_all_streams(tmp_path):
    tid = "trace-7"
    # router events: the trace_id-bearing route record binds req r1
    events = _write_jsonl(tmp_path / "events.jsonl", [
        {"ev": "route", "ts": 10.0, "status": "dispatched",
         "trace_id": tid, "req": "r1", "replica": "r0"},
        {"ev": "req", "ts": 10.1, "req": "r1", "ph": "b",
         "name": "decode"},
        {"ev": "req", "ts": 10.2, "req": "OTHER", "ph": "b",
         "name": "decode"},  # unrelated request: excluded
    ])
    # the killed replica's black box replays through the same reader
    rec = FlightRecorder(tmp_path / "flight", clock=lambda: 10.6)
    rec.tap({"ev": "req", "ts": 10.5, "req": "r1", "ph": "e",
             "name": "decode", "trace_id": tid})
    dump = rec.dump("chaos_kill")
    # serving journal: accept binds r1, tokens summarize first/last
    journal = _write_jsonl(tmp_path / "journal.jsonl", [
        {"ev": "journal", "op": "accept", "ts": 10.05, "req": "r1",
         "trace_id": tid},
        {"ev": "journal", "op": "token", "ts": 10.15, "req": "r1",
         "index": 0, "token": 5},
        {"ev": "journal", "op": "token", "ts": 10.25, "req": "r1",
         "index": 1, "token": 6},
        {"ev": "journal", "op": "token", "ts": 10.35, "req": "r1",
         "index": 2, "token": 7},
        {"ev": "journal", "op": "done", "ts": 10.45, "req": "r1",
         "status": "ok"},
        {"ev": "journal", "op": "accept", "ts": 10.0, "req": "OTHER",
         "trace_id": "not-it"},
    ])
    # alert ledger: anything mentioning the trace joins — written by
    # the real sink so the records carry its field grammar
    from progen_tpu.telemetry.alerts import AlertSink
    sink = AlertSink(tmp_path / "alerts.jsonl")
    sink.slo_transition(
        {"ev": "slo", "ts": 11.0, "state": "burning",
         "objective": "ttft"},
        exemplars={"ttft_s": [{"value": 0.9, "trace_id": tid}]},
    )
    sink.staleness("r9", up=False, age_s=30.0, now=11.5)
    sink.close()
    alerts = tmp_path / "alerts.jsonl"

    tl = trace_timeline(tid, events=[events, dump],
                        journals=[journal], extra_jsonl=[alerts])
    stamps = [(e["ts"], e["src"], e["what"]) for e in tl]
    assert [s[0] for s in stamps] == sorted(s[0] for s in stamps)
    whats = [e["what"] for e in tl]
    assert "route dispatched" in whats
    assert "req decode begin" in whats
    assert "req decode end" in whats  # from the flight dump
    assert "journal accept" in whats
    assert "journal done ok" in whats
    assert any("token first (req r1, index 0)" in w for w in whats)
    assert any("token last (req r1, index 2, 3 journaled)" in w
               for w in whats)
    assert any(w.startswith("alert") for w in whats)
    # nothing from the unrelated request or the staleness alert
    assert not any("OTHER" in json.dumps(e) for e in tl)
    assert len([w for w in whats if w.startswith("alert")]) == 1


def test_trace_timeline_tsdb_exemplars_dedupe(tmp_path):
    from progen_tpu.telemetry.collector import make_sample
    from progen_tpu.telemetry.tsdb import RingTSDB

    tid = "trace-9"
    tsdb = RingTSDB(tmp_path / "tsdb")
    fam = {"ttft_s": {"count": 3,
                      "exemplars": [{"value": 0.8, "trace_id": tid}]}}
    # the same worst exemplar rides every subsequent scrape: one entry
    tsdb.append(make_sample(20.0, "r0", "replica", True, 0.1,
                            timings=fam))
    tsdb.append(make_sample(22.0, "r0", "replica", True, 0.1,
                            timings=fam))
    tsdb.close()
    tl = trace_timeline(tid, tsdb_dir=tmp_path / "tsdb")
    assert len(tl) == 1
    assert "exemplar ttft_s=0.8" in tl[0]["what"]
    assert tl[0]["src"] == "tsdb"


def test_query_cli_discovers_and_reconstructs(tmp_path):
    from click.testing import CliRunner

    from progen_tpu.cli.telemetry import main as telemetry_cli

    tid = "q-trace"
    logs = tmp_path / "logs"
    _write_jsonl(logs / "run" / "events.jsonl", [
        {"ev": "route", "ts": 1.0, "status": "dispatched",
         "trace_id": tid, "req": "r1"},
    ])
    rec = FlightRecorder(logs / "replica0" / "flight",
                         clock=lambda: 2.0)
    rec.tap({"ev": "req", "ts": 1.5, "req": "r1", "ph": "e",
             "name": "decode", "trace_id": tid})
    rec.dump("chaos_kill")
    _write_jsonl(logs / "replica0" / "journal.jsonl", [
        {"ev": "journal", "op": "accept", "ts": 1.1, "req": "r1",
         "trace_id": tid},
    ])

    out_json = tmp_path / "timeline.json"
    r = CliRunner().invoke(telemetry_cli, [
        "query", "--trace", tid, "--logs", str(logs),
        "--json", str(out_json),
    ])
    assert r.exit_code == 0, r.output
    assert f"trace {tid}:" in r.output
    assert "3 streams" in r.output
    doc = json.loads(out_json.read_text())
    assert doc["trace_id"] == tid
    assert len(doc["timeline"]) == 3

    r = CliRunner().invoke(telemetry_cli, [
        "query", "--trace", "never-seen", "--logs", str(logs),
    ])
    assert r.exit_code == 1
    assert "no records found" in r.output


# ------------------------------------- the killed replica's black box


def test_export_and_stitch_accept_flight_dumps(tmp_path):
    from progen_tpu.telemetry.stitch import stitch_trace
    from progen_tpu.telemetry.trace import export_trace

    # a survivor's events.jsonl and a victim's flight dump, same story
    _write_jsonl(tmp_path / "events.jsonl", [
        {"ev": "B", "ts": 1.0, "span": "router/dispatch", "id": 1,
         "pid": 10, "tid": 1},
        {"ev": "E", "ts": 1.2, "span": "router/dispatch", "id": 1,
         "pid": 10, "tid": 1, "dur_s": 0.2},
    ])
    rec = FlightRecorder(tmp_path / "flight", clock=lambda: 2.0)
    rec.tap({"ev": "B", "ts": 1.1, "span": "serve/decode", "id": 2,
             "pid": 20, "tid": 1})
    rec.tap({"ev": "chaos", "ts": 1.15, "site": "serve/decode",
             "kind": "kill", "hit": 3})
    dump = rec.dump("chaos_kill")

    out = tmp_path / "trace.json"
    export_trace(dump, out)
    doc = json.loads(out.read_text())
    names = [e.get("name") for e in doc["traceEvents"]]
    assert "serve/decode" in names
    assert "chaos" in names

    stitched = tmp_path / "stitched.json"
    stitch_trace([tmp_path / "events.jsonl", dump], stitched)
    doc = json.loads(stitched.read_text())
    names = [e.get("name") for e in doc["traceEvents"]]
    assert "router/dispatch" in names and "serve/decode" in names


def test_sigkilled_serve_leaves_queryable_black_box(tmp_path):
    """The acceptance scenario: a serve replica SIGKILLed mid-decode
    (chaos) leaves a digest-valid flight dump whose ring, joined with
    the journal, reconstructs the killed request's journey in one
    ``trace_timeline`` call."""
    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from progen_tpu.checkpoint import Package, get_checkpoint_fns
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen

    config = ProGenConfig(
        num_tokens=256, dim=32, seq_len=32, depth=2, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2,
        dtype="float32",
    )
    model = ProGen(config)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, config.seq_len), jnp.int32)
    )
    params = meta.unbox(variables)["params"]
    _, _, save = get_checkpoint_fns(str(tmp_path / "ck"))
    save(Package(0, {"params": params}, config.to_dict(), "flight"))

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PROGEN_CHAOS"] = "serve/decode:kill@6"
    env["PYTHONPATH"] = f"{REPO}{os.pathsep}" + env.get("PYTHONPATH", "")
    jd = tmp_path / "jd"
    fd = tmp_path / "flight"
    proc = subprocess.Popen(
        [sys.executable, "-m", "progen_tpu.cli.serve",
         "--checkpoint_path", str(tmp_path / "ck"),
         "--max-slots", "2", "--max-queue", "16", "--max-len", "24",
         "--journal_dir", str(jd), "--flight_dir", str(fd)],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, env=env, text=True,
    )
    reqs = [
        json.dumps({"id": f"r{i}", "prime": "MKV", "length": 16,
                    "seed": 70 + i, "trace_id": f"tr-{i}"})
        for i in range(3)
    ]
    out, err = proc.communicate(input="\n".join(reqs) + "\n",
                                timeout=240)
    assert proc.returncode == -9, (out[-500:], err[-2000:])

    dumps = find_dumps(fd)
    assert dumps, err[-2000:]
    payload = verify_dump(dumps[-1])  # digest-valid despite the SIGKILL
    assert payload["reason"] == "chaos_kill"
    traced = {
        r.get("trace_id") for r in payload["records"]
        if r.get("trace_id")
    }
    assert traced & {"tr-0", "tr-1", "tr-2"}

    tid = sorted(traced & {"tr-0", "tr-1", "tr-2"})[0]
    tl = trace_timeline(tid, events=list(dumps),
                        journals=[jd / "journal.jsonl"])
    whats = [e["what"] for e in tl]
    assert "journal accept" in whats
    assert any(w.startswith("req ") for w in whats)
    assert [e["ts"] for e in tl] == sorted(e["ts"] for e in tl)
