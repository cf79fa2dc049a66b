"""Scheduler behavior: admission, backpressure, slot lifecycle, metrics.

These tests exercise the control plane — FIFO order, bounded-queue
rejection with machine-readable reasons, EOS/max-length slot release
under mixed-length concurrent traffic — and the metrics surface the
ops side depends on. Token-level correctness lives in test_serving.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen
from progen_tpu.serving import (
    REJECT_QUEUE_FULL,
    Request,
    Scheduler,
    ServeEngine,
    ServingMetrics,
)

TINY = ProGenConfig(
    num_tokens=32,
    dim=32,
    seq_len=32,
    depth=2,
    window_size=8,
    global_mlp_depth=1,
    heads=2,
    dim_head=16,
    ff_mult=2,
    dtype="float32",
)


@pytest.fixture(scope="module")
def model_and_params():
    model = ProGen(TINY)
    tokens = jnp.zeros((1, TINY.seq_len), jnp.int32)
    variables = model.init(jax.random.PRNGKey(0), tokens)
    from flax.core import meta

    return model, meta.unbox(variables)["params"]


def _req(i, length=10, **knobs):
    return Request(
        id=f"q{i}", prime=np.array([1 + i % 30, 2]), length=length,
        key=jax.random.PRNGKey(i), **knobs,
    )


class TestBackpressure:
    def test_bounded_queue_rejects_with_reason(self, model_and_params):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=2)
        # nothing admitted yet (admission happens inside step), so the
        # queue alone absorbs exactly max_queue submissions
        ok0, r0 = sched.submit(_req(0))
        ok1, r1 = sched.submit(_req(1))
        assert (ok0, r0) == (True, None) and (ok1, r1) == (True, None)
        ok2, r2 = sched.submit(_req(2))
        assert not ok2 and r2 == REJECT_QUEUE_FULL
        m = sched.metrics.snapshot()
        assert m["rejected_queue_full"] == 1
        assert m["requests_rejected"] == 1
        assert m["queue_depth"] == 2
        # a slot frees after completion -> the queue drains -> accepted
        sched.run_to_completion(max_steps=300)
        ok3, r3 = sched.submit(_req(3))
        assert ok3 and r3 is None

    def test_invalid_rejected_before_queueing(self, model_and_params):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=16)
        sched = Scheduler(engine, max_queue=1)
        for bad, why in [
            (_req(0, length=17), "exceeds engine max_len"),
            (_req(1, length=2), "must be <"),  # prime >= length
            (Request(id="t", prime=np.array([1]), length=8,
                     temperature=0.0, key=jax.random.PRNGKey(0)),
             "temperature"),
            (Request(id="p", prime=np.array([1]), length=8, top_p=1.5,
                     key=jax.random.PRNGKey(0)), "top_p"),
            (Request(id="k", prime=np.array([1]), length=8, top_k=99,
                     key=jax.random.PRNGKey(0)), "top_k"),
        ]:
            ok, reason = sched.submit(bad)
            assert not ok and reason.startswith("invalid:") and why in reason
        # none of the invalid submissions consumed queue space
        assert sched.queue_depth == 0
        assert sched.metrics.snapshot()["rejected_invalid"] == 5

    def test_fifo_admission_order(self, model_and_params):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=8)
        for i in range(3):
            assert sched.submit(_req(i, length=6))[0]
        _, comps = sched.run_to_completion(max_steps=300)
        assert [c.request_id for c in comps] == ["q0", "q1", "q2"]


class TestUnbudgetedAdmission:
    """``prefill_chunk=0`` is the budgeted path without a budget: the
    same pending admissions, settled by the same ``_activate``."""

    @staticmethod
    def _run(model, params, prefill_chunk):
        from progen_tpu.telemetry import spans

        seen = []
        spans.configure(sink=seen.append)
        try:
            engine = ServeEngine(model, params, max_slots=3, max_len=32)
            sched = Scheduler(engine, max_queue=8,
                              prefill_chunk=prefill_chunk)
            for i in range(3):  # primes of 5: four positions to feed each
                assert sched.submit(Request(
                    id=f"u{i}", prime=np.arange(1, 6) + 3 * i,
                    length=14 + 2 * i, key=jax.random.PRNGKey(70 + i),
                ))[0]
            sched.step()
            after_one_step = len(sched.active_ids)
            _, comps = sched.run_to_completion(max_steps=300)
        finally:
            spans.configure()
        events = {
            f"u{i}": [
                (r["name"], r["ph"], r.get("slot")) for r in seen
                if r.get("ev") == "req" and r["req"] == f"u{i}"
                and r["name"] in ("queued", "prefill", "decode")
            ]
            for i in range(3)
        }
        counters = {
            name: sched.metrics.snapshot()[name]
            for name in ("requests_admitted", "prefill_tokens",
                         "prefill_blocks", "requests_completed")
        }
        tokens = {c.request_id: c.tokens.tolist() for c in comps}
        return after_one_step, events, counters, tokens

    def test_one_step_admits_what_a_budget_of_four_settles_in_three(
        self, model_and_params
    ):
        model, params = model_and_params
        live0, events0, counters0, tokens0 = self._run(model, params, 0)
        live4, events4, counters4, tokens4 = self._run(model, params, 4)
        assert (live0, live4) == (3, 1)
        assert counters0 == counters4
        assert counters0["requests_admitted"] == 3
        assert counters0["prefill_tokens"] == 12
        assert counters0["prefill_blocks"] == 3
        assert events0 == events4
        for i in range(3):
            assert events0[f"u{i}"] == [
                ("queued", "b", None), ("queued", "e", None),
                ("prefill", "b", i), ("prefill", "e", None),
                ("decode", "b", i), ("decode", "e", None),
            ]
        assert tokens0 == tokens4 and len(tokens0) == 3


class TestSlotLifecycle:
    def test_mixed_length_release_and_reuse(self, model_and_params):
        """6 requests with very different lengths through 2 slots: every
        completion frees a slot for the next admission (EOS or
        max-length, whichever fires), active count never exceeds the
        pool, and the pool is empty at drain."""
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        sched = Scheduler(engine, max_queue=8)
        lengths = [5, 28, 9, 20, 6, 14]
        for i, ln in enumerate(lengths):
            assert sched.submit(_req(i, length=ln))[0]
        completions = []
        while sched.has_work:
            assert engine.num_active <= 2
            assert len(sched.active_ids) <= 2
            _, comp = sched.step()
            completions.extend(comp)
        assert len(completions) == len(lengths)
        assert engine.num_active == 0
        assert sched.queue_depth == 0
        # short requests must not be blocked behind long ones forever:
        # q0 (len 5) finishes before q1 (len 28)
        order = [c.request_id for c in completions]
        assert order.index("q0") < order.index("q1")

    def test_release_is_idempotent_and_engine_reusable(
        self, model_and_params
    ):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        slot = engine.acquire()
        engine.prefill(slot, np.array([3, 4]), 8,
                       key=jax.random.PRNGKey(1))
        engine.release(slot)
        engine.release(slot)  # double-release must not corrupt the pool
        assert engine.num_active == 0
        assert sorted([engine.acquire(), engine.acquire()]) == [0, 1]
        assert engine.acquire() is None  # saturated pool

    def test_engine_rejects_bad_construction(self, model_and_params):
        model, params = model_and_params
        with pytest.raises(ValueError):
            ServeEngine(model, params, max_slots=0)
        with pytest.raises(ValueError):
            ServeEngine(model, params, max_slots=1,
                        max_len=TINY.seq_len + 1)


class TestMetrics:
    def test_counters_gauges_and_throughput(self, model_and_params):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        metrics = ServingMetrics()
        sched = Scheduler(engine, max_queue=2, metrics=metrics)
        for i in range(2):
            assert sched.submit(_req(i, length=8))[0]
        sched.submit(_req(2, length=8))  # queue_full
        sched.run_to_completion(max_steps=300)
        m = metrics.snapshot()
        assert m["requests_submitted"] == 3
        assert m["requests_admitted"] == 2
        assert m["requests_completed"] == 2
        assert m["requests_rejected"] == 1
        assert m["queue_depth"] == 0 and m["active_slots"] == 0
        # prefill feeds start-1 positions (the last primed token is
        # consumed by the first decode step): 1 per request here
        assert m["prefill_tokens"] == 2.0
        assert m["decode_tokens"] > 0
        assert m["ttft_s_count"] == 2 and m["ttft_s_mean_s"] > 0
        assert m["latency_s_count"] == 2
        assert m["latency_s_max_s"] >= m["ttft_s_mean_s"] > 0
        assert m["decode_tokens_per_s"] > 0
        assert m["prefill_tokens_per_s"] > 0

    def test_occupancy_and_compile_gauges(self, model_and_params):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        metrics = ServingMetrics()
        sched = Scheduler(engine, max_queue=4, metrics=metrics)
        # published from construction so the first exposition already
        # carries the fleet-scrape gauges
        m = metrics.snapshot()
        assert m["slot_occupancy"] == 0 and m["slots_free"] == 2
        assert "decode_compile_count" in m
        assert "prefill_compile_count" in m
        for i in range(2):
            assert sched.submit(_req(i, length=8))[0]
        sched.step()
        m = metrics.snapshot()
        assert m["slot_occupancy"] >= 1
        assert m["slots_free"] == 2 - m["slot_occupancy"]
        sched.run_to_completion(max_steps=300)
        m = metrics.snapshot()
        assert m["slot_occupancy"] == 0 and m["slots_free"] == 2
        # the steps above decoded, so at least one decode compile has
        # been published at step cadence
        assert m["decode_compile_count"] >= 1

    def test_log_to_tracker(self, model_and_params, tmp_path):
        from progen_tpu.tracking import JsonlTracker

        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=2)
        assert sched.submit(_req(0, length=6))[0]
        sched.run_to_completion(max_steps=100)
        tracker = JsonlTracker("serve-test", None, str(tmp_path))
        sched.metrics.log_to(tracker, step=1)
        tracker.finish()
        import json

        line = (
            (tmp_path / "serve-test" / tracker.run_id / "metrics.jsonl")
            .read_text()
            .strip()
        )
        rec = json.loads(line)
        assert rec["serve/requests_completed"] == 1.0
        assert rec["_step"] == 1
        assert "serve/decode_tokens_per_s" in rec


class TestDeadlines:
    """Queue-TTL expiry and graceful drain: queued requests past their
    deadline (or shed by a drain) are rejected with machine-readable
    reasons BEFORE admission, never mid-decode."""

    def _sched(self, model_and_params, max_slots=1, max_queue=8):
        model, params = model_and_params
        clock = {"t": 0.0}
        engine = ServeEngine(model, params, max_slots=max_slots, max_len=32)
        sched = Scheduler(
            engine, max_queue=max_queue, clock=lambda: clock["t"]
        )
        return sched, clock

    def test_expired_queued_request_rejected_not_admitted(
        self, model_and_params
    ):
        from progen_tpu.serving import REJECT_DEADLINE

        sched, clock = self._sched(model_and_params, max_slots=1)
        # r0 occupies the only slot; r1 waits in queue with a 5s TTL
        assert sched.submit(_req(0, length=12))[0]
        assert sched.submit(_req(1, length=4, deadline_s=5.0))[0]
        sched.step()  # admits r0 only (one slot)
        clock["t"] = 10.0  # r1's deadline passes while queued
        events, comps = sched.step()
        shed = sched.pop_expired()
        assert [(r.id, reason) for r, reason in shed] == [
            ("q1", REJECT_DEADLINE)
        ]
        assert sched.queue_depth == 0
        m = sched.metrics.snapshot()
        assert m["requests_expired"] == 1
        assert m["rejected_deadline_exceeded"] == 1
        assert m["requests_rejected"] == 1
        # r1 never touched a slot; r0 still completes normally
        _, comps2 = sched.run_to_completion(max_steps=300)
        done = {c.request_id for c in list(comps) + list(comps2)}
        assert done == {"q0"}
        # pop_expired drains: a second call reports nothing
        assert sched.pop_expired() == []

    def test_live_deadline_not_expired_and_inflight_immune(
        self, model_and_params
    ):
        sched, clock = self._sched(model_and_params, max_slots=1)
        assert sched.submit(_req(0, length=12, deadline_s=100.0))[0]
        sched.step()  # admitted within deadline
        clock["t"] = 500.0  # WAY past the deadline — but it's on a slot
        _, comps = sched.run_to_completion(max_steps=300)
        assert [c.request_id for c in comps] == ["q0"]
        assert sched.metrics.snapshot().get("requests_expired", 0) == 0

    def test_invalid_deadline_rejected_at_submit(self, model_and_params):
        sched, _ = self._sched(model_and_params)
        ok, reason = sched.submit(_req(0, deadline_s=-1.0))
        assert not ok and "deadline_s" in reason
        assert sched.metrics.snapshot()["rejected_invalid"] == 1

    def test_drain_queue_sheds_queued_keeps_inflight(self, model_and_params):
        from progen_tpu.serving import REJECT_DRAINING

        sched, _ = self._sched(model_and_params, max_slots=1)
        assert sched.submit(_req(0, length=8))[0]
        sched.step()  # r0 on the slot
        assert sched.submit(_req(1, length=8))[0]
        assert sched.submit(_req(2, length=8))[0]
        assert sched.drain_queue() == 2
        shed = sched.pop_expired()
        assert [(r.id, reason) for r, reason in shed] == [
            ("q1", REJECT_DRAINING), ("q2", REJECT_DRAINING)
        ]
        m = sched.metrics.snapshot()
        assert m["rejected_draining"] == 2 and m["queue_depth"] == 0
        # the in-flight request still runs to completion
        _, comps = sched.run_to_completion(max_steps=300)
        assert [c.request_id for c in comps] == ["q0"]

    def test_deadline_counters_in_prometheus_exposition(
        self, model_and_params
    ):
        from progen_tpu.telemetry import prometheus_text

        sched, clock = self._sched(model_and_params, max_slots=1)
        assert sched.submit(_req(0, length=12))[0]
        assert sched.submit(_req(1, length=4, deadline_s=1.0))[0]
        sched.step()
        clock["t"] = 2.0
        sched.step()
        text = prometheus_text(sched.metrics)
        assert "progen_serve_rejected_deadline_exceeded_total 1" in text
        assert "progen_serve_requests_expired_total 1" in text


class TestRequestTracing:
    """Per-request async spans: every accepted request becomes one async
    track (b/e request with nested queued/prefill/decode phases and a
    first_token instant) in the global telemetry stream, rejects become
    instants, and slot occupancy rides a counter series."""

    @pytest.fixture()
    def records(self):
        from progen_tpu.telemetry import spans

        seen = []
        spans.configure(sink=seen.append)
        try:
            yield seen
        finally:
            spans.configure()  # detach the global sink

    @staticmethod
    def _reqs(records, rid=None):
        out = [r for r in records if r.get("ev") == "req"]
        return out if rid is None else [r for r in out if r["req"] == rid]

    def test_accepted_request_is_one_closed_async_track(
        self, model_and_params, records
    ):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=4)
        for i in range(2):
            assert sched.submit(_req(i, length=6))[0]
        sched.run_to_completion(max_steps=300)
        for rid in ("q0", "q1"):
            evs = self._reqs(records, rid)
            phases = {}
            for r in evs:
                phases.setdefault(r["name"], []).append(r["ph"])
            # the four phases each open exactly once and close
            for name in ("request", "queued", "prefill", "decode"):
                assert phases[name] == ["b", "e"], (rid, name, phases)
            assert phases["first_token"] == ["n"]
            # timestamps are wall-clock and non-decreasing per request
            ts = [r["ts"] for r in evs]
            assert ts == sorted(ts)
        # request args: b request carries length, e request the yield
        done = [
            r for r in self._reqs(records, "q0")
            if r["name"] == "request" and r["ph"] == "e"
        ]
        assert done[0]["n_generated"] > 0
        # the prefill slice itself ran under a serve/prefill_chunk span
        # stamped with the request id (engine-side attribution)
        prefill_spans = [
            r for r in records
            if r.get("ev") == "B" and r.get("span") == "serve/prefill_chunk"
        ]
        assert {r["request_id"] for r in prefill_spans} == {"q0", "q1"}
        assert all(
            r["slot"] == 0 and 0 <= r["lo"] <= r["hi"] for r in prefill_spans
        )

    def test_expired_request_track_closes_with_reason(
        self, model_and_params, records
    ):
        from progen_tpu.serving import REJECT_DEADLINE

        model, params = model_and_params
        clock = {"t": 0.0}
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=8, clock=lambda: clock["t"])
        assert sched.submit(_req(0, length=12))[0]
        assert sched.submit(_req(1, length=4, deadline_s=5.0))[0]
        sched.step()  # r0 takes the only slot
        clock["t"] = 10.0
        sched.step()  # r1 expires while queued
        evs = self._reqs(records, "q1")
        phs = [(r["ph"], r["name"]) for r in evs]
        assert ("n", REJECT_DEADLINE) in phs
        assert phs[-2:] == [("e", "queued"), ("e", "request")]
        closing = evs[-1]
        assert closing["reason"] == REJECT_DEADLINE
        # it never reached a slot: no prefill/decode phases
        assert not any(r["name"] in ("prefill", "decode") for r in evs)

    def test_submit_rejects_are_instants_not_tracks(
        self, model_and_params, records
    ):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=1)
        assert sched.submit(_req(0, length=6))[0]
        ok, reason = sched.submit(_req(1, length=6))  # queue_full
        assert not ok and reason == REJECT_QUEUE_FULL
        sched.submit(_req(2, length=99))  # invalid
        rejects = [
            r for r in records if r.get("ev") == "request_rejected"
        ]
        assert [(r["req"], r["reason"]) for r in rejects] == [
            ("q1", REJECT_QUEUE_FULL), ("q2", "invalid")
        ]
        # a rejected submit never opened an async track
        assert self._reqs(records, "q1") == []
        assert self._reqs(records, "q2") == []

    def test_slot_occupancy_counter_series(
        self, model_and_params, records
    ):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        sched = Scheduler(engine, max_queue=8)
        for i in range(3):
            assert sched.submit(_req(i, length=6))[0]
        sched.run_to_completion(max_steps=300)
        slots = [r for r in records if r.get("ev") == "slots"]
        assert slots, "no slot-occupancy records emitted"
        # every sample is internally consistent with the pool size
        for r in slots:
            assert r["in_use"] + r["free"] == 2
            assert 0 <= r["in_use"] <= 2
        # emitted on change only: no consecutive duplicates
        series = [r["in_use"] for r in slots]
        assert all(a != b for a, b in zip(series, series[1:]))
        assert series[-1] == 0  # drained pool at completion

    def test_itl_observed_per_inter_token_gap(
        self, model_and_params, records
    ):
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=2)
        assert sched.submit(_req(0, length=10))[0]
        sched.run_to_completion(max_steps=300)
        m = sched.metrics.snapshot()
        done = [
            r for r in self._reqs(records, "q0")
            if r["name"] == "request" and r["ph"] == "e"
        ]
        n_generated = done[0]["n_generated"]
        # one gap per consecutive token pair of the single request
        assert m["itl_s_count"] == n_generated - 1
        assert m["ttft_s_count"] == 1

    def test_itl_quantiles_in_prometheus_exposition(
        self, model_and_params
    ):
        from progen_tpu.telemetry import prometheus_text

        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=2)
        # declared at construction: a FRESH scheduler already exposes
        # the summary family at zero (absent family = broken exporter)
        text0 = prometheus_text(sched.metrics)
        assert "progen_serve_itl_seconds_count 0" in text0
        assert 'progen_serve_itl_seconds{quantile="0.5"} 0' in text0
        assert "progen_serve_ttft_seconds_count 0" in text0
        assert "progen_serve_latency_seconds_count 0" in text0
        assert sched.submit(_req(0, length=10))[0]
        sched.run_to_completion(max_steps=300)
        text = prometheus_text(sched.metrics)
        for q in ("0.5", "0.95", "0.99"):
            assert f'progen_serve_itl_seconds{{quantile="{q}"}}' in text
        count = [
            ln for ln in text.splitlines()
            if ln.startswith("progen_serve_itl_seconds_count")
        ]
        assert count and float(count[0].split()[1]) > 0


class TestTelemetryHooks:
    def test_no_record_is_built_without_a_sink_or_a_tap(
        self, model_and_params, monkeypatch
    ):
        """_req_event and _emit_slots keep their docstring's promise: a
        process that records nothing pays for no record. Whether a record
        was built shows in whether ``emit`` was reached."""
        from progen_tpu.telemetry import spans

        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=2)
        tel = spans.get_telemetry()
        reached = []
        monkeypatch.setattr(tel, "emit", reached.append)
        monkeypatch.setattr(spans, "EMIT_TAPS", [])
        monkeypatch.setattr(tel, "_sink", None)
        sched._req_event("n", "r0", "first_token")
        sched._last_slots_emitted = None
        sched._emit_slots()
        assert reached == []
        assert sched.metrics.snapshot()["slot_occupancy"] == 0  # gauges still set
        monkeypatch.setattr(tel, "_sink", lambda rec: None)
        sched._req_event("n", "r0", "first_token", trace="t-1")
        sched._last_slots_emitted = None
        sched._emit_slots()
        assert [r["ev"] for r in reached] == ["req", "slots"]
        assert reached[0]["trace_id"] == "t-1" and reached[1]["in_use"] == 0

    def test_process_compile_count_is_a_gauge_beside_the_two_jit_counts(
        self, model_and_params
    ):
        from progen_tpu.telemetry import compiles

        compiles.install()  # a serving process: load_env_file() does it
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        sched = Scheduler(engine, max_queue=2)
        m = sched.metrics.snapshot()
        assert {"decode_compile_count", "prefill_compile_count",
                "xla_compile_count"} <= set(m)
        # a compile neither jit-cache count sees: an eager op at a new shape
        before = m["xla_compile_count"]
        (jnp.arange(7919) * 2).block_until_ready()
        assert sched.submit(_req(0, length=6))[0]
        sched.run_to_completion(max_steps=100)
        after = sched.metrics.snapshot()["xla_compile_count"]
        assert after == compiles.backend_compiles() > before

    def test_no_process_compile_gauge_where_nothing_counts(
        self, model_and_params, monkeypatch
    ):
        """A process that never called load_env_file() has no count to
        show: the gauge is absent, not 0."""
        from progen_tpu.telemetry import compiles

        monkeypatch.setattr(compiles, "_installed", False)
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=1, max_len=32)
        m = Scheduler(engine, max_queue=2).metrics.snapshot()
        assert "xla_compile_count" not in m
        assert "decode_compile_count" in m
