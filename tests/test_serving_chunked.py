"""Chunked prefill + prefix cache: bit-parity and accounting.

The chunked admission path exists to kill the admission stall, not to
change a single token: a request admitted chunk-at-a-time (any chunk
size, any interleaving with live decodes, hot or cold prefix cache)
must produce EXACTLY the stream the monolithic ``engine.prefill`` path
produces — which tests/test_serving.py already pins to ``sample_fast``.
Every parity test here asserts token-for-token equality between the two
admission paths on the same requests.
"""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen
from progen_tpu.serving import (
    PrefixCache,
    Request,
    Scheduler,
    ServeEngine,
)
from progen_tpu.serving.engine import PreparedParams

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


def _requests(n, with_infill=False):
    """Overlapping requests with mixed primes/lengths/knobs, long
    enough primes that chunking actually splits them."""
    rng = np.random.RandomState(13)
    knob_grid = [
        {},
        {"temperature": 0.7},
        {"top_p": 0.9},
        {"top_k": None},
        {"add_bos": True},
        {"temperature": 1.2, "top_k": 5},
    ]
    reqs = []
    for i in range(n):
        plen = int(rng.randint(6, 16))
        prime = rng.randint(1, TINY.num_tokens, size=plen)
        knobs = dict(knob_grid[i % len(knob_grid)])
        length = int(
            rng.randint(plen + 2 + knobs.get("add_bos", False), 31)
        )
        kwargs = {}
        if with_infill and i % 2 == 0:
            template = np.zeros((length,), np.int32)
            frozen = np.zeros((length,), bool)
            for p in range(plen + 1, length - 1, 3):
                frozen[p] = True
                template[p] = int(rng.randint(1, TINY.num_tokens))
            kwargs = {"template": template, "frozen": frozen}
        reqs.append(
            Request(
                id=f"r{i}", prime=prime, length=length,
                key=jax.random.PRNGKey(4000 + i), **knobs, **kwargs,
            )
        )
    return reqs


def _run(model, params, reqs, **sched_kwargs):
    """Serve ``reqs`` through a fresh engine+scheduler; returns
    ({id: completion_tokens}, {id: [streamed tokens]}, sched)."""
    engine = ServeEngine(model, params, max_slots=3, max_len=32)
    sched = Scheduler(engine, max_queue=len(reqs) + 1, **sched_kwargs)
    for req in reqs:
        ok, reason = sched.submit(req)
        assert ok, reason
    events, completions = sched.run_to_completion(max_steps=5000)
    assert len(completions) == len(reqs)
    streams = {r.id: [] for r in reqs}
    for e in events:
        streams[e.request_id].append((e.index, e.token))
    return (
        {c.request_id: c.tokens for c in completions},
        streams,
        sched,
    )


class TestChunkedParity:
    @pytest.mark.parametrize("chunk", [1, 3, 64])
    def test_chunked_matches_monolithic(self, model_and_params, chunk):
        """Same requests through the monolithic inline path and the
        chunked path (chunk sizes below, around, and ABOVE every prime
        length) — completions and streamed (index, token) pairs must be
        bit-identical."""
        model, params = model_and_params
        reqs = _requests(6)
        mono, mono_streams, _ = _run(model, params, reqs)
        chunked, chunked_streams, _ = _run(
            model, params, reqs, prefill_chunk=chunk
        )
        for req in reqs:
            np.testing.assert_array_equal(
                chunked[req.id], mono[req.id],
                err_msg=f"{req.id} diverged at prefill_chunk={chunk}",
            )
            assert chunked_streams[req.id] == mono_streams[req.id]

    def test_chunked_infill_matches_monolithic(self, model_and_params):
        """Templates/frozen masks ride the pending state and scatter
        only on the final chunk — the infill constraint must survive
        chunking bit-for-bit."""
        model, params = model_and_params
        reqs = _requests(6, with_infill=True)
        mono, _, _ = _run(model, params, reqs)
        chunked, _, _ = _run(model, params, reqs, prefill_chunk=2)
        for req in reqs:
            np.testing.assert_array_equal(chunked[req.id], mono[req.id])
            if req.frozen is not None:
                frozen = np.asarray(req.frozen, bool)
                tpl = np.asarray(req.template, np.int32)
                got = np.asarray(chunked[req.id])
                # frozen positions actually hold the template tokens
                # (cheap sanity that the constraint was applied at all)
                reached = np.arange(len(got)) < len(got)
                m = frozen & reached & (got != 0)
                assert np.all(got[m] == tpl[m])

    def test_engine_level_resume_split_points(self, model_and_params):
        """Drive begin/advance directly with ragged budgets (1, then 2,
        then the rest) and compare against a monolithic prefill of the
        same request on a twin engine: the pool state that matters —
        the produced stream — must match."""
        model, params = model_and_params
        prime = np.asarray([3, 9, 4, 17, 2, 11, 5, 8, 21, 6], np.int32)
        kwargs = dict(top_k=25, key=jax.random.PRNGKey(7))

        def drain(engine, slot, start):
            out = []
            for _ in range(40):
                sampled, was_live, finished = engine.decode_step()
                if not was_live[slot]:
                    break
                out.append(int(sampled[slot]))
                if finished[slot]:
                    break
            return out

        e1 = ServeEngine(model, params, max_slots=2, max_len=32)
        s1 = e1.acquire()
        start1 = e1.prefill(s1, prime, 24, **kwargs)
        t1 = drain(e1, s1, start1)

        e2 = ServeEngine(model, params, max_slots=2, max_len=32)
        s2 = e2.acquire()
        pending = e2.begin_prefill(s2, prime, 24, **kwargs)
        assert not pending.done
        assert e2.advance_prefill(pending, 1) is False
        assert pending.pos == 1
        assert e2.advance_prefill(pending, 2) is False
        assert pending.pos == 3
        assert e2.advance_prefill(pending, None) is True
        assert pending.start == start1
        t2 = drain(e2, s2, pending.start)
        assert t1 == t2


class TestPrefixCache:
    def test_hit_stream_bit_identical(self, model_and_params):
        """The same scaffold served cold then cache-hot: the hot
        request must stream the exact cold tokens, and the cache must
        actually have been used (hits > 0, fewer prefill positions fed
        through the model)."""
        model, params = model_and_params
        prime = np.asarray(
            [5, 12, 3, 3, 8, 19, 2, 7, 14, 9, 4, 22], np.int32
        )
        reqs = [
            Request(id="cold", prime=prime, length=28,
                    key=jax.random.PRNGKey(11)),
            Request(id="hot", prime=prime, length=28,
                    key=jax.random.PRNGKey(11)),
        ]
        mono, _, _ = _run(model, params, reqs[:1])
        cache = PrefixCache(64 << 20)
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        sched = Scheduler(engine, max_queue=4, prefill_chunk=4,
                          prefix_cache=cache)
        ok, _ = sched.submit(reqs[0])
        assert ok
        _, comps0 = sched.run_to_completion(max_steps=2000)
        ok, _ = sched.submit(reqs[1])
        assert ok
        _, comps1 = sched.run_to_completion(max_steps=2000)

        np.testing.assert_array_equal(comps0[0].tokens, mono["cold"])
        np.testing.assert_array_equal(comps1[0].tokens, mono["cold"])
        assert cache.hits >= 1
        m = sched.metrics.snapshot()
        assert m["prefix_cache_hits"] >= 1
        # the hot request skipped its whole feed region
        assert m["prefix_cache_hit_tokens"] >= len(prime) - 1

    def test_hit_with_different_sampling_knobs(self, model_and_params):
        """Cache keys are sampling-irrelevant: a hit may seed a request
        with different temperature/key, and the result must equal that
        request's own monolithic decode (NOT the cached request's)."""
        model, params = model_and_params
        prime = np.asarray([4, 9, 17, 2, 6, 13, 21, 3, 8, 5], np.int32)
        r_a = Request(id="a", prime=prime, length=26,
                      key=jax.random.PRNGKey(1))
        r_b = Request(id="b", prime=prime, length=26, temperature=0.7,
                      top_k=5, key=jax.random.PRNGKey(2))
        mono, _, _ = _run(model, params, [r_a, r_b])
        cache = PrefixCache(64 << 20)
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        sched = Scheduler(engine, max_queue=4, prefill_chunk=3,
                          prefix_cache=cache)
        for r in (r_a, r_b):
            ok, _ = sched.submit(r)
            assert ok
        _, comps = sched.run_to_completion(max_steps=2000)
        by_id = {c.request_id: c.tokens for c in comps}
        np.testing.assert_array_equal(by_id["a"], mono["a"])
        np.testing.assert_array_equal(by_id["b"], mono["b"])
        assert cache.hits >= 1

    def test_lru_byte_budget_eviction(self):
        """Unit-level LRU: inserting past the byte budget evicts the
        least-recently-used snapshot first; bytes never exceed the
        budget; a refreshed entry survives over a stale one."""
        snap = {"k": np.zeros((1024,), np.float32)}  # 4096 bytes
        cache = PrefixCache(3 * 4096)
        rows = [np.full((8,), i + 1, np.int32) for i in range(4)]
        for i in range(3):
            assert cache.insert(rows[i], 8, snap)
        assert len(cache) == 3 and cache.bytes == 3 * 4096
        # refresh row0 so row1 becomes LRU
        depth, got = cache.lookup(rows[0], 8)
        assert depth == 8 and got is snap
        cache.insert(rows[3], 8, snap)
        assert len(cache) == 3
        assert cache.bytes <= cache.max_bytes
        assert cache.evictions == 1
        assert cache.lookup(rows[1], 8)[1] is None  # LRU was evicted
        assert cache.lookup(rows[0], 8)[1] is not None
        assert cache.lookup(rows[3], 8)[1] is not None

    def test_lookup_depth_capped_and_deepest_wins(self):
        snap = {"k": np.zeros((16,), np.float32)}
        cache = PrefixCache(1 << 20)
        row = np.arange(1, 17, dtype=np.int32)
        cache.insert(row, 4, snap)
        cache.insert(row, 8, snap)
        depth, got = cache.lookup(row, 16)
        assert depth == 8 and got is not None
        # feed region shorter than the deepest snapshot: cap applies
        depth, got = cache.lookup(row, 6)
        assert depth == 4
        # diverging prefix: no hit at all
        other = row.copy()
        other[2] = 30
        assert cache.lookup(other, 16) == (0, None)

    def test_oversized_snapshot_is_skipped(self):
        cache = PrefixCache(100)
        big = {"k": np.zeros((1024,), np.float32)}
        assert not cache.insert(np.arange(4, dtype=np.int32), 4, big)
        assert len(cache) == 0 and cache.bytes == 0

    def test_commit_params_clears_snapshots(self, model_and_params):
        """Hot reload invalidation: snapshots were computed under the
        old weights; commit_params must drop them (counters survive)."""
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        cache = PrefixCache(64 << 20)
        engine.set_prefix_cache(cache)
        slot = engine.acquire()
        pending = engine.begin_prefill(
            slot, np.asarray([3, 7, 2, 9, 4], np.int32), 16,
            key=jax.random.PRNGKey(0),
        )
        engine.advance_prefill(pending, 2)
        assert len(cache) >= 1
        inserts = cache.inserts
        engine.commit_params(
            PreparedParams(engine.params)
        )
        assert len(cache) == 0 and cache.bytes == 0
        assert cache.inserts == inserts  # counters not reset


class TestCompileFlatness:
    def test_compile_counts_flat_under_interleaved_traffic(
        self, model_and_params
    ):
        """After one warmup admission, mixed chunked traffic — varied
        primes, chunk boundaries, prefix-cache hits and misses — must
        not compile a single new program: the chunk program's bounds
        are traced, the finish program is shape-fixed, decode is
        untouched."""
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=3, max_len=32)
        cache = PrefixCache(64 << 20)
        sched = Scheduler(engine, max_queue=16, prefill_chunk=2,
                          prefix_cache=cache)
        warm = Request(id="warm", prime=np.asarray([3, 5, 7], np.int32),
                       length=12, key=jax.random.PRNGKey(0))
        ok, _ = sched.submit(warm)
        assert ok
        sched.run_to_completion(max_steps=2000)
        decode_after = ServeEngine.decode_compile_count()
        prefill_after = ServeEngine.prefill_compile_count()

        for req in _requests(6):
            ok, reason = sched.submit(req)
            assert ok, reason
        sched.run_to_completion(max_steps=5000)
        assert ServeEngine.decode_compile_count() == decode_after
        assert ServeEngine.prefill_compile_count() == prefill_after
        m = sched.metrics.snapshot()
        assert m["decode_compile_count"] == decode_after
        assert m["prefill_compile_count"] == prefill_after


class TestOccupancyMidChunk:
    def test_slot_counts_occupied_during_chunked_prefill(
        self, model_and_params
    ):
        """The gauge fix: a slot mid-chunked-prefill is OCCUPIED. With
        chunk=1 and a long prime, the pending admission spans many
        steps — slot_occupancy must show 1 (and slots_free max-1) the
        whole way, not flap free between chunks."""
        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        sched = Scheduler(engine, max_queue=4, prefill_chunk=1)
        prime = np.arange(1, 13, dtype=np.int32)
        req = Request(id="long", prime=prime, length=30,
                      key=jax.random.PRNGKey(3))
        ok, _ = sched.submit(req)
        assert ok
        saw_pending = 0
        while sched.has_work:
            sched.step()
            if sched._pending is not None:
                saw_pending += 1
                m = sched.metrics.snapshot()
                assert m["slot_occupancy"] == 1
                assert m["slots_free"] == 1
        # the prime is long and the chunk is 1: the pending state must
        # have been observable across multiple steps
        assert saw_pending >= 3
        m = sched.metrics.snapshot()
        assert m["slot_occupancy"] == 0
        assert m["slots_free"] == 2


class TestStages:
    """The program's own timing of a step (telemetry.spans.stage): one
    step that admits, prefills a whole prime in one chunk, activates and
    decodes passes every stage of the scheduler's and the engine's
    tables, nested as the code nests, without changing a token."""

    PARENT = {
        "serve/admit": "serve/step",
        "serve/prepare": "serve/admit",
        "serve/prefill_chunk": "serve/step",
        "serve/prefill_dispatch": "serve/prefill_chunk",
        "serve/prefix_insert": "serve/prefill_chunk",
        "serve/prefill_finish": "serve/prefill_chunk",
        "serve/decode": "serve/step",
        "serve/decode_dispatch": "serve/decode",
        "serve/decode_fetch": "serve/decode",
        "serve/emit": "serve/step",
        "serve/journal": "serve/step",
    }

    def test_one_step_with_a_chunk_passes_every_stage_once(
        self, model_and_params, tmp_path
    ):
        import time

        from progen_tpu.serving.journal import RequestJournal
        from progen_tpu.telemetry.spans import get_telemetry

        model, params = model_and_params
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        journal = RequestJournal(tmp_path / "journal.jsonl")
        sched = Scheduler(engine, max_queue=4, prefill_chunk=16,
                          prefix_cache=PrefixCache(max_bytes=1 << 24),
                          journal=journal)
        first = Request(id="first", prime=np.array([5, 6, 7]), length=30,
                        key=jax.random.PRNGKey(1))
        assert sched.submit(first)[0]
        sched.step()  # "first" is decoding from here on
        assert sched.active_ids == ["first"]

        tel = get_telemetry()
        t_submit = time.perf_counter()
        second = Request(id="second", prime=np.array([9, 8, 7, 6]),
                         length=12, key=jax.random.PRNGKey(2))
        assert sched.submit(second)[0]
        t_step = time.perf_counter()
        events, _ = sched.step()
        t_end = time.perf_counter()
        # "second" went live in the decode step this call LAUNCHED; the
        # one it fetched was launched a call earlier, before the admission
        assert {e.request_id for e in events} == {"first"}

        submits = [r for r in tel.stages(since=t_submit, until=t_step)
                   if r[2] == "serve/submit"]
        assert len(submits) == 1
        assert submits[0][1] is None  # submit() runs outside any step

        recs = tel.stages(since=t_step, until=t_end)
        by_name = {}
        for r in recs:
            by_name.setdefault(r[2], []).append(r)
        assert set(by_name) == set(self.PARENT) | {"serve/step"}
        # admission runs again after the activation: twice in this step,
        # once with work
        assert len(by_name.pop("serve/admit")) == 2
        assert all(len(v) == 1 for v in by_name.values()), {
            k: len(v) for k, v in by_name.items()
        }
        by_seq = {r[0]: r for r in recs}
        for seq, parent, name, t0, dur, _ in recs:
            if name == "serve/step":
                assert parent is None
                continue
            p = by_seq[parent]
            assert p[2] == self.PARENT[name], (name, p[2])
            assert p[3] <= t0 and t0 + dur <= p[3] + p[4] + 1e-9
        # the scheduler's decode_time_s is the serve/decode stage's seconds
        decode_s = sched.metrics.structured()["counters"]["decode_time_s"]
        first_step = [r for r in tel.stages(until=t_submit)
                      if r[2] == "serve/decode"][-1]
        assert decode_s == pytest.approx(first_step[4] + by_name["serve/decode"][0][4])
        events, _ = sched.step()
        assert {e.request_id for e in events} == {"first", "second"}
        journal.close()

    def test_stages_change_no_token(self, model_and_params):
        """The stream a staged engine serves is the standalone
        decoder's, token for token (chunked, prefix cache on)."""
        from progen_tpu.sampling import sample_fast

        model, params = model_and_params
        reqs = _requests(4)
        got, _, _ = _run(model, params, reqs, prefill_chunk=3,
                         prefix_cache=PrefixCache(max_bytes=1 << 24))
        for req in reqs:
            want = np.asarray(sample_fast(
                req.key, model, params, jnp.asarray(req.prime), req.length,
                top_k=req.top_k, add_bos=req.add_bos,
                temperature=req.temperature, top_p=req.top_p,
            ))
            np.testing.assert_array_equal(got[req.id], want, err_msg=req.id)


# ----- an admission hands its batch-1 cache on by donation (PR 37) --------

REPO = Path(__file__).resolve().parents[1]
ARGUMENT = re.compile(r"%arg\d+: tensor<[^>]*>( \{[^}]*\})?")


def _arguments(lowered):
    """The marks on each argument of a lowered program's ``main``, in
    order: ``tf.aliasing_output = n`` is jit's record of a donation it
    matched with output ``n``."""
    text = lowered.as_text()
    head = text[text.index("func.func public @main("):text.index(") -> (")]
    return [m.group(1) or "" for m in ARGUMENT.finditer(head)]


def _operands(length):
    return (np.int32(1), jnp.zeros((length,), jnp.int32), np.int32(5),
            np.int32(length), jax.random.PRNGKey(0), np.float32(1.0),
            np.float32(2.0), np.int32(8), np.bool_(True),
            np.zeros((length,), np.int32), np.zeros((length,), bool))


def _latent_moe():
    from progen_tpu.config import load_toml_config
    from progen_tpu.models import build_model

    model = build_model(load_toml_config(
        str(REPO / "configs/model/latent-moe-small.toml")
    ))
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


def _drain(engine, slot):
    out = []
    for _ in range(64):
        sampled, was_live, finished = engine.decode_step()
        if not was_live[slot]:
            break
        out.append(int(sampled[slot]))
        if finished[slot]:
            break
    return out


class TestAdmissionOwnsItsCache:
    """From ``begin_prefill`` to the slot's activation the batch-1 cache
    is the admission's alone: the chunk program and the scatter take it
    donated, the scatter hands it back holding the model's initial
    values, nothing but those programs runs, and only a prefix cache
    makes a copy."""

    @pytest.mark.parametrize("program", ["chunk", "finish"])
    def test_the_lowered_programs_alias_every_cache_leaf(
        self, model_and_params, program
    ):
        from progen_tpu.serving import engine as E

        model, params = model_and_params
        eng = ServeEngine(model, params, max_slots=3, max_len=32)
        n_cache = len(jax.tree.leaves(eng.slots.cache))
        if program == "chunk":
            n_kept = len(jax.tree.leaves(eng.served_params))
            marks = _arguments(E._prefill_chunk.lower(
                eng.model, eng.served_params, eng.new_cache(),
                jnp.zeros((32,), jnp.int32), np.int32(0), np.int32(5),
            ))
            kept, given = marks[:n_kept], marks[n_kept:n_kept + n_cache]
            rest = marks[n_kept + n_cache:]
            assert len(rest) == 3  # the row and the two bounds
        else:
            n_pool = len(jax.tree.leaves(eng.slots))
            marks = _arguments(E._prefill_finish.lower(
                eng.slots, eng.new_cache(), *_operands(32),
                new_cache=eng._build_cache,
            ))
            kept, given = [], marks[:n_pool + n_cache]
            rest = marks[n_pool + n_cache:]
            assert len(rest) == 11
        assert len(given) >= n_cache > 0
        outputs = [re.search(r"tf\.aliasing_output = (\d+)", m) for m in given]
        assert all(outputs), given
        assert len({m.group(1) for m in outputs}) == len(given)
        assert not any(kept) and not any(rest)  # weights, operands: read

    @pytest.mark.parametrize("in_flight", [1, 2])
    def test_an_admission_runs_its_chunks_and_the_scatter_and_nothing_else(
        self, model_and_params, monkeypatch, in_flight
    ):
        """Prime of 10 at 4 positions a call: 3 chunks + 1 scatter, no
        program for a scalar, a key or a row, and none for a fresh tree
        but where a second admission is in flight and the recycled tree
        is taken."""
        from jax._src import dispatch

        from progen_tpu.serving import engine as E

        model, params = model_and_params
        eng = ServeEngine(model, params, max_slots=3, max_len=32)
        prime = np.arange(1, 11, dtype=np.int32)
        eng.prefill(eng.acquire(), prime, 24, seed=3)  # compiled, recycled

        calls = {}

        def counted(name, fn):
            def call(*args, **kwargs):
                calls[name] = calls.get(name, 0) + 1
                return fn(*args, **kwargs)
            return call

        for name in ("_prefill_chunk", "_prefill_finish", "_copy_cache"):
            monkeypatch.setattr(E, name, counted(name, getattr(E, name)))
        # every eagerly applied primitive is a program of its own, looked
        # up here (a ``jnp.int32(n)`` is a ``convert_element_type``: the
        # parent ran 13 of them an admission of three chunks)
        monkeypatch.setattr(dispatch, "xla_primitive_callable", counted(
            "eager", dispatch.xla_primitive_callable))
        # ... but the seed's key, made on the CPU backend, which under
        # these tests is the default one
        real_key = E.seed_key

        def host_key(seed):
            before = calls.get("eager", 0)
            key = real_key(seed)
            calls["eager"] = before
            assert isinstance(key, np.ndarray)
            return key

        monkeypatch.setattr(E, "seed_key", host_key)

        recycled = eng._spare_cache
        assert recycled is not None
        pendings = [
            eng.begin_prefill(eng.acquire(), prime, 24, seed=4 + i,
                              temperature=0.7, top_p=0.9, top_k=5)
            for i in range(in_flight)
        ]
        # the first takes the tree the last scatter handed back; a second
        # in flight beside it gets one from the builder's program
        assert pendings[0].cache is recycled and eng._spare_cache is None
        assert all(p.cache is not recycled for p in pendings[1:])
        for pending in pendings:
            while not eng.advance_prefill(pending, 4):
                pass
            assert pending.cache is None  # handed on
        assert eng._spare_cache is not None
        assert calls.pop("eager", 0) == 0
        assert calls == {"_prefill_chunk": 3 * in_flight,
                         "_prefill_finish": in_flight}
        assert eng.pop_counters()["prefill_cache_copies"] == 0

    @pytest.mark.parametrize("family", ["progen", "latent_moe"])
    def test_the_third_admission_streams_what_a_first_does(
        self, model_and_params, family
    ):
        """The tree a scatter hands back is as good as new: the third
        admission on one engine (its tree fed, scattered and handed back
        twice before) streams what the first on a fresh engine does."""
        model, params = (
            model_and_params if family == "progen" else _latent_moe()
        )
        vocab = model.config.num_tokens
        rng = np.random.default_rng(5)
        primes = [rng.integers(1, vocab, size=n).astype(np.int32)
                  for n in (13, 6, 9)]

        def admit(engine, prime, seed):
            slot = engine.acquire()
            pending = engine.begin_prefill(slot, prime, 24, add_bos=True,
                                           seed=seed)
            while not engine.advance_prefill(pending, 4):
                pass
            return slot

        first = ServeEngine(model, params, max_slots=2, max_len=32)
        want = _drain(first, admit(first, primes[2], 9))
        assert len(want) > 2

        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        for prime, seed in zip(primes[:2], (1, 2)):
            slot = admit(engine, prime, seed)
            for _ in range(3):
                engine.decode_step()
            engine.release(slot)
        assert _drain(engine, admit(engine, primes[2], 9)) == want

    def test_a_prefix_caches_snapshots_are_its_own(self, model_and_params):
        """With a prefix cache attached a tree is copied at the two
        points where it would be shared, and there alone: a snapshot
        inserted at a chunk boundary outlives the chunks and the scatter
        that follow it, a hit leaves it whole for the next hit, and
        ``prefill_cache_copies`` counts both."""
        from progen_tpu.sampling import feed_tokens

        model, params = model_and_params
        prime = np.asarray([5, 12, 3, 3, 8, 19, 2, 7, 14, 9, 4, 22], np.int32)
        cache = PrefixCache(64 << 20)
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        sched = Scheduler(engine, max_queue=4, prefill_chunk=4,
                          prefix_cache=cache)

        def serve(rid):
            assert sched.submit(Request(id=rid, prime=prime, length=28,
                                        key=jax.random.PRNGKey(11)))[0]
            _, comps = sched.run_to_completion(max_steps=2000)
            return comps[0].tokens

        def stored(depth):
            (tree,) = [entry[0] for (d, _), entry in cache._entries.items()
                       if d == depth]
            return tree

        cold = serve("cold")
        assert cache.inserts == 3 and cache.hits == 0  # depths 4, 8, 11
        row = np.zeros((32,), np.int32)
        row[:12] = prime
        for depth in (4, 8, 11):
            want = feed_tokens(engine.model, engine.params,
                               engine._build_cache(), jnp.asarray(row)[None],
                               0, depth)
            for got, ref in zip(jax.tree.leaves(stored(depth)),
                                jax.tree.leaves(want)):
                assert not got.is_deleted()
                np.testing.assert_array_equal(np.asarray(got),
                                              np.asarray(ref))
        for n, rid in enumerate(("hot", "hot again"), start=1):
            np.testing.assert_array_equal(serve(rid), cold)
            assert cache.hits == n and cache.inserts == 3
            assert not any(leaf.is_deleted()
                           for leaf in jax.tree.leaves(stored(11)))
        copies = sched.metrics.snapshot()["prefill_cache_copies"]
        assert copies == cache.inserts + cache.hits == 5

    def test_without_a_prefix_cache_nothing_is_copied(self, model_and_params):
        model, params = model_and_params
        _, _, sched = _run(model, params, _requests(4), prefill_chunk=3)
        assert sched.metrics.snapshot()["prefill_cache_copies"] == 0
