"""The served loop keeps one decode step ahead of the host.

``Scheduler.step()`` launches decode step N+1 before it fetches step N's
tokens, and nothing between two launches reads the device. What that may
not change: any request's token stream, the journal's guarantee, who a
step's tokens belong to. What it does change: a freed slot is refilled
one decode step later. Held here for ProGen and for the latent family at
the tests' size, against ``sample_fast`` and against a strictly serial
loop over ``engine.decode_step()`` with nothing in flight.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.config import ProGenConfig, load_toml_config
from progen_tpu.models import build_model
from progen_tpu.models.progen import ProGen
from progen_tpu.sampling import _prepare_seq, sample_fast
from progen_tpu.serving import (
    PrefixCache,
    Request,
    Scheduler,
    ServeEngine,
)
from progen_tpu.serving.engine import seed_key
from progen_tpu.serving.journal import RequestJournal

REPO = Path(__file__).resolve().parents[1]

TINY = ProGenConfig(
    num_tokens=32, dim=32, seq_len=32, depth=2, window_size=8,
    global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2, dtype="float32",
)


@pytest.fixture(scope="module")
def progen():
    from flax.core import meta

    model = ProGen(TINY)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, TINY.seq_len), jnp.int32)
    )
    return model, meta.unbox(variables)["params"]


@pytest.fixture(scope="module")
def latent():
    small = load_toml_config(str(REPO / "configs/model/latent-moe-small.toml"))
    model = build_model(small)
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture
def family(request, progen, latent):
    return {"progen": progen, "latent": latent}[request.param]


def _requests(model, n, seed=7):
    """Mixed primes, lengths and knobs; more requests than slots, so
    admissions, chunks and completions interleave with decoding."""
    vocab, top = model.config.num_tokens, min(model.config.seq_len, 32)
    rng = np.random.RandomState(seed)
    knobs = [{}, {"temperature": 0.7}, {"top_p": 0.9}, {"add_bos": True},
             {"temperature": 1.2, "top_k": 5}]
    out = []
    for i in range(n):
        plen = int(rng.randint(3, 12))
        kw = dict(knobs[i % len(knobs)])
        length = int(rng.randint(plen + 3 + kw.get("add_bos", False), top))
        if i % 3 == 0:  # an infill template: exact lengths, as the cells'
            start = plen + kw.get("add_bos", False)
            template, frozen = np.zeros((length,), np.int32), np.zeros((length,), bool)
            for p in range(start + 1, length - 1, 3):
                frozen[p], template[p] = True, int(rng.randint(1, vocab))
            kw.update(template=template, frozen=frozen)
        out.append(Request(id=f"r{i}", prime=rng.randint(1, vocab, size=plen),
                           length=length, key=jax.random.PRNGKey(900 + i), **kw))
    return out


def _serial(engine, requests, chunk):
    """The strictly serial loop: admit a chunk, ``decode_step()`` with
    nothing in flight, read, release. Returns ({id: streamed tokens},
    {id: collected row})."""
    queue, pending, active = list(requests), None, {}
    streams, rows = {}, {}
    while queue or pending is not None or active:
        if pending is None and queue and engine._free:
            req = queue.pop(0)
            pending = (req, engine.begin_prefill(
                engine.acquire(), req.prime, req.length, top_k=req.top_k,
                add_bos=req.add_bos, temperature=req.temperature,
                top_p=req.top_p, key=req.key, template=req.template,
                frozen=req.frozen,
            ))
        if pending is not None and engine.advance_prefill(pending[1], chunk):
            active[pending[1].slot] = pending[0]
            streams[pending[0].id] = []
            pending = None
        if not active:
            continue
        assert not engine.step_in_flight
        sampled, was_live, finished = engine.decode_step()
        assert not engine.step_in_flight  # a direct caller never runs ahead
        for slot, req in sorted(active.items()):
            assert was_live[slot]
            streams[req.id].append(int(sampled[slot]))
            if finished[slot]:
                rows[req.id] = engine.collect(slot)
                engine.release(slot)
                del active[slot]
    return streams, rows


def _scheduled(engine, requests, **kw):
    sched = Scheduler(engine, max_queue=len(requests) + 1, **kw)
    for req in requests:
        assert sched.submit(req) == (True, None)
    events, completions = sched.run_to_completion(max_steps=5000)
    streams = {r.id: [] for r in requests}
    for e in events:
        streams[e.request_id].append(e.token)
    return streams, {c.request_id: c.tokens for c in completions}, sched


# ----- (a) streams ---------------------------------------------------------


@pytest.mark.parametrize("chunk", [0, 1, 4])
@pytest.mark.parametrize("family", ["progen", "latent"], indirect=True)
def test_streams_are_the_serial_loops(family, chunk):
    model, params = family
    requests = _requests(model, 7)
    want, want_rows = _serial(
        ServeEngine(model, params, max_slots=3, max_len=32),
        requests, chunk or None,
    )
    engine = ServeEngine(model, params, max_slots=3, max_len=32)
    got, rows, sched = _scheduled(engine, requests, prefill_chunk=chunk)
    assert got == want
    for req in requests:
        assert (rows[req.id] == want_rows[req.id]).all() and got[req.id]
    m = sched.metrics.snapshot()
    assert m["decode_steps_ahead"] == m["decode_steps"] > 0


@pytest.mark.parametrize("chunk", [0, 3])
def test_progen_streams_are_sample_fasts(progen, chunk):
    model, params = progen
    requests = _requests(model, 6, seed=11)
    engine = ServeEngine(model, params, max_slots=2, max_len=32)
    _, rows, _ = _scheduled(engine, requests, prefill_chunk=chunk)
    for req in requests:
        want = np.asarray(sample_fast(
            req.key, model, params, jnp.asarray(req.prime), req.length,
            top_k=req.top_k, add_bos=req.add_bos,
            temperature=req.temperature, top_p=req.top_p,
            template=req.template, frozen=req.frozen,
        ))
        assert (rows[req.id] == want).all(), req.id


def test_a_freed_slot_is_refilled_one_decode_step_later(progen):
    """What moved: the host sees that step N freed a slot after N+1 was
    launched, so the slot's next request is live first in N+2."""
    model, params = progen
    engine = ServeEngine(model, params, max_slots=2, max_len=32)
    sched = Scheduler(engine, max_queue=4)
    for rid, prime, length in (("keep", [3, 4, 5], 12), ("a", [6, 7, 8], 6),
                               ("b", [9, 8], 5)):
        assert sched.submit(Request(id=rid, prime=np.array(prime),
                                    length=length, seed=len(prime)))[0]
    per_step, n = [], {}
    while sched.has_work:
        events, completions = sched.step()
        per_step.append("+".join(e.request_id for e in events))
        n.update({c.request_id: c.n_generated for c in completions})
    # "a" draws 3 tokens. The fetch of its last step frees the slot in
    # call 3; call 4 admits "b" behind the step launched meanwhile, in
    # which the slot is dead, and "b" streams from call 5: a serial loop
    # would have streamed it in call 4
    assert n["a"] == n["b"] == 3 and n["keep"] > 7
    assert per_step == (["keep+a"] * 3 + ["keep"] + ["keep+b"] * 3
                        + ["keep"] * (n["keep"] - 7))
    m = sched.metrics.snapshot()
    assert m["decode_steps"] == n["keep"]
    assert m["decode_tokens"] == n["keep"] + 3 + 3


# ----- (b) cancellation with a step in flight --------------------------------


@pytest.mark.parametrize("family", ["progen", "latent"], indirect=True)
def test_a_cancelled_slots_token_in_flight_reaches_nobody(family, tmp_path):
    model, params = family
    engine = ServeEngine(model, params, max_slots=2, max_len=32)
    journal = RequestJournal(tmp_path / "journal.jsonl")
    sched = Scheduler(engine, max_queue=4, journal=journal)
    keep = Request(id="keep", prime=np.array([3, 4, 5]), length=30, seed=1)
    old = Request(id="old", prime=np.array([6, 7, 8, 9]), length=30, seed=2)
    new = Request(id="new", prime=np.array([9, 8]), length=12, seed=3)
    assert sched.submit(keep)[0] and sched.submit(old)[0]
    events = []
    for _ in range(4):
        events += sched.step()[0]
    assert {e.request_id for e in events} == {"keep", "old"}
    old_slot = next(s for s, r in sched._active.items() if r.req.id == "old")

    # a step is in flight, with "old" live in it
    assert engine.step_in_flight
    assert sched.cancel("old") and not sched.cancel("old")
    assert sched.submit(new)[0]
    # this call admits "new" into the slot "old" held, then fetches the
    # step that was launched while "old" still decoded there
    after, _ = sched.step()
    assert next(s for s, r in sched._active.items()
                if r.req.id == "new") == old_slot
    assert {e.request_id for e in after} == {"keep"}
    rest, completions = sched.run_to_completion(max_steps=200)
    journal.close()
    assert not any(e.request_id == "old" for e in after + rest)

    # "new" streams what it streams alone
    alone, _, _ = _scheduled(
        ServeEngine(model, params, max_slots=2, max_len=32),
        [Request(id="new", prime=np.array([9, 8]), length=12, seed=3)],
    )
    got = [e for e in after + rest if e.request_id == "new"]
    assert [e.token for e in got] == alone["new"]
    assert [e.index for e in got] == list(range(2, 12))
    assert {c.request_id for c in completions} == {"keep", "new"}

    lines = [json.loads(x) for x in
             (tmp_path / "journal.jsonl").read_text().splitlines()]
    tokens = [(r["req"], r["index"]) for r in lines if r["op"] == "token"]
    assert len(tokens) == len(set(tokens))  # none journaled twice
    assert sorted(tokens) == sorted(
        (e.request_id, e.index) for e in events + after + rest
    )
    done = {r["req"]: r for r in lines if r["op"] == "done"}
    assert done["old"]["status"] == "cancelled"
    assert done["old"]["n_generated"] == sum(
        e.request_id == "old" for e in events
    )
    assert sched.metrics.snapshot()["requests_cancelled"] == 1


def test_cancel_finds_a_request_queued_or_mid_prefill(progen):
    model, params = progen
    engine = ServeEngine(model, params, max_slots=1, max_len=32)
    sched = Scheduler(engine, max_queue=4, prefill_chunk=2)
    for i, rid in enumerate(("mid", "queued", "served")):
        assert sched.submit(Request(id=rid, prime=np.arange(1, 9),
                                    length=12, seed=i))[0]
    sched.step()  # "mid" holds the slot, two positions fed
    assert sched._pending.req.id == "mid" and engine.num_active == 1
    assert sched.cancel("mid") and engine.num_active == 0
    assert sched.cancel("queued") and sched.queue_depth == 1
    assert not sched.cancel("nobody")
    events, completions = sched.run_to_completion(max_steps=100)
    assert {e.request_id for e in events} == {"served"}
    assert [c.request_id for c in completions] == ["served"]
    assert not engine.step_in_flight and not engine.any_live


# ----- (c) no read of the device between launches ------------------------------


class _CountingNumpy:
    """``numpy`` as a serving module sees it, noting every conversion of
    a device array. (On the CPU ``jax.transfer_guard_device_to_host``
    guards nothing and ``np.asarray`` of a device array goes through the
    buffer protocol, past every hook of the array's own.)"""

    def __init__(self, reads):
        self._reads = reads

    def __getattr__(self, name):
        return getattr(np, name)

    def asarray(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self._reads.append("np.asarray")
        return np.asarray(a, *args, **kw)

    def array(self, a, *args, **kw):
        if isinstance(a, jax.Array):
            self._reads.append("np.array")
        return np.array(a, *args, **kw)


@pytest.fixture
def host_reads(monkeypatch):
    """Every device-to-host read the served path makes, as a list:
    ``jax.device_get`` (one entry a call), ``bool`` / ``int`` / ``float``
    / ``item`` / ``tolist`` of a device array, and ``np.asarray`` /
    ``np.array`` of one inside the serving modules."""
    from jax._src import array as jax_array

    import progen_tpu.sampling
    import progen_tpu.serving.engine
    import progen_tpu.serving.prefix_cache
    import progen_tpu.serving.scheduler

    reads, quiet = [], [False]
    value = jax_array.ArrayImpl.__dict__["_value"]

    def _value(self):
        if not quiet[0]:
            reads.append("value")
        return value.__get__(self)

    device_get = jax.device_get

    def _device_get(x):
        reads.append("device_get")
        quiet[0] = True
        try:
            return device_get(x)
        finally:
            quiet[0] = False

    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(_value))
    monkeypatch.setattr(jax, "device_get", _device_get)
    for module in (progen_tpu.sampling, progen_tpu.serving.engine,
                   progen_tpu.serving.prefix_cache,
                   progen_tpu.serving.scheduler):
        monkeypatch.setattr(module, "np", _CountingNumpy(reads))
    return reads


def test_the_hooks_see_a_read(host_reads):
    """The detector detects: each kind of read it claims to see."""
    import progen_tpu.serving.engine as engine_module

    x = jnp.arange(3) + 1
    bool(x[0]), int(x[1]), x.tolist()
    assert host_reads == ["value"] * 3
    engine_module.np.asarray(x)
    jax.device_get((x, x))
    assert host_reads[3:] == ["np.asarray", "device_get"]


@pytest.mark.parametrize("family", ["progen", "latent"], indirect=True)
def test_one_wait_on_the_device_a_step_and_none_between(family, tmp_path,
                                                        host_reads):
    model, params = family
    engine = ServeEngine(model, params, max_slots=3, max_len=32)
    cache = None if engine.slot_batched else PrefixCache(max_bytes=1 << 24)
    sched = Scheduler(engine, max_queue=8, prefill_chunk=4,
                      prefix_cache=cache,
                      journal=RequestJournal(tmp_path / "journal.jsonl"))
    requests = _requests(model, 5, seed=3)
    for req in requests[:3]:
        req.key = None  # a seed: the journal derives the key itself
        assert sched.submit(req)[0]
    while len(sched._active) < 2:
        sched.step()
    assert engine.step_in_flight

    del host_reads[:]
    assert sched.submit(requests[3])[0] and sched.submit(requests[4])[0]
    assert host_reads == []
    sched._pump_admissions()  # a chunk of requests[2]: dispatches only
    assert sched._pending is not None and host_reads == []
    while sched._pending is not None or sched._queue:
        del host_reads[:]
        sched.step()  # admission work and a decode step: one wait
        assert host_reads == ["device_get"]
    for _ in range(3):  # steady, decode only
        del host_reads[:]
        events, _ = sched.step()
        assert events and host_reads == ["device_get"]

    del host_reads[:]
    slot = min(sched._active)
    row = engine.collect(slot)
    assert row.shape == (sched._active[slot].req.length,)
    assert sched.cancel(sched._active[slot].req.id)  # release of a live slot
    assert host_reads == []
    # ... and the mirror it was collected from is the pool's own row
    n = engine._cur[slot] + 1
    assert (np.asarray(engine.slots.seqs[slot])[:n] == row[:n]).all()
    sched.run_to_completion(max_steps=500)
    sched.journal.close()


def test_the_journals_key_is_prngkeys(progen):
    for seed in (0, 7, 2**31 - 1):
        assert (seed_key(seed) == np.asarray(jax.random.PRNGKey(seed))).all()
        assert isinstance(seed_key(seed), np.ndarray)


# ----- (d) journaled before returned ---------------------------------------------


def test_every_event_is_in_the_journal_before_step_returns_it(progen, tmp_path):
    model, params = progen
    engine = ServeEngine(model, params, max_slots=2, max_len=32)
    path = tmp_path / "journal.jsonl"
    sched = Scheduler(engine, max_queue=8, prefill_chunk=3,
                      journal=RequestJournal(path))
    for req in _requests(model, 5, seed=5):
        assert sched.submit(req)[0]
    seen, in_flight = 0, 0
    while sched.has_work:
        events, completions = sched.step()
        in_flight += engine.step_in_flight
        lines = [json.loads(x) for x in path.read_text().splitlines()]
        tokens = {(r["req"], r["index"]): r["token"]
                  for r in lines if r["op"] == "token"}
        done = {r["req"] for r in lines if r["op"] == "done"}
        for e in events:
            assert tokens[(e.request_id, e.index)] == e.token
        assert {c.request_id for c in completions} <= done
        seen += len(events)
    sched.journal.close()
    assert seen == len(tokens) > 0 and in_flight > 5


# ----- (e) the counter; idle --------------------------------------------------------


def test_steps_ahead_are_counted_and_the_engine_goes_idle(progen):
    model, params = progen
    engine = ServeEngine(model, params, max_slots=2, max_len=32)
    sched = Scheduler(engine, max_queue=8, prefill_chunk=4)
    assert "decode_steps_ahead" not in sched.metrics.snapshot()
    for req in _requests(model, 4, seed=9):
        assert sched.submit(req)[0]
    launches, forgotten = [], []
    launch, drop = engine.launch_step, engine.drop_step
    engine.launch_step = lambda: (launches.append(1), launch())[1]
    engine.drop_step = lambda: (forgotten.append(engine.step_in_flight),
                                drop())[1]
    steps = 0
    while sched.has_work:
        events, _ = sched.step()
        steps += bool(events)
    m = sched.metrics.snapshot()
    # every fetched step had its successor launched; the one launched
    # behind the last step of a busy stretch is forgotten, never fetched
    assert m["decode_steps_ahead"] == m["decode_steps"] == steps
    assert len(launches) == m["decode_steps"] + sum(forgotten) > steps
    assert not engine.step_in_flight and not engine.any_live
    assert engine._served is None  # idle: the served tree is dropped
    assert "decode_steps_ahead" in sched.metrics.structured()["counters"]

    # a direct caller: launch, fetch, nothing left in flight, as before
    slot = engine.acquire()
    engine.prefill(slot, np.array([1, 2, 3]), 8, seed=4)
    for _ in range(5):
        sampled, was_live, finished = engine.decode_step()
        assert was_live[slot] and not engine.step_in_flight
    assert finished[slot]
    want = np.asarray(sample_fast(jax.random.PRNGKey(4), model, params,
                                  jnp.array([1, 2, 3]), 8))
    assert (engine.collect(slot) == want).all()
    engine.release(slot)
    with pytest.raises(RuntimeError, match="already in flight"):
        engine.launch_step(), engine.launch_step()


def test_a_reload_commits_between_launches(progen):
    """``commit_params`` with a step in flight: that step ran on the old
    tree, the next launch takes the new one, no program recompiles."""
    model, params = progen
    engine = ServeEngine(model, params, max_slots=2, max_len=32)
    sched = Scheduler(engine, max_queue=4)
    assert sched.submit(Request(id="a", prime=np.array([1, 2]), length=20,
                                seed=1))[0]
    sched.step(), sched.step()
    assert engine.step_in_flight
    before = engine.decode_compile_count()
    prepared = engine.prepare_params(jax.tree.map(lambda a: a * 0.5, params))
    engine.commit_params(prepared)
    _, completions = sched.run_to_completion(max_steps=100)
    assert [c.request_id for c in completions] == ["a"]
    assert engine.decode_compile_count() == before


# ----- sampling._prepare_seq pads on the host ------------------------------------------


def _device_pad(model, prime, length, add_bos):
    """``_prepare_seq`` as it padded before: on the device."""
    prime = jnp.asarray(prime, jnp.int32)
    start = prime.shape[-1] + (1 if add_bos else 0)
    pad = ((1, length - prime.shape[-1] - 1) if add_bos
           else (0, length - prime.shape[-1]))
    return jnp.pad(prime, ((0, 0),) * (prime.ndim - 1) + (pad,)), start


@pytest.mark.parametrize("add_bos", [False, True])
@pytest.mark.parametrize("prime", [
    [5], [3, 1, 4, 1, 5], np.arange(1, 12), jnp.arange(1, 7),
    np.arange(1, 13).reshape(3, 4), jnp.ones((2, 5), jnp.int32),
], ids=["one", "list", "numpy", "device", "batched", "batched-device"])
def test_the_host_pad_is_the_device_pad(progen, prime, add_bos):
    model, _ = progen
    for length in (14, 32):
        seq, start = _prepare_seq(model, prime, length, add_bos)
        want, want_start = _device_pad(model, prime, length, add_bos)
        assert isinstance(seq, np.ndarray) and seq.dtype == np.int32
        assert seq.shape == want.shape and start == want_start
        assert (seq == np.asarray(want)).all()


@pytest.mark.parametrize("prime,length,add_bos,message", [
    ([1, 2], 33, False,
     "length 33 exceeds the model's seq_len 32 (RoPE tables and the SGU "
     "spatial matrix are bound to seq_len)"),
    ([], 8, False, "empty prime requires add_bos=True"),
    ([1, 2, 3], 3, False, "prime length 3 must be < length 3"),
    ([1, 2, 3], 4, True, "prime length 4 must be < length 4"),
])
def test_prepare_seqs_refusals_word_for_word(progen, prime, length, add_bos,
                                             message):
    model, _ = progen
    with pytest.raises(ValueError) as e:
        _prepare_seq(model, prime, length, add_bos)
    assert str(e.value) == message
