"""A decode step of ProGen through ``ServeEngine`` with the kernel.

The slots' ``vmap`` of a decode step runs ``ops/pallas_decode_attention``'s
kernel on a TPU and the plain form everywhere else. Here the batching
rule's backend test is turned (``on_tpu`` — not a switch of the program:
the kernel then runs in interpret mode) and a small ProGen is decoded
through the engine both ways: same tokens, same logits to float32
rounding, over steps that cross a window boundary and wrap the ring,
with slots admitted and released on the way; one decode program; and the
host's count of the ring rows a step read against a brute-force count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen
from progen_tpu.ops import pallas_decode_attention as D
from progen_tpu.serving import Request, Scheduler, ServeEngine

# the smallest ProGen the kernel's tiling fits: windows of 128 rows
# (a ring of 256, blocks of 128), heads of 64; seq_len 512 wraps the ring
CFG = ProGenConfig(
    num_tokens=32, dim=64, seq_len=512, depth=3, window_size=128,
    global_mlp_depth=1, heads=2, dim_head=64, ff_mult=2, dtype="float32",
)
W, RING, BLOCK = 128, 256, 128


@pytest.fixture(scope="module")
def progen():
    from flax.core import meta

    model = ProGen(CFG)
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, CFG.seq_len), jnp.int32)
    )
    return model, meta.unbox(variables)["params"]


@pytest.fixture
def rule(request, monkeypatch):
    """The batching rule's backend test, turned or not; programs traced
    under the other answer are dropped before and after."""
    jax.clear_caches()
    monkeypatch.setattr(D, "on_tpu", lambda: request.param == "kernel")
    yield request.param
    jax.clear_caches()


def _logits_fn(engine):
    """What the decode step's vmapped apply computes for the pool as it
    stands (the step itself returns tokens only); traced once a run."""

    def one(cache, tok):
        logits, _ = engine.model.apply(
            {"params": engine.params, "cache": cache}, tok,
            mutable=["cache"],
        )
        return logits[0, 0]

    apply = jax.jit(jax.vmap(one))

    def logits():
        slots = engine.slots
        pos = jnp.clip(slots.cur, 0, engine.max_len - 1)
        toks = jnp.take_along_axis(slots.seqs, pos[:, None], axis=1)
        return np.asarray(apply(slots.cache, toks[:, :, None]))

    return logits


def _scripted(model, params):
    """Three slots; primes that end just under a window boundary, just
    under the ring's end and in the first window; one request finishes
    early and another is admitted into its slot; > 40 steps. Returns the
    tokens of every step, the logits of some, the positions of the live
    slots' queries at every step, and the engine's counters."""
    engine = ServeEngine(model, params, max_slots=3, max_len=CFG.seq_len)
    rng = np.random.RandomState(3)

    def admit(plen, length, seed):
        slot = engine.acquire()
        engine.prefill(slot, rng.randint(1, 32, plen), length, seed=seed,
                       top_k=None)
        return slot

    admit(120, 200, 1)  # crosses position 128 after 8 steps
    admit(246, 300, 2)  # crosses the ring's end (256) after 10
    short = admit(5, 17, 3)  # finishes after 12, first window throughout
    tokens, logits, queries = [], {}, []
    probe = _logits_fn(engine)
    for step in range(44):
        if step in (0, 9, 13, 30):
            logits[step] = probe()
        live = engine._live.copy()
        queries.append([int(p) for p in engine._cur[live]])
        sampled, was_live, finished = engine.decode_step()
        assert (was_live == live).all()
        tokens.append([int(t) for t in sampled[was_live]])
        if finished[short]:
            engine.release(short)
            short = -1
            assert admit(300, 400, 4) == 2  # mid-ring, second lap
    assert short == -1
    return tokens, logits, queries, engine.pop_counters()


@pytest.fixture(scope="module")
def plain_run(progen):
    jax.clear_caches()
    return _scripted(*progen)


@pytest.mark.parametrize("rule", ["kernel"], indirect=True)
def test_the_kernel_draws_the_plain_rules_tokens_and_logits(
        progen, plain_run, rule, monkeypatch):
    calls = []
    real = D.pooled_decode_attention
    monkeypatch.setattr(
        D, "pooled_decode_attention",
        lambda *a, **kw: calls.append(kw) or real(*a, **kw),
    )
    tokens, logits, queries, counters = _scripted(*progen)
    # the decode step and the probe of the logits traced the kernel, once
    # per attention layer, in interpret mode
    assert calls and len(calls) % 2 == 0
    assert all(kw["interpret"] and kw["block"] == BLOCK for kw in calls)
    assert ServeEngine.decode_compile_count() == 1
    want_tokens, want_logits, want_queries, plain_counters = plain_run
    assert queries == want_queries
    assert max(max(q) for q in queries) > RING  # wrapped
    assert any(W - 1 in q for q in queries) and any(W in q for q in queries)
    assert tokens == want_tokens
    for step, want in want_logits.items():
        np.testing.assert_allclose(logits[step], want, atol=2e-5, rtol=2e-5)
    # the host's count against a brute-force count on the rule itself
    read = held = 0
    for positions in queries:
        for p in positions:
            stored = np.full((RING,), -1)
            xs = np.arange(max(0, p - RING + 1), p + 1)
            stored[xs % RING] = xs
            seen = (stored >= 0) & (stored <= p) & (
                p // W - stored // W <= 1
            )
            read += BLOCK * int(seen.reshape(-1, BLOCK).any(axis=1).sum())
            held += RING
    copies = {"prefill_cache_copies": 0}  # the engine's, of every family
    assert counters == {"ring_rows_read": read, "ring_rows_held": held,
                        **copies}
    assert 0.5 < read / held < 1.0
    # the plain rule reads whole rings, and says so
    assert plain_counters == {"ring_rows_read": held, "ring_rows_held": held,
                              **copies}


@pytest.mark.parametrize("rule", ["plain", "kernel"], indirect=True)
def test_the_scheduler_carries_the_counters(progen, rule):
    model, params = progen
    engine = ServeEngine(model, params, max_slots=2, max_len=CFG.seq_len)
    sched = Scheduler(engine, prefill_chunk=64)
    for i, (plen, length) in enumerate([(100, 110), (3, 9), (130, 136)]):
        sched.submit(Request(
            id=f"r{i}", prime=np.arange(1, plen + 1) % 31 + 1,
            length=length, seed=i,
        ))
    _, comps = sched.run_to_completion(max_steps=400)
    assert len(comps) == 3
    c = sched.metrics.counters
    assert c["ring_rows_held"] == RING * c["decode_tokens"]
    if rule == "plain":
        assert c["ring_rows_read"] == c["ring_rows_held"]
    else:
        # r0: 100..109 one block; r1: 3..8 one; r2: 130..135 two
        assert c["ring_rows_read"] == BLOCK * (10 + 6 + 2 * 6)
    assert ServeEngine.decode_compile_count() == 1
    assert "ring_rows_read" in sched.metrics.structured()["help"]


def test_a_slot_batched_family_counts_no_rings():
    from pathlib import Path

    from progen_tpu.config import load_toml_config
    from progen_tpu.models import build_model

    repo = Path(__file__).resolve().parents[1]
    small = load_toml_config(str(repo / "configs/model/latent-moe-small.toml"))
    model = build_model(small)
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    engine = ServeEngine(model, params, max_slots=2, max_len=32)
    engine.prefill(engine.acquire(), np.asarray([5, 400, 17]), 12, seed=0)
    for _ in range(3):
        engine.decode_step()
    assert not any(k.startswith("ring_") for k in engine.pop_counters())
