"""Block prefill: a prompt goes through the decode cache in aligned blocks
of positions (``sampling.feed_tokens``), one ``model.apply`` per block.

What is pinned here, beside tests/test_serving_chunked.py (which holds the
token-level contracts: chunked = monolithic, prefix-cache hit = cold):

  * the cache a block feed leaves equals the one-token decode path's,
    leaf for leaf, to float32 rounding;
  * SPLIT INDEPENDENCE, exactly: however ``[0, hi)`` is cut into calls,
    every cache leaf is bit-equal — the property every serving contract
    that re-prefills the same tokens under another split rests on;
  * the first decoded position after a block-fed prompt reads the logits
    the full forward gives;
  * one compiled program per jit name whatever the chunk sizes and resume
    depths; ``prefill_blocks`` counts the blocks the host arithmetic says.

Widths: the tiny configs' window (8) is under ``_FEED_ROWS``, so their
natural block is one whole window; the cases at width 4 put two blocks in
a window by patching the module constant around a fresh ``jax.jit``.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu import sampling
from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen
from progen_tpu.sampling import (
    _decode_setup,
    feed_block_count,
    feed_tokens,
    feed_width,
)
from progen_tpu.serving import PrefixCache, Request, Scheduler, ServeEngine

GMLP = ProGenConfig(
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
CONFIGS = {
    "gmlp": GMLP,
    "glu": dataclasses.replace(GMLP, global_mlp_depth=0),
}
TOKENS = np.random.RandomState(5).randint(1, 32, size=(1, 32)).astype(np.int32)


@pytest.fixture(scope="module")
def decoders():
    """name -> (full-forward model, decode model, params, fresh cache)."""
    from flax.core import meta

    out = {}
    for name, cfg in CONFIGS.items():
        model = ProGen(cfg)
        params = meta.unbox(
            model.init(
                jax.random.PRNGKey(0), jnp.zeros((1, cfg.seq_len), jnp.int32)
            )
        )["params"]
        out[name] = (model, *_decode_setup(model, params, batch=1))
    return out


@pytest.fixture
def feed(request, monkeypatch):
    """``feed_tokens`` under a fresh jit at the block width the test's
    ``width`` parameter asks for (8 is the tiny window's natural one)."""
    monkeypatch.setattr(sampling, "_FEED_ROWS", request.param)
    assert feed_width(GMLP) == request.param
    return jax.jit(feed_tokens, static_argnums=0)


def _one_token_cache(dec, params, cache, hi):
    """The reference: ``hi`` one-token applies, the decode step's path."""

    @jax.jit
    def one(cache, tok):
        return dec.apply(
            {"params": params, "cache": cache}, tok, mutable=["cache"]
        )[1]["cache"]

    for p in range(hi):
        cache = one(cache, jnp.asarray(TOKENS[:, p:p + 1]))
    return cache


def _leaves(cache):
    return [
        (jax.tree_util.keystr(path), np.asarray(leaf))
        for path, leaf in jax.tree_util.tree_leaves_with_path(cache)
    ]


def _feed_split(feed, dec, params, cache, cuts, hi):
    """Feed ``[0, hi)`` in calls that end at each of ``cuts`` below
    ``hi``, then at ``hi``."""
    lo = 0
    for cut in [c for c in cuts if 0 < c < hi] + [hi]:
        cache = feed(dec, params, cache, jnp.asarray(TOKENS), lo, cut)
        lo = cut
    return cache


class TestBlockFeed:
    # below, at and beyond one window (8); beyond the 2w ring's wrap (16)
    @pytest.mark.parametrize("hi", [3, 8, 13, 21, 30])
    @pytest.mark.parametrize("name", sorted(CONFIGS))
    @pytest.mark.parametrize("feed", [4, 8], indirect=True)
    def test_cache_matches_one_token_decode(self, decoders, feed, name, hi):
        _, dec, params, fresh = decoders[name]
        got = feed(dec, params, fresh, jnp.asarray(TOKENS), 0, hi)
        want = _one_token_cache(dec, params, fresh, hi)
        for (path, g), (_, w) in zip(_leaves(got), _leaves(want)):
            if path.endswith("['slot_pos']") or path.endswith("['pos']"):
                np.testing.assert_array_equal(g, w, err_msg=path)
            else:
                np.testing.assert_allclose(
                    g, w, rtol=1e-5, atol=1e-5, err_msg=path
                )

    @pytest.mark.parametrize(
        "cuts",
        [
            (1, 3),  # 1, 2, rest
            tuple(range(3, 32, 3)),  # 3, 3, ...
            (6,),  # mid-block at either width
            (8,),  # on a window boundary
            (16, 17),  # on the ring's wrap, then one position alone
            (5, 11, 12, 27),  # ragged
        ],
        ids=["1-2-rest", "threes", "mid-block", "window", "wrap", "ragged"],
    )
    @pytest.mark.parametrize("hi", [13, 30])
    @pytest.mark.parametrize("feed", [4, 8], indirect=True)
    def test_split_independence_is_exact(self, decoders, feed, hi, cuts):
        """Every leaf ``array_equal``, not close: a position's arithmetic
        does not depend on how the prompt was split."""
        _, dec, params, fresh = decoders["gmlp"]
        whole = feed(dec, params, fresh, jnp.asarray(TOKENS), 0, hi)
        split = _feed_split(feed, dec, params, fresh, cuts, hi)
        for (path, w), (_, s) in zip(_leaves(whole), _leaves(split)):
            np.testing.assert_array_equal(s, w, err_msg=path)

    @pytest.mark.parametrize("n_prompt", [1, 5, 8, 19, 31])
    @pytest.mark.parametrize("name", sorted(CONFIGS))
    def test_first_decoded_logits_match_full_forward(
        self, decoders, name, n_prompt
    ):
        """Feed ``n_prompt`` positions in blocks, decode the next token's
        position through the cache: its logits are the full forward's
        (what ``sample`` reads) at that position."""
        model, dec, params, fresh = decoders[name]
        cache = jax.jit(feed_tokens, static_argnums=0)(
            dec, params, fresh, jnp.asarray(TOKENS), 0, n_prompt
        )
        got, _ = dec.apply(
            {"params": params, "cache": cache},
            jnp.asarray(TOKENS[:, n_prompt:n_prompt + 1]), mutable=["cache"],
        )
        want = model.apply({"params": params}, jnp.asarray(TOKENS))
        np.testing.assert_allclose(
            np.asarray(got[0, 0]), np.asarray(want[0, n_prompt]),
            rtol=1e-4, atol=1e-4,
        )

    def test_no_live_row_leaves_the_cache_alone(self, decoders):
        """``hi <= lo`` runs no block, whatever ``lo`` is."""
        _, dec, params, fresh = decoders["gmlp"]
        feed = jax.jit(feed_tokens, static_argnums=0)
        cache = feed(dec, params, fresh, jnp.asarray(TOKENS), 0, 5)
        again = feed(dec, params, cache, jnp.asarray(TOKENS), 5, 5)
        for (path, a), (_, b) in zip(_leaves(again), _leaves(cache)):
            np.testing.assert_array_equal(a, b, err_msg=path)

    @pytest.mark.parametrize(
        "width,lo,hi,want",
        [(8, 0, 0, 0), (8, 0, 1, 1), (8, 0, 8, 1), (8, 0, 9, 2),
         (8, 7, 9, 2), (8, 8, 9, 1), (8, 5, 5, 0), (8, 9, 3, 0),
         (64, 16, 32, 1), (64, 48, 80, 2), (1, 3, 7, 4)],
    )
    def test_feed_block_count(self, width, lo, hi, want):
        assert feed_block_count(width, lo, hi) == want
        assert want == len({p // width for p in range(lo, hi)})

    @pytest.mark.parametrize(
        "window,want", [(512, 128), (8, 8), (320, 80), (96, 96), (7, 7), (131, 1)]
    )
    def test_feed_width_divides_the_window(self, window, want):
        cfg = dataclasses.replace(GMLP, window_size=window, seq_len=2 * window)
        assert feed_width(cfg) == want
        assert window % want == 0


def _requests(n):
    rng = np.random.RandomState(29)
    reqs = []
    for i in range(n):
        plen = int(rng.randint(2, 22))
        reqs.append(
            Request(
                id=f"r{i}", prime=rng.randint(1, GMLP.num_tokens, size=plen),
                length=min(plen + 4, 31), key=jax.random.PRNGKey(90 + i),
            )
        )
    return reqs


class TestEngineBlocks:
    @pytest.mark.parametrize("budgets", [(1, 2, None), (3,), (5, 64), (None,)])
    def test_compile_counts_flat(self, decoders, budgets):
        """Mixed chunk sizes, resume depths from a prefix cache and the
        monolithic path re-execute one program per jit name."""
        model, _, params, _ = decoders["gmlp"]
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        engine.set_prefix_cache(PrefixCache(1 << 22))

        def admit(req, budgets):
            slot = engine.acquire()
            pending = engine.begin_prefill(
                slot, req.prime, req.length, key=req.key
            )
            i = 0
            while not engine.advance_prefill(
                pending, budgets[min(i, len(budgets) - 1)]
            ):
                i += 1
            engine.decode_step()
            engine.release(slot)
            return pending

        reqs = _requests(4)
        admit(reqs[0], (4,))  # warm-up: chunk, finish and decode programs
        slot = engine.acquire()
        engine.prefill(slot, reqs[0].prime, reqs[0].length, key=reqs[0].key)
        engine.release(slot)
        before = (engine.prefill_compile_count(),
                  engine.decode_compile_count())
        hits = 0
        for req in reqs + reqs:  # the second pass resumes from snapshots
            hits += admit(req, budgets).hit_depth > 0
        slot = engine.acquire()
        engine.prefill(slot, reqs[1].prime, reqs[1].length, key=reqs[1].key)
        assert hits > 0
        assert (engine.prefill_compile_count(),
                engine.decode_compile_count()) == before

    @pytest.mark.parametrize("chunk", [0, 1, 3, 5, 64])
    def test_prefill_blocks_counter(self, decoders, chunk):
        """``prefill_blocks`` is the number of aligned blocks each fed
        range touches, summed over the chunks the scheduler really made
        (chunk 0 = the monolithic path)."""
        model, _, params, _ = decoders["gmlp"]
        engine = ServeEngine(model, params, max_slots=2, max_len=32)
        width = engine.prefill_width
        assert width == feed_width(GMLP) == 8
        fed = []
        advance = engine.advance_prefill

        def spy(pending, budget=None):
            lo = pending.pos
            done = advance(pending, budget)
            fed.append((lo, pending.pos))
            return done

        engine.advance_prefill = spy
        sched = Scheduler(engine, max_queue=16, prefill_chunk=chunk)
        reqs = _requests(6)
        for req in reqs:
            ok, reason = sched.submit(req)
            assert ok, reason
        sched.run_to_completion(max_steps=2000)
        if chunk == 0:
            fed = [(0, len(r.prime) - 1) for r in reqs]
        want = sum(len({p // width for p in range(lo, hi)}) for lo, hi in fed)
        counters = sched.metrics.counters
        tokens = sum(len(r.prime) - 1 for r in reqs)
        assert counters["prefill_tokens"] == tokens
        assert counters["prefill_blocks"] == want
        if chunk == 1:
            assert want == tokens
        assert tokens <= want * width
