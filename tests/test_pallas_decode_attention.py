"""The decode step's attention kernel, in interpret mode.

``ops/pallas_decode_attention.py``: single-query attention over the slot
pool that reads, per slot, only the ring blocks its query sees. Held here
to the plain form (``ring_attention``, what a decode step ran before and
still runs off the TPU) at every place the rule has an edge: the first
window's phantom keys, a window boundary, the ring's last row, a ring
that has wrapped, a dead slot, slots at mixed depths in one call; and the
block table to a brute-force count of visible rows.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.ops import pallas_decode_attention as D

W = 256  # window of the tests' rings: ring 512, blocks of 128 or 256 rows


def _ring_positions(pos, ring, resumed_at=0):
    """``slot_pos`` of a ring that was fed positions ``resumed_at`` ..
    ``pos`` (-1 where nothing was written)."""
    stored = np.full((ring,), -1, np.int32)
    if pos >= 0:
        xs = np.arange(max(resumed_at, pos - ring + 1), pos + 1)
        stored[xs % ring] = xs
    return stored


def _pool(positions, heads, dh, dtype, window=W, seed=0, resumed_at=0):
    """Pool-shaped operands: a query, a K and a V ring and the stored
    positions per slot; a position of -1 is a slot nothing was fed to."""
    n, ring = len(positions), 2 * window
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((n, 1, heads, 1, dh)), dtype)
    k = jnp.asarray(rng.standard_normal((n, 1, heads, ring, dh)), dtype)
    v = jnp.asarray(rng.standard_normal((n, 1, heads, ring, dh)), dtype)
    stored = np.stack(
        [_ring_positions(p, ring, resumed_at) for p in positions]
    )
    pos = np.maximum(np.asarray(positions, np.int32), 0)
    return q, k, v, jnp.asarray(stored), jnp.asarray(pos)


def _plain(q, k, v, stored, pos, window=W):
    return jax.vmap(
        lambda q, k, v, s, p: D.ring_attention(q, k, v, s, p, window)
    )(q, k, v, stored, pos[:, None])


def _kernel(q, k, v, stored, pos, block, window=W):
    n, _, heads, _, dh = q.shape
    out = D.pooled_decode_attention(
        q.reshape(n, heads, dh), k, v, stored, pos, window=window,
        block=block, interpret=True,
    )
    return out.reshape(q.shape)


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    else:
        # one rounding of the output to bfloat16 (8 bits) on either side
        np.testing.assert_allclose(got, want, atol=2 ** -8, rtol=2 ** -7)


# where the rule has an edge, by name: the query's position
EDGES = {
    "first_token": 0,
    "first_window": 100,  # phantom keys, one block
    "window_minus_1": W - 1,
    "window": W,  # first row of the second window
    "window_plus_1": W + 1,
    "ring_last_row": 2 * W - 1,
    "wrapped_first_row": 2 * W,  # seq_len > ring: row 0 is overwritten
    "wrapped": 5 * W + 17,
    "wrapped_window_end": 7 * W - 1,
}


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("edge", sorted(EDGES))
def test_one_slot_at_an_edge_is_the_plain_form(edge, dtype, block):
    args = _pool([EDGES[edge]], heads=3, dh=128, dtype=dtype, seed=3)
    _close(_kernel(*args, block), _plain(*args), dtype)


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("dh", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_slots_at_mixed_depths_in_one_call(dtype, dh, block):
    positions = [5, 700, W, 31, 2 * W - 1, 3 * W + 9, W - 1, 1234]
    args = _pool(positions, heads=5, dh=dh, dtype=dtype, seed=5)
    _close(_kernel(*args, block), _plain(*args), dtype)


@pytest.mark.parametrize("block", [128, 256])
def test_a_dead_slot_is_finite_and_disturbs_nobody(block):
    """A slot nothing was ever written to lists block 0, all masked; a
    slot past its first window with no visible row reads the mean of
    that block as the plain form reads the mean of its ring — nobody
    reads either, and neither may be a NaN that a later write keeps."""
    live = [40, 2 * W + 3]
    args = _pool(live + [-1], heads=3, dh=128, dtype=jnp.float32, seed=9)
    q, k, v, stored, pos = args
    pos = pos.at[2].set(3 * W)  # past window 0: no phantom keys to lean on
    got = _kernel(q, k, v, stored, pos, block)
    assert np.isfinite(np.asarray(got)).all()
    alone = (q[:2], k[:2], v[:2], stored[:2], pos[:2])
    np.testing.assert_array_equal(
        np.asarray(got[:2]), np.asarray(_kernel(*alone, block))
    )
    _close(got[:2], _plain(*alone), jnp.float32)


@pytest.mark.parametrize("block", [128, 256])
def test_a_slot_that_resumed_mid_ring(block):
    """A ring fed from position 300 on (what lies before was never
    written: ``slot_pos`` -1 there) — the table reads the stored
    positions, not a closed form in the query's position."""
    args = _pool([310, 300 + W, 700], heads=3, dh=128, dtype=jnp.float32,
                 seed=11, resumed_at=300)
    _close(_kernel(*args, block), _plain(*args), jnp.float32)
    tbl, cnt, _ = D.block_table(args[3], args[4], W, block)
    # positions 300 .. 310 lie in one block of either size
    assert int(cnt[0]) == 1 and int(tbl[0, 0]) == 300 // block


@pytest.mark.parametrize("block", [128, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_rows_nobody_sees_are_not_read_into_the_result(dtype, block):
    """NaN in every K and V row the query does not see — rows past
    ``pos`` and stale rows of a wrapped ring, inside listed blocks and
    outside them — leaves the output finite and equal to the output over
    clean rings."""
    positions = [3, 130, W + 2, 2 * W - 1, 2 * W + 5, 4 * W + 200]
    q, k, v, stored, pos = _pool(positions, heads=3, dh=128, dtype=dtype,
                                 seed=13)
    floor = np.maximum((np.asarray(pos) // W - 1) * W, 0)[:, None]
    seen = (np.asarray(stored) >= floor) & (
        np.asarray(stored) <= np.asarray(pos)[:, None]
    )
    assert 0 < seen.sum() < seen.size
    hole = jnp.asarray(~seen)[:, None, None, :, None]
    k_bad = jnp.where(hole, jnp.nan, k)
    v_bad = jnp.where(hole, jnp.nan, v)
    clean = np.asarray(_kernel(q, k, v, stored, pos, block), np.float32)
    got = np.asarray(_kernel(q, k_bad, v_bad, stored, pos, block),
                     np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, clean)


@pytest.mark.parametrize(
    "window,block", [(128, 64), (128, 128), (256, 64), (256, 128), (256, 256)]
)
def test_the_block_table_is_the_brute_force_count(window, block):
    ring = 2 * window
    rng = np.random.default_rng(17)
    positions = [-1, 0, block - 1, block, window - 1, window, window + 1,
                 ring - 1, ring, ring + 1] + [
        int(p) for p in rng.integers(0, 9 * window, 30)
    ]
    stored = np.stack([_ring_positions(p, ring) for p in positions])
    # two rings that were resumed mid-way, and one with a hole
    stored[3] = _ring_positions(positions[3], ring, resumed_at=block // 2)
    stored[-1, ::3] = -1
    pos = np.maximum(np.asarray(positions, np.int32), 0)
    tbl, cnt, part = (np.asarray(x) for x in D.block_table(
        jnp.asarray(stored), jnp.asarray(pos), window, block
    ))
    for s, p in enumerate(pos):
        # the plain form's rule, row by row
        seen = (
            (stored[s] >= 0) & (stored[s] <= p)
            & (p // window - stored[s] // window <= 1)
        ).reshape(ring // block, block)
        listed = [b for b in range(ring // block) if seen[b].any()] or [0]
        assert cnt[s] == len(listed)
        assert list(tbl[s, : cnt[s]]) == listed
        assert (tbl[s, cnt[s]:] == listed[-1]).all()  # no new copy
        assert list(part[s, : cnt[s]]) == [
            int(not seen[b].all()) for b in listed
        ]
        if positions[s] >= 0 and s != 3 and s != len(positions) - 1:
            assert D.listed_rows(p, window, ring, block) == cnt[s] * block


def test_block_rows_fit_the_tiling_or_refuse():
    # ProGen-large and long8k as served; the tests' models fit nothing
    assert D.block_rows(512, 14, 128, jnp.bfloat16) == 256
    assert D.block_rows(512, 8, 64, jnp.bfloat16) == 256
    assert D.block_rows(128, 4, 128, jnp.float32) == 128
    assert D.block_rows(8, 2, 16, jnp.float32) is None  # dh 16
    assert D.block_rows(512, 8, 48, jnp.bfloat16) is None
    assert D.block_rows(192, 8, 128, jnp.bfloat16) is None  # 192 % 128
    with pytest.raises(ValueError, match="no block of rows fits"):
        args = _pool([5], heads=2, dh=16, dtype=jnp.float32, window=8)
        D.pooled_decode_attention(
            args[0].reshape(1, 2, 16), *args[1:], window=8, interpret=True
        )


@pytest.mark.parametrize("on_tpu", [False, True], ids=["plain", "kernel"])
def test_the_batching_rule_picks_by_what_it_sees(monkeypatch, on_tpu):
    """Under a ``vmap`` over slots the rule is the kernel where the
    backend test says TPU and the shapes fit, the vmapped plain form
    otherwise; unbatched, and at shapes the tiling refuses, the plain
    form whatever the backend."""
    monkeypatch.setattr(D, "on_tpu", lambda: on_tpu)
    calls = []
    real = D.pooled_decode_attention
    monkeypatch.setattr(
        D, "pooled_decode_attention",
        lambda *a, **kw: calls.append(kw["block"]) or real(*a, **kw),
    )
    args = _pool([5, 300, 2 * W + 1], heads=2, dh=128, dtype=jnp.float32)
    q, k, v, stored, pos = args
    attend = D.decode_attention.__wrapped__(W)  # not the cached closure
    got = jax.vmap(attend)(q, k, v, stored, pos[:, None])
    assert calls == ([128 if W % 256 else 256] if on_tpu else [])
    _close(got, _plain(*args), jnp.float32)
    # unbatched: one slot's call is the plain form
    one = attend(q[1], k[1], v[1], stored[1], pos[1:2])
    _close(one, _plain(*args)[1], jnp.float32)
    # a shape the tiling refuses (dh 16) and a ring shared by the slots
    small = _pool([3, 9], heads=2, dh=16, dtype=jnp.float32, window=8)
    jax.vmap(D.decode_attention.__wrapped__(8))(
        small[0], small[1], small[2], small[3], small[4][:, None]
    )
    jax.vmap(attend, in_axes=(0, None, None, None, 0))(
        q, k[0], v[0], stored[0], jnp.minimum(pos, 5)[:, None]
    )
    assert len(calls) == int(on_tpu)
