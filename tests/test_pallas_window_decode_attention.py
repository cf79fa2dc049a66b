"""The window_moe decode step's attention kernel, in interpret mode.

``ops/pallas_window_decode_attention.py``: one query a slot, in groups
over each key/value head, against a sliding layer's ring or a full layer's
rows, reading per slot only the blocks that hold a position its query
sees. Held here to the plain form (``window_moe._attend_rows`` at T = 1,
what a decode step runs off the TPU) wherever the rule has an edge: slots
at mixed depths in one call, a ring before its first wrap and across it,
a window start inside a block, the window's own edge, a full layer's last
block, a dead slot; and the block table and its host twin to a
brute-force count of the blocks that hold a visible row.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.models.window_moe import _attend_rows, held_position, visible
from progen_tpu.ops import pallas_window_decode_attention as K

# a ring of a window of 256 and a block of 128 rows, a full layer of 512
WINDOW, RING, FULL = 256, 384, 512


def _leaf(positions, rows, a, dtype, seed=0, groups=2, d=128):
    rng = np.random.default_rng(seed)
    n = len(positions)
    q = jnp.asarray(rng.standard_normal((n, groups, a, d)), dtype)
    k = jnp.asarray(rng.standard_normal((n, groups, rows, d)), dtype)
    v = jnp.asarray(rng.standard_normal((n, groups, rows, d)), dtype)
    return q, k, v, jnp.asarray(positions, jnp.int32)


def _plain(q, k, v, t, window):
    return _attend_rows(q[:, None], k, v, t[:, None], window, k.shape[2])[:, 0]


def _kernel(q, k, v, t, window, block):
    return K.window_decode_attention(q, k, v, t, window=window, block=block,
                                     interpret=True)


def _close(got, want, dtype):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if dtype == jnp.float32:
        np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    else:
        # one rounding of the output to bfloat16 (8 bits) on either side
        np.testing.assert_allclose(got, want, atol=2 ** -8, rtol=2 ** -7)


# where the rule has an edge, by name: (rows, window, block, positions)
CASES = {
    "mixed_depths": (RING, WINDOW, 128, [0, 7, 255, 256, 383, 384, 700, 1033]),
    "ring_before_first_wrap": (RING, WINDOW, 128, [1, 127, 128, 200, 383]),
    "across_the_wrap": (RING, WINDOW, 128, [384, 385, 511, 512, 767, 768]),
    "window_start_inside_a_block": (RING, WINDOW, 128, [300, 317, 450, 901]),
    "window_edge": (RING, WINDOW, 128, [WINDOW - 1, WINDOW]),
    "full_last_block": (FULL, None, 128, [384, 450, 511]),
    "full_mixed_depths": (FULL, None, 256, [0, 1, 255, 256, 300, 511]),
}


@pytest.mark.parametrize("a", [1, 6])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_kernel_is_the_plain_form(case, dtype, a):
    rows, window, block, positions = CASES[case]
    args = _leaf(positions, rows, a, dtype, seed=len(case))
    _close(_kernel(*args, window, block), _plain(*args, window), dtype)


@pytest.mark.parametrize("window", [WINDOW, None], ids=["ring", "full"])
def test_a_dead_slot_is_finite_and_disturbs_nobody(window):
    """A slot released long ago keeps a stale position and whatever its
    leaves held (huge values here): its output is finite, and the live
    slots beside it read exactly what they read alone."""
    rows = RING if window else FULL
    q, k, v, t = _leaf([40, 300, 3 * rows + 17], rows, 6, jnp.float32, seed=9)
    k, v = k.at[2].multiply(1e4), v.at[2].multiply(1e4)
    got = _kernel(q, k, v, t, window, 128)
    assert np.isfinite(np.asarray(got)).all()
    alone = _kernel(q[:2], k[:2], v[:2], t[:2], window, 128)
    np.testing.assert_array_equal(np.asarray(got[:2]), np.asarray(alone))
    _close(got[:2], _plain(q, k, v, t, window)[:2], jnp.float32)


def _brute_blocks(t, rows, window, block):
    """The blocks that hold a row a query at ``t`` sees, by the plain
    form's own rule over every row."""
    seen = np.asarray(visible(held_position(t, jnp.arange(rows), rows), t,
                              window))
    return set(np.flatnonzero(seen.reshape(-1, block).any(axis=1)))


LEAVES = {
    "ring": (RING, WINDOW, 128),
    "ring_256": (512, WINDOW, 256),
    "trinity_ring": (4608, 4096, 512),
    "full": (FULL, None, 128),
    "trinity_full": (11264, None, 512),
}


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_the_table_lists_the_blocks_that_hold_a_visible_row(leaf):
    rows, window, block = LEAVES[leaf]
    span = window or rows
    edges = [0, 1, block - 1, block, span - 1, span, span + 1, rows - 1,
             rows, rows + block // 2, 2 * rows + 3, 7 * rows - 1]
    t = np.asarray(edges + list(range(0, 3 * rows, 37)), np.int32)
    tbl, cnt = K.block_table(jnp.asarray(t), rows, window, block)
    tbl, cnt = np.asarray(tbl), np.asarray(cnt)
    host = K.listed_rows(t, rows, window, block)
    for i, p in enumerate(t):
        want = _brute_blocks(int(p), rows, window, block)
        listed = list(tbl[i, :cnt[i]])
        assert set(listed) == want and len(listed) == len(want), p
        # past the count, the last listed block again: no new copy
        assert (tbl[i, cnt[i]:] == listed[-1]).all()
        assert host[i] == len(want) * block
    # a query that sees nothing lists one block, which its mask empties
    tbl, cnt = K.block_table(jnp.asarray([-1]), rows, window, block)
    assert int(cnt[0]) == 1 and K.listed_rows([-1], rows, window, block)[0] == block


@pytest.mark.parametrize(
    "rows,window,kv_heads,d,dtype,want",
    [
        (4608, 4096, 8, 128, jnp.bfloat16, 512),  # trinity's rings
        (11264, None, 8, 128, jnp.bfloat16, 512),  # its full layer's rows
        (384, 256, 2, 128, jnp.float32, 128),  # the tests' ring
        (640, 512, 2, 128, jnp.float32, 128),  # 512 and 256 do not divide
        (40, 32, 2, 16, jnp.float32, None),  # the small preset: 16 lanes
        (4608, 4096, 8, 64, jnp.bfloat16, None),  # half a lane tile
        (4680, 4096, 8, 128, jnp.bfloat16, None),  # no block divides it
        (4608, 4160, 8, 128, jnp.bfloat16, None),  # nor the window
    ],
)
def test_the_block_rule(rows, window, kv_heads, d, dtype, want):
    assert K.block_rows(rows, window, kv_heads, d, dtype) == want


@pytest.mark.parametrize("on_tpu", [False, True])
def test_the_kernel_is_chosen_by_what_the_code_sees(monkeypatch, on_tpu):
    monkeypatch.setattr(K, "on_tpu", lambda: on_tpu)
    got = K.kernel_block(4608, 4096, 8, 128, jnp.bfloat16)
    assert got == (512 if on_tpu else None)
    assert K.kernel_block(40, 32, 2, 16, jnp.float32) is None
