"""Single-query attention over the slot pool's K/V rings, live blocks only.

A decode step of ProGen attends one query per slot against that slot's
rolling 2-window ring (``models/layers.py::LocalAttentionBlock``). The
plain form, ``ring_attention``, scores the whole ring and masks by the
stored positions afterwards: under the slots' ``vmap`` XLA reads all of a
ring's rows for every slot, though a query at position q sees q + 1 of
them in its first window and at most two windows ever (PERF.md, PR 34:
266 of 1,024 rows on average in ``large.gen-closed``).

The kernel here reads, per slot, only the ring BLOCKS in which that
slot's query sees a row. Grid (slots, ring blocks); a prefetched int32
table (``block_table``, computed from the cache's own ``slot_pos`` and the
query's position) lists each slot's visible blocks and their count; a
step past the count keeps the last block's index, so no new copy is
started, and skips its body. Inside a block visibility is the plain
form's rule on the stored positions; the softmax statistics and the
``e·V`` accumulator are float32, combined across blocks online, and the
first window's phantom keys enter at the end exactly as in the plain
form. K and V are read where the pool holds them, (slots, 1, heads, ring,
dh): no copy, no re-layout.

``decode_attention(window)`` is what the model calls at T = 1: its plain
form is ``ring_attention`` and its batching rule — the slots' ``vmap`` —
is the kernel where the backend is a TPU and the shapes fit the kernel's
tiling (``block_rows``), the vmapped plain form everywhere else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from progen_tpu.ops.attention import ATTN_MASK_VALUE

# what a masked score is set to, here as in the full-sequence paths:
# finite, so that a block (or a dead slot's whole ring) with no visible
# row still has a mean
MASK_VALUE = ATTN_MASK_VALUE


def ring_attention(q, k, v, slot_pos, pos, window: int):
    """The plain form: T queries (b, h, T, dh) at absolute positions
    ``pos`` (T,) against a ring (b, h, ring, dh) whose row r holds
    position ``slot_pos[r]`` (-1: nothing). A row is visible to a query
    at p if it holds a position in p's window or the one before, not
    after p. Window-0 queries' softmax is diluted by exactly ``window``
    phantom zero-score/zero-value keys via an analytic denominator
    correction — the reference's zero-padded previous window
    (progen.py:90-96) without materializing it."""
    dh = q.shape[-1]
    w = window
    visible = (
        (slot_pos >= 0)
        & (slot_pos <= pos[:, None])
        & (pos[:, None] // w - slot_pos // w <= 1)
    )  # (T, ring)
    scores = jnp.einsum(
        "bhqd,bhkd->bhqk", q, k,
        preferred_element_type=jnp.float32,
    ) * (dh ** -0.5)
    scores = jnp.where(visible[None, None], scores, MASK_VALUE)

    first_window = (pos < w).astype(jnp.float32)[:, None]  # (T, 1)
    # softmax with analytic phantom-key dilution: shift-invariant, so a
    # stable max including the phantoms' score 0 is fine
    m = jnp.maximum(
        scores.max(axis=-1, keepdims=True),
        jnp.where(first_window > 0, 0.0, -jnp.inf),
    )
    e = jnp.exp(scores - m)
    denom = e.sum(axis=-1, keepdims=True) + first_window * w * jnp.exp(-m)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", e, v.astype(jnp.float32)
    ) / denom
    return out.astype(q.dtype)


def _window_floor(pos, window: int):
    """First position a query at ``pos`` sees: ``pos // w - p // w <= 1``
    is ``p >= (pos // w - 1) * w`` for whole numbers, and a stored
    position is never negative."""
    return (pos // window - 1).clip(0) * window


def block_table(slot_pos, pos, window: int, block: int):
    """Per slot, the ring blocks of ``block`` rows in which its query sees
    a row. ``slot_pos`` (slots, ring), ``pos`` (slots,). Returns ``tbl``
    (slots, ring // block) int32 — the visible blocks in ring order, then
    the last of them repeated —, ``cnt`` (slots,) their count (a slot that
    sees nothing lists block 0, which the mask empties) and ``part``
    (slots, ring // block), 1 where the listed block also holds a row the
    query does not see."""
    n_slots, ring = slot_pos.shape
    nblk = ring // block
    vis = (slot_pos >= _window_floor(pos, window)[:, None]) & (
        slot_pos <= pos[:, None]
    )
    per = vis.reshape(n_slots, nblk, block)
    has = per.any(axis=-1)
    cnt = jnp.maximum(has.sum(axis=-1), 1).astype(jnp.int32)
    rank = jnp.cumsum(has, axis=-1) - 1
    blocks = jnp.arange(nblk, dtype=jnp.int32)
    want = jnp.minimum(blocks[None, :], cnt[:, None] - 1)  # (slots, j)
    pick = has[:, None, :] & (rank[:, None, :] == want[:, :, None])
    tbl = jnp.sum(pick * blocks, axis=-1, dtype=jnp.int32)
    part = jnp.sum(pick & ~per.all(axis=-1)[:, None, :], axis=-1,
                   dtype=jnp.int32)
    # a slot that sees nothing picks nothing: its block 0 is all masked
    part = jnp.where(has.any(axis=-1)[:, None], part, 1)
    return tbl, cnt, part


def listed_rows(pos, window: int, ring: int, block: int):
    """Rows the kernel reads for queries at ``pos`` (numpy, on the host):
    blocks listed x ``block``, in closed form for a ring that holds every
    position up to ``pos`` — the visible positions are the run from the
    previous window's start to ``pos``, shorter than the ring, and a
    block never straddles a window."""
    pos = np.asarray(pos)
    return (pos // block - _window_floor(pos, window) // block + 1) * block


# Rows of a ring block: whole sublane tiles of either type at the least;
# at the most what the chip read fastest (a v5e, ProGen-large's pool,
# PERF.md, PR 34: a layer's call 0.144 / 0.148 / 0.20 ms at 128 / 256 /
# 512 rows — a grid step costs about 0.35 us whether it copies or skips,
# and a larger block reads more rows nobody sees), within a budget for
# the K and V blocks, double-buffered, of half the scoped VMEM.
_MIN_BLOCK = 128
_MAX_BLOCK = 256
_VMEM_BUDGET = 8 * 1024 * 1024


def block_rows(window: int, heads: int, dh: int, dtype) -> Optional[int]:
    """Rows of a ring block for these shapes, or None where the kernel's
    tiling does not fit them: the head size must fill half a tile's lanes
    or whole tiles, a block whole sublane tiles of either type, and blocks
    must not straddle a window (so that a window's rows are whole
    blocks)."""
    if dh % 64 or window % _MIN_BLOCK:
        return None
    # bytes of one row of a K and a V block, each held twice (a head's
    # rows lie on whole lanes)
    row_bytes = 4 * heads * max(dh, 128) * jnp.dtype(dtype).itemsize
    block = _MIN_BLOCK
    while (
        block * 2 <= _MAX_BLOCK
        and window % (block * 2) == 0
        and block * 2 * row_bytes <= _VMEM_BUDGET
    ):
        block *= 2
    return block


def _kernel(tbl_ref, cnt_ref, part_ref, pos_ref, lo_ref,
            q_ref, k_ref, v_ref, sp_ref, o_ref, m_sc, l_sc, acc_sc,
            *, heads: int, window: int, scale: float, precision):
    s, j = pl.program_id(0), pl.program_id(1)
    nblk = pl.num_programs(1)
    f32 = jnp.float32
    hp = q_ref.shape[0]

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, -jnp.inf, f32)
        l_sc[...] = jnp.zeros(l_sc.shape, f32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

    @pl.when(j < cnt_ref[s])
    def _():
        stored = sp_ref[...]  # (1, block) int32
        visible = (stored >= lo_ref[s]) & (stored <= pos_ref[s])
        q = q_ref[...]  # (hp, dh): row h is head h's query
        block = stored.shape[-1]
        head_of_row = jax.lax.broadcasted_iota(jnp.int32, (hp, block), 0)
        # every head's K block against every head's query: the product's
        # cost is loading the block into the MXU, whatever the rows
        # beside it, and row h of head h's product is what is kept
        scores = jnp.zeros((hp, block), f32)
        for h in range(heads):
            s_h = jax.lax.dot_general(
                q, k_ref[h], (((1,), (1,)), ((), ())),
                preferred_element_type=f32, precision=precision,
            )
            scores = jnp.where(head_of_row == h, s_h, scores)
        scores = jnp.where(visible, scores * scale, MASK_VALUE)

        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        e = jnp.exp(scores - m_new)  # (hp, block) f32
        l_sc[...] = alpha * l_sc[...] + e.sum(axis=-1, keepdims=True)
        m_sc[...] = m_new

        if v_ref.dtype == f32:
            lhs = e
        else:
            # e keeps float32's worth of bits through a product in V's
            # type: its rounding and what the rounding lost, as two rows
            hi = e.astype(v_ref.dtype)
            lo = (e - hi.astype(f32)).astype(v_ref.dtype)
            lhs = jnp.concatenate([hi, lo], axis=0)
        head_of_out = jax.lax.broadcasted_iota(
            jnp.int32, (hp, acc_sc.shape[-1]), 0
        )

        def weighted(clean):
            out = jnp.zeros(acc_sc.shape, f32)
            for h in range(heads):
                o_h = jax.lax.dot_general(
                    lhs, clean(v_ref[h]), (((1,), (0,)), ((), ())),
                    preferred_element_type=f32, precision=precision,
                )
                if o_h.shape[0] != hp:
                    o_h = o_h[:hp] + o_h[hp:]
                out = jnp.where(head_of_out == h, o_h, out)
            return out

        def seen_rows(v_h):
            # an unseen row's weight is 0, and 0 x NaN is NaN: where the
            # block holds such rows they are emptied, by a mask laid
            # along the sublanes, where V's rows lie
            col = jnp.transpose(
                jnp.broadcast_to(visible.astype(f32), (128, block))
            )[:, : v_h.shape[-1]]
            return jnp.where(col > 0, v_h, jnp.zeros_like(v_h))

        acc_sc[...] = alpha * acc_sc[...] + jax.lax.cond(
            part_ref[s * nblk + j] == 0,
            lambda: weighted(lambda v_h: v_h),
            lambda: weighted(seen_rows),
        )

    @pl.when(j == nblk - 1)
    def _():
        m, l_sum = m_sc[...], l_sc[...]
        first = pos_ref[s] < window
        m_end = jnp.where(first, jnp.maximum(m, 0.0), m)
        alpha = jnp.exp(m - m_end)
        denom = alpha * l_sum + jnp.where(
            first, window * jnp.exp(-m_end), 0.0
        )
        o_ref[...] = (acc_sc[...] * alpha / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block", "interpret"))
def pooled_decode_attention(q, k, v, slot_pos, pos, *, window: int,
                            block: Optional[int] = None,
                            interpret: bool = False):
    """The kernel over the pool: ``q`` (slots, heads, dh), ``k`` / ``v``
    (slots, 1, heads, ring, dh) as the pool holds them, ``slot_pos``
    (slots, ring), ``pos`` (slots,). Returns (slots, heads, dh) in
    ``q``'s type. Jitted for the trace's sake, not to be a program of
    its own: a decode step calls it once a layer with the same shapes,
    and traced and lowered anew each time the kernel's body (two
    products a head, written out) cost ProGen-large's 24 layers 6-7 s
    of every start-up (PERF.md, PR 34); the step's trace now holds one
    body and 24 calls of it, which XLA inlines."""
    n_slots, heads, dh = q.shape
    ring = slot_pos.shape[-1]
    if block is None:
        block = block_rows(window, heads, dh, k.dtype)
    if block is None or ring % block or window % block:
        raise ValueError(
            f"decode attention: no block of rows fits window {window}, "
            f"ring {ring}, heads {heads} x {dh} of {k.dtype} "
            f"(block {block})"
        )
    nblk = ring // block
    pos = pos.astype(jnp.int32)
    tbl, cnt, part = block_table(slot_pos, pos, window, block)
    hp = -(-heads // 16) * 16  # whole sublane tiles of either type
    qp = jnp.pad(q, ((0, 0), (0, hp - heads), (0, 0)))
    f32 = jnp.float32

    kv_spec = pl.BlockSpec(
        (None, None, heads, block, dh),
        lambda s, j, tbl, *_: (s, 0, 0, tbl[s * nblk + j], 0),
    )
    out = pl.pallas_call(
        functools.partial(
            _kernel, heads=heads, window=window, scale=dh ** -0.5,
            precision=(
                jax.lax.Precision.HIGHEST if k.dtype == f32 else None
            ),
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_slots, nblk),
            in_specs=[
                pl.BlockSpec((None, hp, dh), lambda s, j, *_: (s, 0, 0)),
                kv_spec,
                kv_spec,
                pl.BlockSpec(
                    (None, 1, block),
                    lambda s, j, tbl, *_: (s, 0, tbl[s * nblk + j]),
                ),
            ],
            out_specs=pl.BlockSpec(
                (None, hp, dh), lambda s, j, *_: (s, 0, 0)
            ),
            scratch_shapes=[
                pltpu.VMEM((hp, 1), f32),
                pltpu.VMEM((hp, 1), f32),
                pltpu.VMEM((hp, dh), f32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, hp, dh), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        name="decode_ring_attention",
        interpret=interpret,
    )(
        tbl.reshape(-1), cnt, part.reshape(-1), pos,
        _window_floor(pos, window).astype(jnp.int32),
        qp, k, v, slot_pos.reshape(n_slots, 1, ring),
    )
    return out[:, :heads]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_block(window: int, heads: int, dh: int, dtype) -> Optional[int]:
    """Rows of the blocks the kernel reads a pool's rings in, or None
    where the slots' ``vmap`` of a decode step runs the plain form and
    reads whole rings: off the TPU, or at shapes ``block_rows`` refuses."""
    return block_rows(window, heads, dh, dtype) if on_tpu() else None


@functools.lru_cache(maxsize=None)
def decode_attention(window: int):
    """``ring_attention`` at T = 1 with a batching rule of its own: the
    serving pool vmaps the one-token apply over its slots, each with a
    ring and a position of its own, and there the rule is the kernel over
    the slot axis — where the backend is a TPU, every operand carries the
    slot axis and ``block_rows`` fits the shapes. Anywhere else it is the
    plain form, vmapped: what a ``vmap`` of the plain form would trace."""

    def plain(q, k, v, slot_pos, pos):
        return ring_attention(q, k, v, slot_pos, pos, window)

    attend = jax.custom_batching.custom_vmap(plain)

    @attend.def_vmap
    def over_slots(axis_size, in_batched, q, k, v, slot_pos, pos):
        b, heads, t, dh = q.shape[-4:]
        block = kernel_block(window, heads, dh, k.dtype)
        if (
            block is not None
            and all(in_batched)
            and (b, t) == (1, 1)
            and k.dtype == v.dtype
        ):
            out = pooled_decode_attention(
                q.reshape(axis_size, heads, dh), k, v, slot_pos,
                pos.reshape(axis_size), window=window, block=block,
                interpret=jax.default_backend() != "tpu",
            )
            return out.reshape(q.shape), True
        in_axes = [0 if batched else None for batched in in_batched]
        return jax.vmap(plain, in_axes=in_axes)(q, k, v, slot_pos, pos), True

    return attend
