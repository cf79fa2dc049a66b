"""One-token grouped attention over the window_moe pool's rings and rows,
live blocks only.

A decode step of ``models/window_moe.py`` attends one query a slot, in
groups of ``A`` query heads per key/value head, against that slot's cache
leaves: a sliding layer's RING of ``rows`` rows (a row holds the latest
position congruent to it, ``held_position``) and a full layer's rows,
which grow with the sequence. The plain form, ``window_moe._attend_rows``,
scores every row of every leaf for every slot, though a query at ``t``
sees ``min(t + 1, window)`` rows of a ring and ``t + 1`` of the full
layer's: XLA has no bound on a read per slot.

The kernel here reads, per slot, only the BLOCKS of a leaf that hold a
position its query sees. Grid (slots, blocks); a prefetched int32 table
(``block_table``, computed on the device from the positions alone) lists
each slot's visible blocks, from the one that holds its first visible
position onwards, wrapping at the ring's end, and their count; a step past
the count keeps the last listed index, so that no new copy is started,
and skips its body. Inside a block visibility is the plain form's rule on
the block's row indices; the softmax statistics are float32, combined
across blocks online, and the value product takes the plain form's
operand types (probabilities in the query's type, float32 accumulation).
K and V are read where the pool holds them, (slots, kv heads, rows, d):
no copy, no re-layout.

``kernel_block`` says where the model runs it: on a TPU, at one query a
slot, where ``block_rows`` fits the leaf's shapes. ``listed_rows`` is the
table's twin in numpy, the host's account of what a step read.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# what a masked score is set to and what a running maximum starts at: the
# plain form's, so that a block (or a dead slot's leaf) with no visible row
# adds nothing and a slot that sees nothing reads zeros
_FLOOR = -1e30


def _visible_run(t, rows: int, window: Optional[int], block: int, xp):
    """(first listed block, count of blocks) of queries at ``t`` over a leaf
    of ``rows`` rows: its visible positions are the run of ``n = min(t + 1,
    span)`` ending at ``t`` (``span`` the window, or the whole leaf), which
    the leaf holds in consecutive rows from ``(t - n + 1) mod rows``,
    wrapping at its end. A query that sees nothing lists one block, which
    its mask empties. ``xp`` is ``numpy`` or ``jax.numpy``."""
    nblk = rows // block
    span = rows if window is None else min(window, rows)
    n = xp.clip(t + 1, 0, span)
    lo = t - n + 1
    first = (lo % rows) // block
    count = xp.clip((lo % block + n - 1) // block + 1, 1, nblk)
    return first, count


def block_table(t, rows: int, window: Optional[int], block: int):
    """Per slot, the blocks of ``block`` rows of a leaf of ``rows`` rows that
    hold a position its query at ``t`` (slots,) sees. Returns ``tbl``
    (slots, rows // block) int32 — the visible blocks in the order the
    positions run, then the last of them repeated — and ``cnt`` (slots,)
    their count."""
    nblk = rows // block
    t = t.astype(jnp.int32)
    first, cnt = _visible_run(t, rows, window, block, jnp)
    steps = jnp.arange(nblk, dtype=jnp.int32)
    tbl = (first[:, None] + jnp.minimum(steps[None, :], cnt[:, None] - 1)) % nblk
    return tbl.astype(jnp.int32), cnt.astype(jnp.int32)


def listed_rows(t, rows: int, window: Optional[int], block: int):
    """Rows the kernel reads for queries at ``t`` (numpy, on the host): the
    table's count x ``block``, by the table's own arithmetic."""
    _, count = _visible_run(np.asarray(t, np.int64), rows, window, block, np)
    return count * block


# Rows of a block, the fastest first and then down to whole 128-row
# tiles: the first that divides the leaf, and the window where there is
# one, within a budget for the K and V blocks, double-buffered, of half the
# scoped VMEM. Read on a v5e at trinity-large-preview's pool (32 slots, 8 x
# 128 bfloat16, PERF.md §6): a 4,608-row ring took 0.75 ms at 512 rows
# and 1.12 at 256 (a grid step costs about 1.3 us beyond its copy), a
# 11,264-row leaf 1.04 and 1.57; with the groups in one batched product,
# 0.71 and 0.98 at 512 rows, 683 and 665 GB/s on the rows listed.
_BLOCKS = (512, 256, 128)
_VMEM_BUDGET = 8 * 1024 * 1024


def block_rows(rows: int, window: Optional[int], kv_heads: int, d: int,
               dtype) -> Optional[int]:
    """Rows of a block for a leaf of ``rows`` rows of ``kv_heads`` x ``d``,
    or None where the kernel's tiling does not fit it: ``d`` must be whole
    lanes, and a block must divide the leaf and the window."""
    if d % 128:
        return None
    row_bytes = 4 * kv_heads * d * jnp.dtype(dtype).itemsize
    for block in _BLOCKS:
        if (rows % block == 0
                and (window is None or window % block == 0)
                and block * row_bytes <= _VMEM_BUDGET):
            return block
    return None


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def kernel_block(rows: int, window: Optional[int], kv_heads: int, d: int,
                 dtype) -> Optional[int]:
    """Rows of the blocks a one-token step reads a leaf in, or None where it
    runs the plain form over the whole leaf: off the TPU, or at shapes
    ``block_rows`` refuses."""
    return block_rows(rows, window, kv_heads, d, dtype) if on_tpu() else None


def _kernel(tbl_ref, cnt_ref, t_ref, tmod_ref, lo_ref, q_ref, k_ref, v_ref,
            o_ref, m_sc, l_sc, acc_sc, *, rows: int, scale: float):
    s, j = pl.program_id(0), pl.program_id(1)
    nblk = pl.num_programs(1)
    f32 = jnp.float32
    block = k_ref.shape[1]

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _FLOOR, f32)
        l_sc[...] = jnp.zeros(l_sc.shape, f32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, f32)

    @pl.when(j < cnt_ref[s])
    def _():
        # the position row r holds as the query sees it: t - ((t - r) mod
        # rows), with r and t mod rows both in [0, rows); visible from lo
        r = tbl_ref[s * nblk + j] * block + jax.lax.broadcasted_iota(
            jnp.int32, (1, 1, block), 2)
        back = tmod_ref[s] - r
        held = t_ref[s] - back - jnp.where(back < 0, rows, 0)
        ok = held >= lo_ref[s]  # (1, 1, block)
        # every group's query rows against its K block, then its weights
        # against its V block, as two products batched over the groups
        scores = jnp.einsum("gad,gsd->gas", q_ref[...], k_ref[...],
                            preferred_element_type=f32) * scale
        m_prev = m_sc[...]
        m_new = jnp.maximum(
            m_prev, jnp.where(ok, scores, _FLOOR).max(-1, keepdims=True))
        p = jnp.where(ok, jnp.exp(scores - m_new), 0.0)
        rescale = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * rescale + p.sum(-1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = acc_sc[...] * rescale + jnp.einsum(
            "gas,gsd->gad", p.astype(q_ref.dtype), v_ref[...],
            preferred_element_type=f32)

    @pl.when(j == nblk - 1)
    def _():
        o_ref[...] = (acc_sc[...] / jnp.maximum(l_sc[...], 1e-30)).astype(
            o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "block", "interpret"))
def window_decode_attention(q, k, v, t, *, window: Optional[int], block: int,
                            interpret: bool = False):
    """The kernel over the pool: ``q`` (slots, kv heads, A, d), ``k`` / ``v``
    (slots, kv heads, rows, d) as the pool holds them, ``t`` (slots,) the
    queries' positions, ``window`` the layer's (None: a full layer).
    Returns (slots, kv heads, A, d) in ``q``'s type."""
    n_slots, groups, a, d = q.shape
    rows = k.shape[2]
    if rows % block or (window is not None and window % block) or d % 128:
        raise ValueError(
            f"window decode attention: blocks of {block} rows do not tile "
            f"{rows} rows, window {window}, head size {d}")
    nblk = rows // block
    t = t.astype(jnp.int32)
    tbl, cnt = block_table(t, rows, window, block)
    span = rows if window is None else min(window, rows)
    lo = jnp.maximum(t - span + 1, 0)
    # a group's query rows padded to whole sublane tiles of either type
    ap = -(-a // 16) * 16
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, ap - a), (0, 0)))
    f32 = jnp.float32
    kv_spec = pl.BlockSpec(
        (None, groups, block, d),
        lambda s, j, tbl, *_: (s, 0, tbl[s * nblk + j], 0),
    )
    per_slot = pl.BlockSpec((None, groups, ap, d), lambda s, j, *_: (s, 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, rows=rows, scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n_slots, nblk),
            in_specs=[per_slot, kv_spec, kv_spec],
            out_specs=per_slot,
            scratch_shapes=[
                pltpu.VMEM((groups, ap, 1), f32),
                pltpu.VMEM((groups, ap, 1), f32),
                pltpu.VMEM((groups, ap, d), f32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((n_slots, groups, ap, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="window_decode_attention",
        interpret=interpret,
    )(tbl.reshape(-1), cnt, t, t % rows, lo, qp, k, v)
    return out[:, :, :a]
