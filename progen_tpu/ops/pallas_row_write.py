"""One row a slot, written into a pooled cache leaf in place, in one call.

A decode step writes one new row per slot into every cache leaf of the
slot pool: each slot at a start of its own along the leaf's row axis. As
one ``dynamic_update_slice`` a slot and leaf that is 74 x 32 = 2,368
updates a step in ProGen-large, each a launch of about 1 us however few
bytes it writes (PERF.md §5: 2.3 ms of a 10.7 ms step).

The kernel here writes every slot's row of one leaf in one invocation,
with no grid: the leaf stays where the pool holds it (HBM, aliased to
the output), and for each slot the tile rows that hold its row are
copied into VMEM, the row is set there, and the tile rows are copied
back. Mosaic copies whole tiles of a leaf's two minor axes only (a DMA
of one row of a tiled axis is refused), so a slot moves one tile's rows
of every leading index, 16 rows of bf16 or 8 of float32. All slots'
reads are started first; each slot's row is set as its read lands and
its write is started; the call ends when every write has landed.

``fits`` says where the kernel applies: the row axis is the leaf's
second-minor, the minor axis fills whole lanes, the row axis whole
tiles, and every slot's tile rows fit the VMEM budget.
``layers._update_at``'s batching rule asks it; a leaf it refuses keeps
another form of the write (``layers._row_write_path``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM the slots' tile rows may take (the new rows come on top: one
# tile's rows a slot at most): half the default scoped VMEM of a v5e
_VMEM_BUDGET = 8 * 1024 * 1024
_LANES = 128


def tile_rows(dtype) -> Optional[int]:
    """Rows of one HBM tile of the row axis for ``dtype``: 8 of a 32-bit
    type, packed pairs of 16-bit and quads of 8-bit ones."""
    itemsize = jnp.dtype(dtype).itemsize
    return 8 * 4 // itemsize if itemsize in (1, 2, 4) else None


def fits(shape, dtype, axis: int) -> bool:
    """Whether the kernel writes rows of a pooled leaf ``shape`` (slots
    leading) along ``axis`` of the pooled leaf."""
    rows = tile_rows(dtype)
    if rows is None or len(shape) < 3 or axis != len(shape) - 2:
        return False
    if shape[-1] % _LANES or shape[-2] % rows:
        return False
    tiles = math.prod(shape[:-2]) * rows * shape[-1]
    return tiles * jnp.dtype(dtype).itemsize <= _VMEM_BUDGET


def _kernel(*refs, rows: int, size: int, masked: bool):
    if masked:
        start_ref, live_ref, new_ref, buf_ref, out_ref, tiles, sems = refs
    else:
        start_ref, new_ref, buf_ref, out_ref, tiles, sems = refs
        live_ref = None
    del buf_ref  # aliased: ``out_ref`` is the same buffer
    n_slots = new_ref.shape[0]

    def row_of(s):
        # a negative start wraps once and any start is clamped so that
        # the row lies in the leaf, as ``dynamic_update_slice`` clamps
        i = start_ref[s]
        i = jnp.where(i < 0, i + size, i)
        return jnp.clip(i, 0, size - 1)

    def tile_of(s):
        lo = pl.multiple_of(row_of(s) // rows * rows, rows)
        return out_ref.at[s, ..., pl.ds(lo, rows), :]

    def read(s):
        return pltpu.make_async_copy(tile_of(s), tiles.at[s], sems.at[0, s])

    def write(s):
        return pltpu.make_async_copy(tiles.at[s], tile_of(s), sems.at[1, s])

    def each(step):
        # a loop, not the slots unrolled in Python: an unrolled body took
        # seconds to trace and lower a kernel shape at every start-up
        def body(s, carry):
            step(s)
            return carry

        jax.lax.fori_loop(0, n_slots, body, 0)

    def merge(s):
        read(s).wait()
        x = tiles[s]
        at = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 2)
        hit = at == row_of(s) % rows
        if masked:
            hit = hit & (live_ref[s] != 0)
        tiles[s] = jnp.where(hit, jnp.broadcast_to(new_ref[s], x.shape), x)
        write(s).start()

    each(lambda s: read(s).start())
    each(merge)
    each(lambda s: write(s).wait())


@functools.partial(jax.jit, static_argnames=("interpret",))
def write_rows(buf, new, start, live=None, *, interpret: bool = False):
    """Write ``new[s]`` (one row along the second-minor axis) into
    ``buf[s]`` at row ``start[s]`` for every slot s, in place; where
    ``live`` (slots,) is given, a slot whose flag is False keeps its row.
    ``buf`` (slots, ..., rows, lanes) as the pool holds it, ``new``
    (slots, ..., 1, lanes) in ``buf``'s type, ``start`` (slots,).
    Jitted for the trace's sake, not to be a program of its own: a step
    calls it once a leaf at a few shapes, and the step's trace then holds
    one body a shape, which XLA inlines (a kernel traced anew at each
    call cost ProGen-large's set-up seconds, PERF.md §6)."""
    if not fits(buf.shape, buf.dtype, buf.ndim - 2):
        raise ValueError(
            f"row write: no tiling fits a leaf {buf.shape} of {buf.dtype}"
        )
    rows = tile_rows(buf.dtype)
    n_slots = buf.shape[0]
    scalars = [start.astype(jnp.int32)]
    if live is not None:
        scalars.append(live.astype(jnp.int32))
    return pl.pallas_call(
        functools.partial(_kernel, rows=rows, size=buf.shape[-2],
                          masked=live is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                pltpu.VMEM(buf.shape[:-2] + (rows, buf.shape[-1]),
                           buf.dtype),
                pltpu.SemaphoreType.DMA((2, n_slots)),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(buf.shape, buf.dtype),
        input_output_aliases={len(scalars) + 1: 0},
        name="cache_row_write",
        interpret=interpret,
    )(*scalars, new, buf)
