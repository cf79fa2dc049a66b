"""Fused Pallas TPU kernels for the gMLP layer's non-attention hot path.

The layer today launches pre-norm, token-shift, the SGU norm, the causal
spatial mix, and the multiplicative gate as SEPARATE XLA ops
(ops/shift.py + ops/sgu.py composed in models/layers.py), each paying
its own HBM round-trip over the full (batch, n, dim) activation. Two
kernels close that gap:

  * ``fused_norm_shift`` — the ``ScaleNorm -> shift_tokens`` head shared
    by the attention and FF blocks, in ONE pass: each program normalizes
    its (block, d) row-tile in f32 (flax LayerNorm replica: scale-only,
    f32 stats, biased variance via E[x^2]-E[x]^2 clamped at 0),
    normalizes the single halo row it needs from the previous block (a
    second BlockSpec over the same array fetching the sublane-aligned
    group of rows that ends at it — Mosaic refuses a one-row block; no
    HBM duplication), shifts the whole tile down one row, and keeps the
    shifted values only in the first ``d - d//2`` lanes (the split
    ``shift_tokens`` applies). Program 0's halo row is zeroed
    in-register, reproducing the reference's zero pad.

  * ``fused_sgu_mix_gate`` — the SpatialGatingUnit tail
    (``ScaleNorm(gate) -> causal mix -> x * gate``) with the gate's
    output tile resident in VMEM across all three. Grid (batch, rows i,
    cols j) with j the reduction ("arbitrary") dimension: the structural
    zeros the recursive ``_block_triangular_mix`` skips by calling
    ``_dense_mix`` on ever-smaller sub-triangles are skipped INSIDE the
    kernel instead — ``@pl.when(j <= i)`` makes the strictly-upper
    blocks true no-ops, and only the diagonal block pays a tril mask.
    The gate block is normalized in-register right before it feeds the
    MXU (round-tripped through the compute dtype so bf16 parity with the
    unfused norm-then-mix holds bit-for-bit), accumulation is an f32
    VMEM scratch, and the final j applies bias + ``x * gate`` before the
    (1, block, d) output tile is written once.

Both are ``jax.custom_vjp``: the backward differentiates the XLA
reference composition (``norm_shift_reference`` /
``sgu_mix_gate_reference``) on the saved primal inputs — the same
escape-hatch structure as pallas_attention's ``bwd_impl="xla"``, and
the right default here because both ops are bandwidth-bound enough that
the fused forward is where the win lives.

Impl selection mirrors the attention policy: ``layer_entries`` in the
same pallas_policy.json, keyed (kind, n, d), read via
``measured_layer_impl`` (bench.py's ``kernel-fused-w*`` phases write rows
of the same shape under runs/).

VMEM: see ``safe_layer_block`` — the SGU kernel's working set is what
bounds the row tile (256 rows at tiny's gate width 1024, 128 at large's
3584 under the 16 MiB scoped limit of a v5e).
"""

from __future__ import annotations

import functools
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from progen_tpu.ops.pallas_attention import _POLICY_PATH, _sds
from progen_tpu.ops.sgu import causal_sgu_mix
from progen_tpu.ops.shift import shift_tokens


# --------------------------------------------------------------------------
# XLA reference compositions — the exact unfused math (flax LayerNorm with
# use_bias=False + ops/shift.py + ops/sgu.py), used as the policy's "xla"
# forward, the custom-VJP backward, and the parity golden in tests.


def norm_reference(x, scale, epsilon, out_dtype):
    """Scale-only LayerNorm over the last axis, replicating flax
    ``nn.LayerNorm(use_bias=False)``: f32 stats, biased variance as
    ``max(0, E[x^2] - E[x]^2)``, the rsqrt*scale product formed first."""
    f32 = jnp.float32
    x32 = x.astype(f32)
    mu = x32.mean(axis=-1, keepdims=True)
    mu2 = (x32 * x32).mean(axis=-1, keepdims=True)
    var = jnp.maximum(0.0, mu2 - mu * mu)
    y = (x32 - mu) * (jax.lax.rsqrt(var + epsilon) * scale.astype(f32))
    return y.astype(out_dtype)


def norm_shift_reference(x, scale, epsilon, out_dtype):
    """Unfused golden for ``fused_norm_shift``."""
    return shift_tokens(norm_reference(x, scale, epsilon, out_dtype))


def sgu_mix_gate_reference(x, gate, weights, biases, scale, epsilon,
                           out_dtype):
    """Unfused golden for ``fused_sgu_mix_gate``: normalize the gate,
    dense causal mix (block_size=0 — the blocked recursion is the same
    math reassociated), multiply into ``x``."""
    g = norm_reference(gate, scale, epsilon, out_dtype)
    g = causal_sgu_mix(g, weights, biases)
    return x * g.astype(x.dtype)


# --------------------------------------------------------------------------
# Kernels.


def _norm_rows(x32, scale32, epsilon):
    """The flax-replica normalization on an f32 (rows, d) tile."""
    mu = x32.mean(axis=-1, keepdims=True)
    mu2 = (x32 * x32).mean(axis=-1, keepdims=True)
    var = jnp.maximum(0.0, mu2 - mu * mu)
    return (x32 - mu) * (jax.lax.rsqrt(var + epsilon) * scale32)


def _halo_rows(dtype) -> int:
    """Rows in fused_norm_shift's halo block: one native sublane tile of
    the input dtype ((8, 128) for 4-byte, (16, 128) for 2-byte elements).
    Mosaic refuses a block whose last two dims are not tile-aligned, so
    the "previous row" is fetched as the aligned group of rows ending at
    it, not as a one-row block."""
    return 8 * max(1, 4 // jnp.dtype(dtype).itemsize)


def _norm_shift_kernel(x_ref, prev_ref, s_ref, o_ref, *, epsilon, split):
    f32 = jnp.float32
    scale = s_ref[...].astype(f32)  # (1, d), broadcasts over rows
    y = _norm_rows(x_ref[0].astype(f32), scale, epsilon)  # (bn, d)
    # the halo: the previous block's LAST row, normalized here rather
    # than re-read from the neighbor's output (programs are independent).
    # It arrives as the last row of an aligned (hb, d) group; a masked
    # sublane sum picks it out without an unaligned slice. Program 0
    # reads its own first group through the clamped index map and masks
    # it to the reference's zero pad.
    halo = _norm_rows(prev_ref[0].astype(f32), scale, epsilon)  # (hb, d)
    hrow = jax.lax.broadcasted_iota(jnp.int32, halo.shape, 0)
    keep = (hrow == halo.shape[0] - 1) & (pl.program_id(1) > 0)
    prev = jnp.sum(jnp.where(keep, halo, 0.0), axis=0, keepdims=True)
    # shift the tile down one row: sublane rotate, then row 0 <- halo
    row = jax.lax.broadcasted_iota(jnp.int32, y.shape, 0)
    shifted = jnp.where(row == 0, prev, pltpu.roll(y, 1, 0))
    col = jax.lax.broadcasted_iota(jnp.int32, y.shape, 1)
    out = jnp.where(col < split, shifted, y)
    o_ref[0] = out.astype(o_ref.dtype)


def _sgu_kernel(x_ref, g_ref, w_ref, b_ref, s_ref, o_ref, acc_ref, *,
                epsilon):
    f32 = jnp.float32
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j <= i)
    def _accumulate():
        # normalize the gate tile right before it feeds the MXU; the
        # round-trip through the output dtype replicates the unfused
        # path's bf16 rounding between the norm and the mix
        g = _norm_rows(g_ref[0].astype(f32), s_ref[...].astype(f32),
                       epsilon)
        g = g.astype(o_ref.dtype).astype(f32)
        w = w_ref[...].astype(f32)  # (bn out-rows, bn in-cols)
        # strictly-lower blocks (j < i) are fully causal; only the
        # diagonal block pays the tril mask. j > i never runs — that is
        # _block_triangular_mix's structural-zero skip, in-kernel.
        row = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        w = jnp.where((j < i) | (col <= row), w, 0.0)
        acc_ref[...] += jax.lax.dot_general(
            w, g,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=f32,
        )

    @pl.when(j == pl.num_programs(2) - 1)
    def _finalize():
        gate = (acc_ref[...] + b_ref[...].astype(f32)).astype(o_ref.dtype)
        o_ref[0] = x_ref[0] * gate


# --------------------------------------------------------------------------
# pallas_call wrappers + custom VJPs. ``out_dtype`` rides as a STRING so
# the nondiff args stay hashable under jit.


def _norm_shift_pallas(x, scale, epsilon, block, interpret, out_dtype):
    b, n, d = x.shape
    bn = block
    hb = _halo_rows(x.dtype)
    if bn % hb or n % bn:
        raise ValueError(
            f"fused_norm_shift: row tile {bn} must divide n={n} and be a "
            f"multiple of the {hb}-row sublane tile of {x.dtype}"
        )
    scale2 = scale.reshape(1, d)
    grid = (b, n // bn)
    return pl.pallas_call(
        functools.partial(
            _norm_shift_kernel, epsilon=epsilon, split=d - d // 2
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bn, d), lambda bi, i: (bi, i, 0),
                         memory_space=pltpu.VMEM),
            # halo spec over the SAME array: the aligned hb-row group
            # ending at element row i*bn - 1 (the previous block's last
            # row), clamped at group 0
            pl.BlockSpec(
                (1, hb, d),
                lambda bi, i: (bi, jnp.maximum(i * (bn // hb) - 1, 0), 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec((1, d), lambda bi, i: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, bn, d), lambda bi, i: (bi, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_sds((b, n, d), jnp.dtype(out_dtype), x),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")
        ),
        interpret=interpret,
    )(x, x, scale2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def fused_norm_shift(x, scale, epsilon, block, interpret, out_dtype):
    """Fused ScaleNorm + token-shift. ``x``: (batch, n, d); ``scale``:
    (d,) norm scale param; ``block`` row-tile must divide n. Returns
    (batch, n, d) in ``out_dtype`` (pass a dtype NAME — nondiff args
    must hash). Backward differentiates ``norm_shift_reference``."""
    out, _ = _norm_shift_fwd(x, scale, epsilon, block, interpret,
                             out_dtype)
    return out


def _norm_shift_fwd(x, scale, epsilon, block, interpret, out_dtype):
    return (
        _norm_shift_pallas(x, scale, epsilon, block, interpret, out_dtype),
        (x, scale),
    )


def _norm_shift_bwd(epsilon, block, interpret, out_dtype, res, g):
    x, scale = res

    def ref(x_, s_):
        return norm_shift_reference(x_, s_, epsilon, out_dtype)

    _, vjp = jax.vjp(ref, x, scale)
    return vjp(g)


fused_norm_shift.defvjp(_norm_shift_fwd, _norm_shift_bwd)


def _sgu_pallas(x, gate, weights, biases, scale, epsilon, block, interpret,
                out_dtype):
    b, n, d = gate.shape
    bn = block
    nb = n // bn
    scale2 = scale.reshape(1, d)
    return pl.pallas_call(
        functools.partial(_sgu_kernel, epsilon=epsilon),
        grid=(b, nb, nb),
        in_specs=[
            pl.BlockSpec((1, bn, d), lambda bi, i, j: (bi, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, bn, d), lambda bi, i, j: (bi, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, bn), lambda bi, i, j: (i, j),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((bn, 1), lambda bi, i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d), lambda bi, i, j: (0, 0),
                         memory_space=pltpu.VMEM),
        ],
        # j-independent output tile: stays VMEM-resident across the whole
        # j reduction, flushed to HBM once when (bi, i) advances
        out_specs=pl.BlockSpec((1, bn, d), lambda bi, i, j: (bi, i, 0),
                               memory_space=pltpu.VMEM),
        out_shape=_sds((b, n, d), jnp.dtype(out_dtype), gate),
        scratch_shapes=[pltpu.VMEM((bn, d), jnp.float32)],
        cost_estimate=pl.CostEstimate(
            flops=b * n * n * d,  # causal half of 2*b*n*n*d
            transcendentals=0,
            bytes_accessed=4 * b * n * d * 2 + 4 * n * n,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(x, gate, weights, biases, scale2)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def fused_sgu_mix_gate(x, gate, weights, biases, scale, epsilon, block,
                       interpret, out_dtype):
    """Fused SGU tail: ScaleNorm(gate) -> causal spatial mix -> x * gate.
    ``x``/``gate``: (batch, n, d) halves of the FF hidden; ``weights``:
    (n, n); ``biases``: (n, 1); ``scale``: (d,). ``block`` must divide
    n. Backward differentiates ``sgu_mix_gate_reference``."""
    out, _ = _sgu_fwd(x, gate, weights, biases, scale, epsilon, block,
                      interpret, out_dtype)
    return out


def _sgu_fwd(x, gate, weights, biases, scale, epsilon, block, interpret,
             out_dtype):
    out = _sgu_pallas(x, gate, weights, biases, scale, epsilon, block,
                      interpret, out_dtype)
    return out, (x, gate, weights, biases, scale)


def _sgu_bwd(epsilon, block, interpret, out_dtype, res, g):
    x, gate, weights, biases, scale = res

    def ref(x_, g_, w_, b_, s_):
        return sgu_mix_gate_reference(x_, g_, w_, b_, s_, epsilon,
                                      out_dtype)

    _, vjp = jax.vjp(ref, x, gate, weights, biases, scale)
    return vjp(g)


fused_sgu_mix_gate.defvjp(_sgu_fwd, _sgu_bwd)


# --------------------------------------------------------------------------
# Measured layer policy: ``layer_entries`` in the same pallas_policy.json
# the attention table lives in (record_policy_entry there only rewrites
# "entries", so the two tables coexist). Keyed (kind, n, d); read at layer
# trace time.

_LAYER_ENTRY_KEYS = ("kind", "n", "d", "impl", "block")

_LAYER_KINDS = ("norm_shift", "sgu_mix")

# Unmeasured defaults: the fused kernels exist to cut HBM round-trips, so
# until a kernel-fused-w* phase's on-chip numbers are promoted into the
# table the opt-in flag gets the kernel at the attention bench's tile size.
_LAYER_FALLBACK_ENTRIES = (
    {"kind": "norm_shift", "n": 1024, "d": 512, "impl": "pallas",
     "block": 256},
    {"kind": "sgu_mix", "n": 1024, "d": 1024, "impl": "pallas",
     "block": 256},
)


def _layer_entries(path: Path | None = None) -> list[dict]:
    path = path or _POLICY_PATH

    def _valid(e: dict) -> bool:
        try:
            return (
                all(k in e for k in _LAYER_ENTRY_KEYS)
                and e["kind"] in _LAYER_KINDS
                and all(
                    isinstance(e[k], (int, float)) and e[k] > 0
                    for k in ("n", "d")
                )
                and isinstance(e["block"], int) and e["block"] >= 1
                and e["impl"] in ("pallas", "xla")
            )
        except TypeError:
            return False

    try:
        doc = json.loads(path.read_text())
        entries = [e for e in doc.get("layer_entries", []) if _valid(e)]
        if entries:
            return entries
    except (OSError, ValueError):
        pass
    return list(_LAYER_FALLBACK_ENTRIES)


def layer_policy_decision(kind: str, n: int, d: int,
                          path: Path | None = None) -> dict:
    """Measured-winner entry for ``kind`` nearest to (n, d) in log-space
    (n dominates: the mix is O(n^2) while d only widens the tiles),
    annotated like the attention table's policy_decision."""
    if kind not in _LAYER_KINDS:
        raise ValueError(f"unknown layer kernel kind {kind!r}")
    entries = [e for e in _layer_entries(path) if e["kind"] == kind]
    if not entries:
        entries = [e for e in _LAYER_FALLBACK_ENTRIES if e["kind"] == kind]

    def dist(e: dict) -> float:
        return (
            2.0 * abs(math.log2(n / e["n"]))
            + abs(math.log2(d / e["d"]))
        )

    best = min(entries, key=dist)
    exact = best["n"] == n and best["d"] == d
    return {
        **best,
        "exact_shape_match": exact,
        "requested": {"kind": kind, "n": n, "d": d},
    }


def measured_layer_impl(kind: str, n: int, d: int) -> tuple[str, int]:
    """(impl, block) from the layer policy table for the given shape."""
    e = layer_policy_decision(kind, n, d)
    return e["impl"], e["block"]


def record_layer_policy_entry(entry: dict, path: Path) -> None:
    """Merge one measured layer-kernel winner into ``layer_entries`` of
    the table at ``path`` (under ``runs/`` for bench phases), preserving
    every other top-level key (notably the attention table's "entries")
    — the mirror of record_policy_entry's contract."""
    missing = [k for k in _LAYER_ENTRY_KEYS if k not in entry]
    if missing:
        raise ValueError(f"layer policy entry missing keys {missing}")
    try:
        doc = json.loads(path.read_text())
        assert isinstance(doc, dict)
    except (OSError, ValueError, AssertionError):
        doc = {"schema": "pallas-policy-v1", "entries": []}
    doc.setdefault("layer_entries", [])
    key = lambda e: (e["kind"], e["n"], e["d"])
    kept = [
        e for e in doc["layer_entries"]
        if all(k in e for k in ("kind", "n", "d")) and key(e) != key(entry)
    ]
    doc["layer_entries"] = sorted(kept + [entry], key=key)
    tmp = path.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(doc, indent=1))
    tmp.replace(path)


# --------------------------------------------------------------------------
# Dispatch entry points for models/layers.py.


# The fused-SGU kernel's scoped-VMEM budget. Mosaic's default scoped limit
# on a v5e is 16 MiB; the estimate below reproduces its own accounting to
# within a few percent (bf16, bn=256, d=3584: estimated 18.9 MB, Mosaic
# reported 17.82 MB and refused it; bn=128 compiled — chip run, PR 21), so
# the budget keeps a margin under the limit.
_VMEM_BUDGET = 14 << 20


def safe_layer_block(kind: str, block: int, n: int, d: int,
                     dtype=jnp.float32) -> int | None:
    """Largest row-tile <= block that Mosaic accepts for the ``kind``
    kernel on (batch, n, d) activations of ``dtype``, or None (the
    dispatchers below raise on that). A tile must divide n, be whole
    sublane tiles of the dtype (``_halo_rows``: 8 rows for f32, 16 for
    bf16) and fit ``_VMEM_BUDGET``. The SGU's row tile is also the lane
    dim of its (bn, bn) weight tile, so there it must be a multiple of
    128 unless it spans the whole sequence. Working sets: SGU — the x and
    gate input tiles and the output tile (each double-buffered by the
    pipeline), the f32 accumulator, one f32 tile of temporaries and the
    double-buffered weight tile; norm-shift — the double-buffered input
    and output tiles plus three f32 tiles of temporaries."""
    if kind not in _LAYER_KINDS:
        raise ValueError(f"unknown layer kernel kind {kind!r}")
    item = jnp.dtype(dtype).itemsize
    rows = _halo_rows(dtype)
    if kind == "sgu_mix":
        step = 128
        cost = lambda bn: bn * d * (6 * item + 8) + 8 * bn * bn
    else:
        step = rows
        cost = lambda bn: bn * d * (4 * item + 12)
    block = max(1, int(block))
    candidates = [n] if n <= block else []
    candidates += range(block - block % step, 0, -step)
    for bn in candidates:
        if n % bn == 0 and bn % rows == 0 and cost(bn) <= _VMEM_BUDGET:
            return bn
    return None


def _resolve(kind: str, n: int, d: int, dtype, block_override: int):
    impl, blk = measured_layer_impl(kind, n, d)
    if block_override:
        impl, blk = "pallas", int(block_override)
    tile = safe_layer_block(kind, blk, n, d, dtype)
    if impl == "pallas" and tile is None:
        # the config asked for the kernel: no silent XLA stand-in
        raise ValueError(
            f"fused {kind} kernel: no legal row tile <= {blk} for "
            f"n={n}, d={d}, {jnp.dtype(dtype).name} (see safe_layer_block)"
        )
    return impl, tile


def norm_shift(x, scale, epsilon, out_dtype, *, block_override: int = 0,
               interpret: bool = False):
    """Policy-dispatched fused norm+shift on (batch, n, d): the kernel, or
    the XLA reference (plain autodiff, no VJP indirection) where the
    policy table's measured winner at this shape is "xla".
    ``block_override`` (config.pallas_layer_block) forces the kernel at
    that tile."""
    dt = jnp.dtype(out_dtype).name
    impl, blk = _resolve("norm_shift", x.shape[-2], x.shape[-1], x.dtype,
                         block_override)
    if impl != "pallas":
        return norm_shift_reference(x, scale, epsilon, dt)
    return fused_norm_shift(x, scale, epsilon, blk, interpret, dt)


def sgu_mix_gate(x, gate, weights, biases, scale, epsilon, out_dtype, *,
                 block_override: int = 0, interpret: bool = False):
    """Policy-dispatched fused SGU tail; same contract as ``norm_shift``."""
    dt = jnp.dtype(out_dtype).name
    impl, blk = _resolve("sgu_mix", gate.shape[-2], gate.shape[-1],
                         gate.dtype, block_override)
    if impl != "pallas":
        return sgu_mix_gate_reference(x, gate, weights, biases, scale,
                                      epsilon, dt)
    return fused_sgu_mix_gate(x, gate, weights, biases, scale, epsilon,
                              blk, interpret, dt)
