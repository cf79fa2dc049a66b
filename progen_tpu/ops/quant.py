"""Per-channel symmetric int8 weight quantization for the serving path.

Decode is bandwidth-bound: every step re-reads the full weight set to
emit one token per slot, so halving (vs bf16) or quartering (vs f32) the
bytes the matmuls pull from HBM is a direct tokens/s lever — the
weight-only-quantization recipe of LLM.int8()/AWQ-style serving stacks,
minus activation quantization (activations stay in the compute dtype, so
the MXU consumes ``int8 -> convert -> scale`` fused into the matmul; XLA
folds the dequant into the dot's operand, no materialized f32 copy).

Scheme: for each 2D matmul kernel W (in, out), one scale per OUTPUT
channel: ``scale[o] = max_i |W[i, o]| / 127``, ``Q = round(W / scale)``
clipped to [-127, 127] (symmetric — no zero point, so dequant is a
single multiply). Per-channel keeps the worst-case relative error at
~0.4% per weight regardless of cross-channel dynamic range. Embeddings,
norms, biases, and the SGU's (n, n) spatial mix stay in full precision:
they are small, and the spatial weights' ±eps/n init makes them
quantization-hostile (the whole tensor sits inside one int8 step).

The calibration report every quantizing caller must surface (the
serving engine logs it at load) records max-abs-error per quantized
leaf — honesty about the accuracy trade, in the same spirit as
bench.py's ``_suspect_fields``.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class QuantizedParams(NamedTuple):
    """``quantize_tree``'s tree and scales as one pytree: what an int8
    engine hands the serving programs in place of a float tree."""

    q: dict  # the params tree, matmul kernels int8
    scales: dict  # keystr(path) -> (out,) f32


def _is_matmul_kernel(path, leaf) -> bool:
    """Quantize exactly the 2D Dense kernels: leaves named "kernel" with
    rank 2. Leaves the embedding table, scales, biases, and the SGU
    spatial weights (named spatial_weights) alone."""
    if getattr(leaf, "ndim", 0) != 2:
        return False
    last = path[-1]
    name = getattr(last, "key", getattr(last, "name", None))
    return name == "kernel"


def quantize_leaf(w: jnp.ndarray):
    """(q_int8, scale_f32, max_abs_err_f32) for one (in, out) kernel."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=0)
    scale = jnp.where(amax > 0, amax, 1.0) / 127.0
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    err = jnp.max(jnp.abs(q.astype(jnp.float32) * scale - w32))
    return q, scale, err


def dequantize_leaf(q: jnp.ndarray, scale: jnp.ndarray, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_tree(params):
    """Quantize every matmul kernel in a params tree.

    Returns ``(q_params, scales, report)``: ``q_params`` is the tree with
    quantized leaves replaced by int8 (everything else untouched),
    ``scales`` maps ``jax.tree_util.keystr(path)`` -> (out,) f32 scales
    (a dict keyed by strings, so membership is concrete at trace time),
    and ``report`` is a list of per-leaf calibration dicts
    (path/shape/max_abs_err/bytes before+after)."""
    scales: dict = {}
    report: list = []

    def visit(path, leaf):
        if not _is_matmul_kernel(path, leaf):
            return leaf
        q, scale, err = quantize_leaf(leaf)
        key = jax.tree_util.keystr(path)
        scales[key] = scale
        report.append({
            "path": key,
            "shape": tuple(int(s) for s in leaf.shape),
            "max_abs_err": float(err),
            "bytes_fp": int(leaf.size * leaf.dtype.itemsize),
            "bytes_int8": int(q.size + scale.size * 4),
        })
        return q

    q_params = jax.tree_util.tree_map_with_path(visit, params)
    return q_params, scales, report


def dequantize_tree(q_params, scales, dtype):
    """Inverse of ``quantize_tree`` for the quantized leaves (identity on
    the rest). Trace-safe: the ``scales`` keys are host strings, so this
    inlines one convert+multiply per quantized leaf under jit and XLA
    fuses it into the consuming matmul."""

    def visit(path, leaf):
        key = jax.tree_util.keystr(path)
        if key in scales:
            return dequantize_leaf(leaf, scales[key], dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(visit, q_params)


def dequantized(params, dtype):
    """The float tree a serving program computes with: ``params`` as it
    is, or a ``QuantizedParams`` dequantized to ``dtype``. Decided while
    tracing, from the type handed in: one program serves both."""
    if isinstance(params, QuantizedParams):
        return dequantize_tree(params.q, params.scales, dtype)
    return params
