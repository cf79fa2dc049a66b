"""Pallas TPU kernel for windowed causal local attention (fwd + custom VJP).

Same math as progen_tpu/ops/attention.py:local_attention (the XLA golden,
itself bit-parity with /root/reference/progen_transformer/progen.py:88-101,
including the window-0 zero-key softmax dilution). Design:

  * block = one attention window (w queries), halo = the previous window:
    grid (batch*heads, n/w); each program loads q[i] (w, d) and k/v for
    windows i-1 and i (the halo is expressed as a second BlockSpec over the
    same array with a shifted index map — no data duplication in HBM);
  * window 0's "previous window" is zeroed in-register (multiply by
    ``i > 0``), reproducing the reference's zero-padding;
  * scores/softmax accumulate in f32 whatever the input dtype (bf16-safe);
  * backward is flash-style: recompute the (w, 2w) probabilities from the
    saved q/k/v instead of storing them. TWO implementations, selectable
    via ``bwd_impl`` (both golden-tested; the kernel bench times both):

    - ``"kv"`` (default) — kv-centric: program j recomputes the softmax
      rows of windows j AND j+1 (the only two consumers of k_j/v_j) and
      emits dq_j, dk_j, dv_j directly, fully combined in-register. Extra
      score recompute, but NO f32 halo scratch in HBM and no combine
      pass — windowed attention is bandwidth-bound, so trading one (w,2w)
      matmul for 2x duplicated f32 k/v-grad HBM traffic is the
      TPU-friendly direction. ``"kv_g<N>"`` runs the same kernel with N
      batch-heads per program (the forward's bh_block lever, bench-
      selectable).
    - ``"halo"`` — q-centric: each program emits dq for its window and
      d(k2)/d(v2) for its [prev|cur] halo pair as (bh, nw, 2w, d) f32
      scratch, and the halo overlap is resolved OUTSIDE the kernel by one
      shifted add (window i's dk gets the "current" half of program i
      plus the "previous" half of program i+1). The discarded first-half
      at program 0 is exactly the gradient of the phantom zero keys.

    Additionally ``"xla"`` differentiates the XLA golden on the saved
    residuals — the measured policy's escape hatch for shapes where both
    Pallas backwards lose on-chip.

``pallas_local_attention_halo`` is the ring-composition variant: window
0's "previous window" comes from a sequence-parallel neighbor's halo
(parallel/ring_attention.py) instead of the phantom zeros; its gradient
is one tiny window-0 recompute outside the kernel (_halo_grads).

Impl selection is a measured policy table (pallas_policy.json +
measured_impls) keyed by the shapes bench.py's kernel phases actually
timed on-chip; see the policy section below.

VMEM at w=512, d=64, f32: q/k2/v2 ~0.4 MB + probs (w, 2w) 2 MB (the kv
backward holds two rows' worth); at w=256 everything halves.
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

from progen_tpu.ops.attention import ATTN_MASK_VALUE


def _window_mask(w: int) -> jnp.ndarray:
    i = jax.lax.broadcasted_iota(jnp.int32, (w, 2 * w), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (w, 2 * w), 1)
    return j <= i + w


def _prev_block(p_ref, h_ref, dtype):
    """(g, w, d) previous-window block in f32: window 0's is the halo when
    one is given (ring sequence-parallel shards), zeros otherwise (the
    reference's phantom zero keys)."""
    not_first = (pl.program_id(1) > 0).astype(dtype)
    prev = p_ref[...].astype(dtype) * not_first
    if h_ref is not None:
        prev = prev + h_ref[...].astype(dtype) * (1 - not_first)
    return prev


def _halo_kv(kp_ref, kc_ref, vp_ref, vc_ref, dtype, hk_ref=None,
             hv_ref=None):
    """Concatenate [prev | cur] k/v for ONE window program; window 0's
    prev is the halo if given, zeros otherwise."""
    k2 = jnp.concatenate(
        [_prev_block(kp_ref, hk_ref, dtype)[0], kc_ref[0]], axis=0
    )
    v2 = jnp.concatenate(
        [_prev_block(vp_ref, hv_ref, dtype)[0], vc_ref[0]], axis=0
    )
    return k2, v2


def _fwd_kernel(q_ref, kp_ref, kc_ref, vp_ref, vc_ref, *rest, scale):
    """Forward over a (g, w, d) block: g batch-heads' windows per program
    (g=1 is the original one-window-per-program layout). Larger g means
    fewer, fatter programs — bigger MXU tiles and less per-program
    overhead at small w; bounded by the (g, w, 2w) f32 probabilities in
    VMEM. The on-chip winner is chosen by the kernel bench, not assumed.
    ``rest`` is (o_ref,) or, in ring-halo mode, (hk_ref, hv_ref, o_ref)."""
    hk_ref, hv_ref = (rest[0], rest[1]) if len(rest) == 3 else (None, None)
    o_ref = rest[-1]
    w = q_ref.shape[1]
    f32 = jnp.float32
    q = q_ref[...].astype(f32)  # (g, w, d)
    k2 = jnp.concatenate(
        [_prev_block(kp_ref, hk_ref, f32), kc_ref[...].astype(f32)], axis=1
    )  # (g, 2w, d)
    v2 = jnp.concatenate(
        [_prev_block(vp_ref, hv_ref, f32), vc_ref[...].astype(f32)], axis=1
    )
    p = _softmax_rows_batched(q, k2, w, scale)  # (g, w, 2w)
    o = jax.lax.dot_general(  # (g, w, d)
        p, v2,
        dimension_numbers=(((2,), (1,)), ((0,), (0,))),
        preferred_element_type=f32,
    )
    o_ref[...] = o.astype(o_ref.dtype)


def _bwd_kernel(
    q_ref, kp_ref, kc_ref, vp_ref, vc_ref, do_ref, *rest, scale,
):
    hk_ref, hv_ref = (rest[0], rest[1]) if len(rest) == 5 else (None, None)
    dq_ref, dk2_ref, dv2_ref = rest[-3:]
    w = q_ref.shape[1]
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    k2, v2 = _halo_kv(kp_ref, kc_ref, vp_ref, vc_ref, jnp.float32,
                      hk_ref, hv_ref)
    p = _softmax_row(q, k2, w, scale)  # (w, 2w)
    ds = _ds_from(p, do, v2)  # softmax bwd
    # masked positions have p == 0 => ds == 0 there; no extra mask needed

    dq_ref[0] = (
        jnp.dot(ds, k2, preferred_element_type=jnp.float32) * scale
    ).astype(dq_ref.dtype)
    dk2_ref[0, 0] = (
        jax.lax.dot_general(  # ds^T @ q -> (2w, d)
            ds, q,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
    ).astype(dk2_ref.dtype)
    dv2_ref[0, 0] = jax.lax.dot_general(  # p^T @ dO -> (2w, d)
        p, do,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dv2_ref.dtype)


def _softmax_row(q, k2, w, scale):
    """Masked softmax probabilities for one window's (w, 2w) attention
    row (the halo backward's recompute; the forward and kv backward use
    the g-batched twin below)."""
    s = jax.lax.dot_general(
        q, k2,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale
    s = jnp.where(_window_mask(w), s, ATTN_MASK_VALUE)
    s = s - s.max(axis=-1, keepdims=True)
    e = jnp.exp(s)
    return e / e.sum(axis=-1, keepdims=True)


def _ds_from(p, do, v2):
    dp = jax.lax.dot_general(  # dO @ v2^T -> (w, 2w)
        do, v2,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))


def _softmax_rows_batched(q, k2, w, scale):
    """(g, w, d) x (g, 2w, d) -> (g, w, 2w) masked softmax (the g-batched
    twin of _softmax_row; same mask, same f32 accumulation)."""
    s = jax.lax.dot_general(
        q, k2,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    ) * scale
    s = jnp.where(_window_mask(w)[None], s, ATTN_MASK_VALUE)
    s = s - s.max(axis=-1, keepdims=True)
    e = jnp.exp(s)
    return e / e.sum(axis=-1, keepdims=True)


def _ds_from_batched(p, do, v2):
    dp = jax.lax.dot_general(  # (g, w, 2w)
        do, v2,
        dimension_numbers=(((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
    )
    return p * (dp - jnp.sum(dp * p, axis=-1, keepdims=True))


def _bwd_kv_kernel_batched(
    qc_ref, qn_ref, doc_ref, don_ref,
    kp_ref, kc_ref, kn_ref, vp_ref, vc_ref, vn_ref,
    *rest, scale,
):
    """kv-centric backward over (g, w, d) blocks: program j owns k_j/v_j,
    whose only consumers are query windows j ([prev|CUR] half) and j+1
    ([PREV|cur] half); recompute both softmax rows and emit dq_j, dk_j,
    dv_j fully combined — no halo scratch, no post-kernel combine. g=1 is
    the one-window-per-program layout; larger g batches g batch-heads per
    program for fatter MXU tiles (the lever that wins the w=512 forward).
    VMEM cost doubles vs the forward's g blocks — two (g, w, 2w) f32
    probability tensors live at once — so _safe_bh_block gets n_probs=2.
    ``rest`` is (dq, dk, dv) refs or, in ring-halo mode,
    (hk, hv, dq, dk, dv) — the halo only changes row 0's recompute; its
    own gradient is produced outside the kernel (see _bwd_rule)."""
    hk_ref, hv_ref = (rest[0], rest[1]) if len(rest) == 5 else (None, None)
    dq_ref, dk_ref, dv_ref = rest[-3:]
    w = qc_ref.shape[1]
    f32 = jnp.float32
    j = pl.program_id(1)
    has_next = (j < pl.num_programs(1) - 1).astype(f32)

    qc = qc_ref[...].astype(f32)  # (g, w, d)
    doc = doc_ref[...].astype(f32)
    kc = kc_ref[...].astype(f32)
    vc = vc_ref[...].astype(f32)

    # row j: k2 = [k_{j-1} | k_j]; j == 0's prev is halo-or-zeros
    k2 = jnp.concatenate([_prev_block(kp_ref, hk_ref, f32), kc], axis=1)
    v2 = jnp.concatenate([_prev_block(vp_ref, hv_ref, f32), vc], axis=1)
    p = _softmax_rows_batched(qc, k2, w, scale)
    ds = _ds_from_batched(p, doc, v2)

    dq_ref[...] = (
        jax.lax.dot_general(  # (g, w, d)
            ds, k2,
            dimension_numbers=(((2,), (1,)), ((0,), (0,))),
            preferred_element_type=f32,
        ) * scale
    ).astype(dq_ref.dtype)

    tq = lambda a, b: jax.lax.dot_general(  # a^T @ b per g -> (g, w, d)
        a, b,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=f32,
    )
    dk = tq(ds[:, :, w:], qc) * scale
    dv = tq(p[:, :, w:], doc)

    # row j+1: k2 = [k_j | k_{j+1}], zeroed past the clamped last program
    qn = qn_ref[...].astype(f32)
    don = don_ref[...].astype(f32)
    k2n = jnp.concatenate([kc, kn_ref[...].astype(f32)], axis=1)
    v2n = jnp.concatenate([vc, vn_ref[...].astype(f32)], axis=1)
    pn = _softmax_rows_batched(qn, k2n, w, scale)
    dsn = _ds_from_batched(pn, don, v2n)
    dk = dk + has_next * tq(dsn[:, :, :w], qn) * scale
    dv = dv + has_next * tq(pn[:, :, :w], don)

    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _index_maps(w: int, d: int, g: int = 1):
    """cur/prev(-clamped) index maps + a BlockSpec factory for (g, w, d)
    blocks over a (bh, n, d) array; g=1 is one window per program."""
    cur = lambda b, i: (b, i, 0)
    prev = lambda b, i: (b, jnp.maximum(i - 1, 0), 0)
    block = (g, w, d)
    spec = lambda idx: pl.BlockSpec(block, idx, memory_space=pltpu.VMEM)
    return cur, prev, spec


def _specs(w: int, d: int):
    """(q, k_prev, k_cur, v_prev, v_cur) block specs on a (bh, n, d) array.
    The halo spec points one window back (clamped at 0; program 0 zeroes it
    in-register)."""
    cur, prev, spec = _index_maps(w, d)
    return [spec(cur), spec(prev), spec(cur), spec(prev), spec(cur)]


# every kernel here writes disjoint output blocks per grid step (the halo
# backward's overlap is resolved OUTSIDE the kernel), so Mosaic may reorder
# and pipeline both grid dimensions freely.
_PARALLEL_GRID = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel")
)


def _flops(bh: int, n: int, d: int, w: int, n_matmuls: int) -> pl.CostEstimate:
    return pl.CostEstimate(
        flops=n_matmuls * 2 * bh * n * 2 * w * d,
        transcendentals=bh * n * 2 * w,
        bytes_accessed=4 * bh * n * d * 4,
    )


def _parse_bwd_impl(bwd_impl: str) -> tuple[str, int] | None:
    """"kv" / "halo" / "xla" / "kv_g<N>" -> (base_impl, g); None if
    unknown. The kv_g variants run the g-batched kv backward — same math, g
    batch-heads per program (kernel-bench-selectable like the forward's
    bh_block). "xla" differentiates the XLA golden on the saved residuals
    (for shapes where the measured policy finds neither Pallas backward
    wins)."""
    if bwd_impl in ("kv", "halo", "xla"):
        return bwd_impl, 1
    if bwd_impl.startswith("kv_g") and bwd_impl[4:].isdigit():
        return "kv", int(bwd_impl[4:])
    return None


# --------------------------------------------------------------------------
# Measured kernel policy.
#
# pallas_policy.json is a table of on-chip-measured (fwd, bwd, bh_block)
# winners keyed by the shape they were measured at — (window, n, batch*heads)
# — read here. bench.py's kernel phases write the same shape under runs/
# (record_policy_entry); promoting a row into the tracked table is a change.
# Lookup picks the nearest measured shape in log-space with the window
# dominating (the masked-waste/overhead crossover is a function of w first;
# n and bh move the per-program amortization second). An exact match applies
# the evidence directly; a non-exact match is a documented extrapolation,
# surfaced via ``exact_shape_match`` so bench rows can record which one a
# train phase actually ran under.

_POLICY_PATH = Path(__file__).with_name("pallas_policy.json")

# Measurements on one v5e chip, 2026-07-29, older jax; not re-measured on
# the current code — the built-in rows when the JSON table is absent or
# unreadable:
#   w=256 @ n1024 bh128: fwd XLA 3.56 ms vs Pallas 3.99 → XLA fwd;
#          bwd halo 8.79 ms vs XLA 10.71 → Pallas halo bwd (1.22x)
#   w=512 @ n1024 bh128: fwd Pallas g4 4.02 vs XLA 7.87 → Pallas fwd g4;
#          bwd kv 10.12 ms vs XLA 10.94 → Pallas kv bwd (1.08x)
# The crossover: at w>=512 the XLA dense path's masked-waste grows faster
# than the kernel's per-program overhead amortizes, and the kv backward's
# recompute beats the halo scratch traffic. Mixing per-direction winners is
# sound because fwd and bwd are independent pallas_call/XLA programs joined
# only through the (q, k, v) residuals.
_FALLBACK_ENTRIES = (
    {"window": 256, "n": 1024, "bh": 128,
     "fwd": "xla", "bwd": "halo", "bh_block": 1},
    {"window": 512, "n": 1024, "bh": 128,
     "fwd": "pallas", "bwd": "kv", "bh_block": 4},
)

_ENTRY_KEYS = ("window", "n", "bh", "fwd", "bwd", "bh_block")


def _policy_entries(path: Path | None = None) -> list[dict]:
    path = path or _POLICY_PATH
    def _valid(e: dict) -> bool:
        try:
            return (
                all(k in e for k in _ENTRY_KEYS)
                and all(
                    isinstance(e[k], (int, float)) and e[k] > 0
                    for k in ("window", "n", "bh")
                )
                and isinstance(e["bh_block"], int) and e["bh_block"] >= 1
                and e["fwd"] in ("pallas", "xla")
                and _parse_bwd_impl(e["bwd"]) is not None
            )
        except TypeError:
            return False

    try:
        doc = json.loads(path.read_text())
        entries = [e for e in doc.get("entries", []) if _valid(e)]
        if entries:
            return entries
    except (OSError, ValueError):
        pass
    return list(_FALLBACK_ENTRIES)


def policy_decision(
    window_size: int, n: int | None = None, bh: int | None = None,
    path: Path | None = None,
) -> dict:
    """The measured-winner entry nearest to (window, n, bh), annotated with
    ``exact_shape_match`` and the requested shape. ``n``/``bh`` omitted
    match any measured value at that window (nearest by window alone)."""
    entries = _policy_entries(path)

    def dist(e: dict) -> float:
        d = 4.0 * abs(math.log2(window_size / e["window"]))
        if n:
            d += abs(math.log2(n / e["n"]))
        if bh:
            d += 0.5 * abs(math.log2(bh / e["bh"]))
        return d

    best = min(entries, key=dist)
    exact = (
        best["window"] == window_size
        and (n is None or best["n"] == n)
        and (bh is None or best["bh"] == bh)
    )
    return {
        **best,
        "exact_shape_match": exact,
        "requested": {"window": window_size, "n": n, "bh": bh},
    }


def measured_impls(
    window_size: int, n: int | None = None, bh: int | None = None
) -> tuple[str, str, int]:
    """(fwd_impl, bwd_impl, bh_block) from the measured policy table for
    the given shape (nearest measured shape when not an exact match — see
    policy_decision)."""
    e = policy_decision(window_size, n, bh)
    return e["fwd"], e["bwd"], e["bh_block"]


def record_policy_entry(entry: dict, path: Path) -> None:
    """Merge one measured winner into the policy-shaped table at ``path``
    (bench.py's kernel phases call this after an on-chip, non-suspect run,
    with a path under ``runs/`` — the tracked pallas_policy.json is an
    input that a reviewed change updates, never a run; keyed by the
    measured (window, n, bh) so re-measurement replaces, never duplicates).
    Extra keys (timings, provenance) are stored verbatim."""
    missing = [k for k in _ENTRY_KEYS if k not in entry]
    if missing:
        raise ValueError(f"policy entry missing keys {missing}")
    try:
        doc = json.loads(path.read_text())
        assert isinstance(doc.get("entries"), list)
    except (OSError, ValueError, AssertionError):
        doc = {"schema": "pallas-policy-v1", "entries": []}
    key = lambda e: (e["window"], e["n"], e["bh"])
    # drop malformed/legacy rows rather than KeyError after the bench has
    # already spent its chip time — read-side tolerates them the same way
    kept = [
        e for e in doc["entries"]
        if all(k in e for k in ("window", "n", "bh")) and key(e) != key(entry)
    ]
    doc["entries"] = sorted(kept + [entry], key=key)
    tmp = path.with_suffix(".json.tmp")
    tmp.write_text(json.dumps(doc, indent=1))
    tmp.replace(path)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def pallas_local_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    window_size: int,
    scale: float | None = None,
    interpret: bool = False,
    bwd_impl: str = "kv",
    bh_block: int = 1,
    fwd_impl: str = "pallas",
) -> jnp.ndarray:
    """q, k, v: (batch, heads, n, dim_head), n % window_size == 0.
    Returns (batch, heads, n, dim_head) in q.dtype. ``interpret=True`` runs
    the kernel in the Pallas interpreter (CPU tests). ``bwd_impl``:
    ``"kv"`` (combined-in-register, default) or ``"halo"`` (f32 halo
    scratch + shifted add) — see the module docstring. ``bh_block``:
    batch-heads per FORWARD program (falls back to 1 when it doesn't
    divide batch*heads or its f32 probabilities would exceed ~8 MB VMEM);
    the backward's batching is selected independently via
    ``bwd_impl="kv_g<N>"`` so each direction runs only its
    on-chip-measured winner.
    ``fwd_impl``: ``"pallas"`` or ``"xla"`` — the forward and backward are
    independently selectable so callers can pair the measured winner per
    direction (``measured_impls``); the XLA forward still records the same
    (q, k, v) residuals for the Pallas backward."""
    if _parse_bwd_impl(bwd_impl) is None:
        # validate at the call site, not first-grad-time deep in the VJP
        raise ValueError(f"unknown bwd_impl {bwd_impl!r}")
    if fwd_impl not in ("pallas", "xla"):
        raise ValueError(f"unknown fwd_impl {fwd_impl!r}")
    out, _ = _fwd(q, k, v, window_size, scale, interpret, bh_block, fwd_impl)
    return out


def _safe_bh_block(bh_block: int, bh: int, w: int, n_probs: int = 1) -> int:
    """Largest usable g <= bh_block: must divide bh and keep the n_probs
    (g, w, 2w) f32 probability tensors within ~8 MB of VMEM (the batched
    kv backward holds two at once)."""
    g = max(1, min(bh_block, (8 << 20) // (n_probs * w * 2 * w * 4) or 1))
    while bh % g:
        g -= 1
    return g



def _sds(shape, dtype, like):
    """ShapeDtypeStruct carrying ``like``'s varying-mesh-axes type (vma):
    under shard_map's check_vma, pallas_call outputs must declare which
    manual axes they vary over — inherit it from an input, which is
    frozenset() outside shard_map (a no-op there)."""
    return jax.ShapeDtypeStruct(shape, dtype, vma=jax.typeof(like).vma)

def _halo_spec(w: int, d: int, g: int):
    """BlockSpec for a (bh, w, d) halo array: every program reads its own
    batch-heads' halo block (only window 0 uses it in-kernel)."""
    return pl.BlockSpec(
        (g, w, d), lambda b_, i: (b_, 0, 0), memory_space=pltpu.VMEM
    )


def _fwd(q, k, v, window_size, scale, interpret, bh_block=1,
         fwd_impl="pallas", halo_k=None, halo_v=None):
    b, h, n, d = q.shape
    w = window_size
    if n % w != 0:
        raise ValueError(f"sequence length {n} not divisible by window {w}")
    if scale is None:
        scale = d ** -0.5
    if fwd_impl == "xla":
        # measured winner at small windows (see measured_impls): XLA's
        # fused dense path computes the primal; the residuals stay (q, k,
        # v) so the Pallas backward recomputes probabilities identically
        # to the pure-Pallas path (flash-style recompute either way)
        from progen_tpu.ops.attention import local_attention

        out = local_attention(
            q, k, v, window_size=w, scale=scale,
            first_prev_k=halo_k, first_prev_v=halo_v,
        )
        return out, (q, k, v)
    bh, nw = b * h, n // w
    g = _safe_bh_block(bh_block, bh, w)
    qf, kf, vf = (t.reshape(bh, n, d) for t in (q, k, v))

    cur, prev, spec = _index_maps(w, d, g)
    in_specs = [spec(cur), spec(prev), spec(cur), spec(prev), spec(cur)]
    operands = [qf, kf, kf, vf, vf]
    if halo_k is not None:
        in_specs += [_halo_spec(w, d, g)] * 2
        operands += [halo_k.reshape(bh, w, d), halo_v.reshape(bh, w, d)]
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale),
        grid=(bh // g, nw),
        in_specs=in_specs,
        out_specs=spec(cur),
        out_shape=_sds((bh, n, d), q.dtype, qf),
        cost_estimate=_flops(bh, n, d, w, 2),
        compiler_params=_PARALLEL_GRID,
        interpret=interpret,
    )(*operands)
    return out.reshape(b, h, n, d), (q, k, v)


def _fwd_rule(q, k, v, window_size, scale, interpret, bwd_impl, bh_block,
              fwd_impl):
    return _fwd(q, k, v, window_size, scale, interpret, bh_block, fwd_impl)


def _halo_grads(qf, kf, vf, gf, halo_k, halo_v, w, d, scale, shape):
    """d(halo_k), d(halo_v): only window 0's row touches the halo, so its
    gradient is one tiny (bh, w, 2w) recompute in plain XLA — both Pallas
    backwards deliberately exclude the prev-half of row 0 from dk/dv (for
    zero halos those keys are constants), so nothing double-counts."""
    b, h, _, _ = shape
    bh = b * h
    f32 = jnp.float32
    hk = halo_k.reshape(bh, w, d).astype(f32)
    hv = halo_v.reshape(bh, w, d).astype(f32)
    q0 = qf[:, :w].astype(f32)
    do0 = gf[:, :w].astype(f32)
    k2_0 = jnp.concatenate([hk, kf[:, :w].astype(f32)], axis=1)
    v2_0 = jnp.concatenate([hv, vf[:, :w].astype(f32)], axis=1)
    p0 = _softmax_rows_batched(q0, k2_0, w, scale)
    ds0 = _ds_from_batched(p0, do0, v2_0)
    tq = lambda a, b_: jax.lax.dot_general(
        a, b_,
        dimension_numbers=(((1,), (1,)), ((0,), (0,))),
        preferred_element_type=f32,
    )
    d_hk = (tq(ds0[:, :, :w], q0) * scale).astype(halo_k.dtype)
    d_hv = tq(p0[:, :, :w], do0).astype(halo_v.dtype)
    return d_hk.reshape(b, h, w, d), d_hv.reshape(b, h, w, d)


def _bwd_core(window_size, scale, interpret, bwd_impl, bh_block, fwd_impl,
              residuals, g, halo_k=None, halo_v=None):
    q, k, v = residuals
    b, h, n, d = q.shape
    w = window_size
    if scale is None:
        scale = d ** -0.5
    bh, nw = b * h, n // w
    qf, kf, vf = (t.reshape(bh, n, d) for t in (q, k, v))
    gf = g.reshape(bh, n, d)
    with_halo = halo_k is not None
    halo_ops = (
        [halo_k.reshape(bh, w, d), halo_v.reshape(bh, w, d)]
        if with_halo else []
    )

    parsed = _parse_bwd_impl(bwd_impl)
    if parsed is None:
        raise ValueError(f"unknown bwd_impl {bwd_impl!r}")
    base_impl, g_req = parsed

    if base_impl == "xla":
        # differentiate the XLA golden from the same residuals — the
        # policy's escape hatch for shapes where both Pallas backwards
        # lose on-chip (fwd_impl stays independently selectable)
        from progen_tpu.ops.attention import local_attention

        if with_halo:
            _, vjp = jax.vjp(
                lambda q_, k_, v_, hk_, hv_: local_attention(
                    q_, k_, v_, window_size=w, scale=scale,
                    first_prev_k=hk_, first_prev_v=hv_,
                ),
                q, k, v, halo_k, halo_v,
            )
            return vjp(g)
        _, vjp = jax.vjp(
            lambda q_, k_, v_: local_attention(
                q_, k_, v_, window_size=w, scale=scale
            ),
            q, k, v,
        )
        return vjp(g)

    if base_impl == "kv":
        g_bwd = _safe_bh_block(g_req, bh, w, n_probs=2)
        cur, prev, spec = _index_maps(w, d, g_bwd)
        nxt = lambda b_, i: (b_, jnp.minimum(i + 1, nw - 1), 0)
        in_specs = [
            spec(cur), spec(nxt),              # q_j, q_{j+1}
            spec(cur), spec(nxt),              # do_j, do_{j+1}
            spec(prev), spec(cur), spec(nxt),  # k_{j-1}, k_j, k_{j+1}
            spec(prev), spec(cur), spec(nxt),  # v_{j-1}, v_j, v_{j+1}
        ]
        if with_halo:
            in_specs += [_halo_spec(w, d, g_bwd)] * 2
        dq, dk, dv = pl.pallas_call(
            functools.partial(_bwd_kv_kernel_batched, scale=scale),
            grid=(bh // g_bwd, nw),
            in_specs=in_specs,
            out_specs=[spec(cur)] * 3,
            out_shape=[
                _sds((bh, n, d), q.dtype, qf),
                _sds((bh, n, d), k.dtype, qf),
                _sds((bh, n, d), v.dtype, qf),
            ],
            cost_estimate=_flops(bh, n, d, w, 8),
            compiler_params=_PARALLEL_GRID,
            interpret=interpret,
        )(qf, qf, gf, gf, kf, kf, kf, vf, vf, vf, *halo_ops)
        out = tuple(t.reshape(b, h, n, d) for t in (dq, dk, dv))
        if with_halo:
            return out + _halo_grads(
                qf, kf, vf, gf, halo_k, halo_v, w, d, scale, q.shape
            )
        return out

    halo_block = pl.BlockSpec(
        (1, 1, 2 * w, d), lambda b_, i: (b_, i, 0, 0), memory_space=pltpu.VMEM
    )
    in_specs = _specs(w, d) + [
        pl.BlockSpec(
            (1, w, d), lambda b_, i: (b_, i, 0), memory_space=pltpu.VMEM
        )
    ]
    if with_halo:
        in_specs += [_halo_spec(w, d, 1)] * 2
    dq, dk2, dv2 = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale),
        grid=(bh, nw),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec(
                (1, w, d), lambda b_, i: (b_, i, 0), memory_space=pltpu.VMEM
            ),
            halo_block,
            halo_block,
        ],
        out_shape=[
            _sds((bh, n, d), q.dtype, qf),
            _sds((bh, nw, 2 * w, d), jnp.float32, qf),
            _sds((bh, nw, 2 * w, d), jnp.float32, qf),
        ],
        cost_estimate=_flops(bh, n, d, w, 5),
        compiler_params=_PARALLEL_GRID,
        interpret=interpret,
    )(qf, kf, kf, vf, vf, gf, *halo_ops)

    def combine(d2):
        """dk[i] = d2[i, cur-half] + d2[i+1, prev-half]; program 0's
        prev-half is dropped — for zero halos those keys are constants,
        and for a real halo its gradient is produced by _halo_grads."""
        cur = d2[:, :, w:]
        nxt = jnp.pad(d2[:, 1:, :w], ((0, 0), (0, 1), (0, 0), (0, 0)))
        return (cur + nxt).reshape(bh, n, d)

    dk = combine(dk2).astype(k.dtype).reshape(b, h, n, d)
    dv = combine(dv2).astype(v.dtype).reshape(b, h, n, d)
    out = (dq.reshape(b, h, n, d), dk, dv)
    if with_halo:
        return out + _halo_grads(
            qf, kf, vf, gf, halo_k, halo_v, w, d, scale, q.shape
        )
    return out


def _bwd_rule(window_size, scale, interpret, bwd_impl, bh_block, fwd_impl,
              residuals, g):
    return _bwd_core(window_size, scale, interpret, bwd_impl, bh_block,
                     fwd_impl, residuals, g)


pallas_local_attention.defvjp(_fwd_rule, _bwd_rule)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def pallas_local_attention_halo(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    halo_k: jnp.ndarray,
    halo_v: jnp.ndarray,
    window_size: int,
    scale: float | None = None,
    interpret: bool = False,
    bwd_impl: str = "kv",
    bh_block: int = 1,
    fwd_impl: str = "pallas",
) -> jnp.ndarray:
    """``pallas_local_attention`` with window 0's "previous window"
    overridden by ``halo_k``/``halo_v`` (batch, heads, window, dim_head) —
    the sequence-parallel composition: ring shards exchange one window of
    k/v over ``ppermute`` (parallel/ring_attention.py) and run this kernel
    locally, so long-context multi-chip training uses the same measured
    kernel as single-chip. Exactly equals ``ops.attention.local_attention``
    with ``first_prev_k/v`` (the golden), including halo gradients (the
    halo's grad is one tiny window-0 recompute outside the kernel)."""
    if _parse_bwd_impl(bwd_impl) is None:
        raise ValueError(f"unknown bwd_impl {bwd_impl!r}")
    if fwd_impl not in ("pallas", "xla"):
        raise ValueError(f"unknown fwd_impl {fwd_impl!r}")
    out, _ = _fwd(q, k, v, window_size, scale, interpret, bh_block,
                  fwd_impl, halo_k, halo_v)
    return out


def _fwd_rule_halo(q, k, v, halo_k, halo_v, window_size, scale, interpret,
                   bwd_impl, bh_block, fwd_impl):
    out, _ = _fwd(q, k, v, window_size, scale, interpret, bh_block,
                  fwd_impl, halo_k, halo_v)
    return out, (q, k, v, halo_k, halo_v)


def _bwd_rule_halo(window_size, scale, interpret, bwd_impl, bh_block,
                   fwd_impl, residuals, g):
    q, k, v, halo_k, halo_v = residuals
    return _bwd_core(window_size, scale, interpret, bwd_impl, bh_block,
                     fwd_impl, (q, k, v), g, halo_k, halo_v)


pallas_local_attention_halo.defvjp(_fwd_rule_halo, _bwd_rule_halo)
