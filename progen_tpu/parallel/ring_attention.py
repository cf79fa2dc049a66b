"""Explicit sequence-parallel windowed attention via ring halo exchange.

The GSPMD path (mesh ``seq`` axis + logical constraints) already handles
sequence-sharded training automatically — see the seq-parallel parity test
in tests/test_train.py. This module is the EXPLICIT collective formulation
of the same computation, the windowed-attention specialization of ring
attention: because each query window attends to at most the previous
window, a sequence shard needs exactly ONE window of halo from its left
neighbor, exchanged with a single ``ppermute`` hop over the ring (rides ICI
on a TPU torus). No iteration over the ring is needed — the window
structure collapses ring attention's S-step pipeline to one step.

Per shard (inside ``shard_map`` over the ``seq`` axis):
  1. send my LAST window's k/v to my right neighbor (ppermute, one hop);
  2. shard 0 zeroes the received halo (window 0's "previous window" is
     zeros in the reference semantics — progen.py:90-96);
  3. run the standard windowed attention locally, overriding window 0's
     previous window with the halo.

Requires local_seq_len % window_size == 0 (i.e. shard boundaries align
with window boundaries: seq_len % (S * window_size) == 0).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from progen_tpu.ops.attention import local_attention


def ring_local_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    window_size: int,
    mesh: Mesh,
    seq_axis: str = "seq",
    batch_axis: str | None = "data",
    scale: float | None = None,
    use_pallas: bool = False,
) -> jnp.ndarray:
    """q, k, v: (batch, heads, n, dim_head), n sharded over ``seq_axis``
    (batch over ``batch_axis`` when given). Returns same shape/sharding.
    Exactly equal to ``local_attention`` on the gathered arrays.

    ``use_pallas`` runs each shard's local attention through the measured
    Pallas kernel (ops/pallas_attention.pallas_local_attention_halo — the
    halo-aware variant, impls chosen by the policy table at the SHARD's
    shapes), so long-context multi-chip training composes the two flagship
    paths instead of falling back to the XLA dense attention per shard."""
    n_shards = mesh.shape[seq_axis]
    _, _, n, _ = q.shape
    w = window_size
    if n % (n_shards * w) != 0:
        raise ValueError(
            f"seq_len {n} must divide into {n_shards} shards of whole "
            f"{w}-token windows"
        )
    # decided OUTSIDE shard_map so check_vma below can stay on for
    # compiled TPU runs (the checker only trips on the interpret-mode
    # pallas lowering)
    interpret = jax.default_backend() != "tpu"

    def shard_fn(q, k, v):
        # NOTE: deliberately TWO ppermutes. Fusing the k/v halos into one
        # collective (stack or concat) trips a shard_map transpose
        # sharding-inference assertion in jax 0.9 when differentiated;
        # XLA's collective combiner merges adjacent small ppermutes anyway.
        perm = [(i, (i + 1) % n_shards) for i in range(n_shards)]
        halo_k = jax.lax.ppermute(k[:, :, -w:], seq_axis, perm=perm)
        halo_v = jax.lax.ppermute(v[:, :, -w:], seq_axis, perm=perm)
        is_first = jax.lax.axis_index(seq_axis) == 0
        zero = jnp.zeros((), halo_k.dtype)
        halo_k = jnp.where(is_first, zero, halo_k)
        halo_v = jnp.where(is_first, zero, halo_v)
        if use_pallas:
            from progen_tpu.ops.pallas_attention import (
                measured_impls,
                pallas_local_attention_halo,
            )

            # policy lookup at the LOCAL (per-shard) shapes — what the
            # kernel actually runs; trace-time Python, so file reads are
            # fine inside shard_map
            b_l, h_l, n_l, _ = q.shape
            fwd_impl, bwd_impl, g = measured_impls(
                w, n=n_l, bh=b_l * h_l
            )
            # both directions lost on-chip at this shape: the XLA halo
            # path below is the measured winner, not a fallback
            if not (fwd_impl == "xla" and bwd_impl == "xla"):
                return pallas_local_attention_halo(
                    q, k, v, halo_k, halo_v, w, scale, interpret,
                    bwd_impl, g, fwd_impl,
                )
        return local_attention(
            q, k, v,
            window_size=w,
            scale=scale,
            first_prev_k=halo_k,
            first_prev_v=halo_v,
        )

    spec = P(batch_axis, None, seq_axis, None)
    # check_vma off ONLY for the interpret-mode Pallas path (CPU tests):
    # that lowering mixes kernel-internal constants (no vma) with varying
    # operands under the varying-manual-axes checker, which rejects the mul
    # ("Primitive mul requires varying manual axes to match"); jax's own
    # error message prescribes check_vma=False. The compiled kernel and the
    # XLA path keep the checker on (the compiled halo kernel passes it on
    # four v5e chips, data=2 x seq=2 — chip run, PR 21).
    return jax.shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=not (use_pallas and interpret),
    )(q, k, v)
