"""GPipe-style pipeline parallelism over a stacked layer axis.

The reference has no pipeline parallelism (SURVEY §2.5: PP "NO"); this is
the TPU-native formulation for when a model's layers outgrow one chip's
HBM even after TP: the ``scan_layers`` stacked parameter axis (L, ...) is
sharded over a mesh axis into P stages of L/P layers, and microbatches
flow through the stages with a rotating ``ppermute`` schedule.

Schedule (classic GPipe, M microbatches, P stages, M+P-1 ticks):

  tick t: stage p runs microbatch (t - p) through its local layers when
  0 <= t-p < M — stage 0 injects microbatch t from the input, every other
  stage consumes the activation its left neighbor sent last tick; after
  computing, every stage sends its activation one hop right. The first
  P-1 and last P-1 ticks are the pipeline bubble.

Differentiable end-to-end: the backward pass is jax's transpose of the
scan-of-ppermute (activations flow left, cotangents flow right). The backward
schedule is the autodiff TRANSPOSE of GPipe — all forwards then all
backwards, so activations for all M microbatches stay live until the
backward sweep (O(M) activation memory, not 1F1B's O(P)); pair with
remat on the block_fn when that matters.

This module is deliberately a standalone op + tests (like
parallel/ring_attention.py): the production train step covers dp/tp/sp via
GSPMD; pipeline_apply is the building block for depth-sharded deployments.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    block_fn: Callable,
    stacked_params,
    x: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str,
    n_microbatches: int,
    data_axis: str | None = None,
) -> jnp.ndarray:
    """Run L stacked layers as a P-stage pipeline over microbatches.

    block_fn(params_one_layer, x) -> x : one layer's forward.
    stacked_params: pytree with leading axis L on every leaf (the
      scan_layers layout), sharded/split over mesh axis ``axis`` (P stages,
      L % P == 0 — each stage owns L/P consecutive layers).
    x: (B, ...) global batch, B % n_microbatches == 0.
    data_axis: optional mesh axis to ALSO shard each microbatch's row dim
      over (PP x DP composition): every data row then pipelines its own
      1/D slice of each microbatch instead of redundantly recomputing the
      full batch. None or a size-1 axis = pure pipeline.

    Returns block-sequential-equivalent output (B, ...).
    """
    n_stages = mesh.shape[axis]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if L % n_stages:
        raise ValueError(f"{L} layers not divisible by {n_stages} stages")
    B = x.shape[0]
    M = n_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    dp = (data_axis is not None and data_axis in mesh.shape
          and mesh.shape[data_axis] > 1)
    if dp and mb % mesh.shape[data_axis]:
        raise ValueError(
            f"microbatch rows {mb} not divisible by data axis "
            f"{mesh.shape[data_axis]}"
        )
    x_mb = x.reshape((M, mb) + x.shape[1:])

    def stage_fn(local_params, x_mb):
        # local_params leaves: (L/P, ...); x_mb replicated (M, mb, ...)
        p = jax.lax.axis_index(axis)
        T = M + n_stages - 1

        def local_layers(h):
            def body(h, layer_params):
                return block_fn(layer_params, h), None

            h, _ = jax.lax.scan(body, h, local_params)
            return h

        def tick(carry, t):
            left_buf = carry  # activation received from the left neighbor
            mb_idx = jnp.clip(t - p, 0, M - 1)
            inject = jax.lax.dynamic_index_in_dim(
                x_mb, mb_idx, axis=0, keepdims=False
            )
            h = jnp.where(p == 0, inject, left_buf)
            out = local_layers(h)
            # rotate one hop right for the next tick
            left_buf = jax.lax.ppermute(
                out, axis,
                perm=[(i, (i + 1) % n_stages) for i in range(n_stages)],
            )
            return left_buf, out

        # carry must be marked device-varying over the pipeline axis (the
        # varying-manual-axes typing for scan-of-ppermute); under DP
        # composition the zeros_like already inherits the data-varying type
        # from the sharded input, so only the stage axis needs the cast
        init = jax.lax.pcast(
            jnp.zeros_like(x_mb[0]), (axis,), to="varying"
        )
        _, outs = jax.lax.scan(tick, init, jnp.arange(T))
        # the LAST stage's outputs at ticks P-1 .. P-1+M-1 are the finished
        # microbatches; other stages' rows are bubble garbage that the
        # (P, ...)-stacked out_spec lets the caller discard
        return outs[None]  # (1, T, mb, ...) -> stage-stacked by out_spec

    outs = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(P(axis), P(None, data_axis) if dp else P()),
        out_specs=P(axis, None, data_axis) if dp else P(axis),
    )(stacked_params, x_mb)
    # outs: (P, T, mb, ...); finished microbatches live on the last stage
    final = outs[n_stages - 1, n_stages - 1 : n_stages - 1 + M]
    return final.reshape((B,) + x.shape[1:])


def pipeline_forward(
    model,
    params,
    tokens: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str = "model",
    n_microbatches: int,
    data_axis: str | None = "data",
) -> jnp.ndarray:
    """Full ProGen forward with the uniform block stack executed as a
    pipeline — the model-level integration of ``pipeline_apply``.

    ``model`` is a ``ProGen`` built with ``config.scan_layers=True`` (the
    stacked ``params['layers']`` subtree IS the pipeline's layer axis;
    ``models/progen.stack_params`` converts unrolled checkpoints). Embedding,
    RoPE tables, the trailing gMLP blocks, and the logits head run outside
    the pipeline (they are O(1) in depth — the uniform stack is what
    outgrows a chip); each is the SAME flax module the plain forward uses,
    applied to the same param subtrees, so outputs match
    ``model.apply({'params': params}, tokens)`` exactly.

    Run OUTSIDE any ``nn.logical_axis_rules`` context: stages execute inside
    ``shard_map``, where GSPMD sharding constraints don't apply (the
    modules' ``with_logical_constraint`` calls no-op without active rules).
    """
    from flax import linen as nn

    from progen_tpu.models.layers import (
        FeedForwardBlock,
        LocalAttentionBlock,
        ScaleNorm,
    )
    from progen_tpu.models.progen import UniformBlock
    from progen_tpu.ops.rotary import fixed_pos_embedding

    c = model.config
    if "layers" not in params:
        raise ValueError(
            "pipeline_forward needs the scan_layers stacked param layout "
            "(use models.progen.stack_params to convert)"
        )
    n = tokens.shape[-1]
    n_uniform = c.depth - c.global_mlp_depth

    x = nn.Embed(
        c.num_tokens,
        c.dim,
        dtype=c.compute_dtype,
        param_dtype=c.params_dtype,
        name="embed",
    ).apply({"params": params["embed"]}, tokens)
    sin, cos = fixed_pos_embedding(n, c.dim_head)

    block = UniformBlock(c, glu=c.ff_glu)

    def block_fn(layer_params, h):
        h, _ = block.apply({"params": layer_params}, h, sin, cos)
        return h

    if c.remat:
        # the backward sweep only keeps each layer's INPUT boundary and
        # recomputes its internals — the same per-block remat the plain
        # scan_layers path gets (models/progen.py), which is what bounds
        # the GPipe transpose's live activations to microbatch boundaries
        block_fn = jax.checkpoint(block_fn)

    x = pipeline_apply(
        block_fn,
        params["layers"],
        x,
        mesh=mesh,
        axis=axis,
        n_microbatches=n_microbatches,
        data_axis=data_axis,
    )

    for i in range(n_uniform, c.depth):
        use_gmlp = (c.depth - i) <= c.global_mlp_depth
        x = x + LocalAttentionBlock(c).apply(
            {"params": params[f"attn{i}"]}, x, sin, cos, None
        )
        x = x + FeedForwardBlock(
            c, glu=(not use_gmlp) and c.ff_glu, spatial_gate=use_gmlp
        ).apply({"params": params[f"ff{i}"]}, x, None)

    x = ScaleNorm(c.layer_norm_epsilon, c.compute_dtype, c.params_dtype).apply(
        {"params": params["ScaleNorm_0"]}, x
    )
    logits = nn.Dense(
        c.num_tokens,
        dtype=c.compute_dtype,
        param_dtype=c.params_dtype,
        name="to_logits",
    ).apply({"params": params["to_logits"]}, x)
    return logits.astype(jnp.float32)


def make_pipeline_train_step(
    model,
    optimizer,
    *,
    mesh: Mesh,
    axis: str = "model",
    n_microbatches: int,
):
    """The production train step (EOS-masked CE, grad-accum scan, clip,
    masked AdamW — training/step.make_train_step) with the forward replaced
    by ``pipeline_forward``: the depth-sharded deployment path when the
    layer stack outgrows one chip even after TP. Composes with data
    parallelism: on a mesh with ``data > 1`` each microbatch's rows are
    sharded over the data axis inside the pipeline (every chip does 1/D of
    the work; grads psum over data via the shard_map transpose).

    Uses ``rules=()``: sharding is explicit (shard_map over ``axis``), so
    GSPMD logical constraints must stay inert — they cannot apply inside
    manual axes. Gradients flow through the pipeline as its autodiff
    transpose (cotangents ride the reversed ppermute ring)."""
    from progen_tpu.training.step import make_train_step

    def forward(params, ids):
        return pipeline_forward(
            model, params, ids,
            mesh=mesh, axis=axis, n_microbatches=n_microbatches,
        )

    return make_train_step(model, optimizer, rules=(), forward_fn=forward)


def compile_pipeline_train_step(
    model,
    optimizer,
    shardings,
    mesh: Mesh,
    *,
    axis: str = "model",
    n_microbatches: int,
):
    """jit ``make_pipeline_train_step`` with explicit state/batch shardings
    and a donated state — the pipeline twin of
    ``training/step.compile_train_step``. ``shardings`` must be built with
    ``partition.PIPELINE_RULES`` (stacked layer axis over ``axis``; TP rules
    off). MEMORY NOTE: the backward is GPipe's autodiff transpose — all M
    microbatches' stage activations stay live until the backward sweep
    (O(M) activation memory, not 1F1B's O(stages)); pair with
    ``config.remat`` when that matters."""
    from progen_tpu.parallel.partition import batch_sharding

    step = make_pipeline_train_step(
        model, optimizer, mesh=mesh, axis=axis,
        n_microbatches=n_microbatches,
    )
    return jax.jit(
        step,
        in_shardings=(shardings, batch_sharding(mesh, accum_axis=True)),
        out_shardings=(shardings, None),
        donate_argnums=(0,),
    )
