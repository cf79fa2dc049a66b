"""1F1B (one-forward-one-backward) pipeline-parallel training schedule.

`parallel/pipeline.py` runs GPipe: all forwards, then the autodiff
transpose — every microbatch's stage activations stay live until the
backward sweep (O(M) per stage, bounded to boundary activations by remat).
This module owns BOTH directions in one manually-scheduled loop instead:
the last stage computes its microbatch loss the moment the activation
arrives and the cotangent immediately flows back, so a stage holds at most
``2*(P-1)`` in-flight boundary activations — **O(stages), independent of
the microbatch count**. The reference has no pipeline parallelism at all
(SURVEY §2.5: PP "NO"); this is the TPU-native deployment path for depth
that outgrows a chip at large M.

Schedule (unit tick = one F slot + one B slot per stage, SPMD-uniform):

  stage p forwards  microbatch f = t - p                while 0 <= f < M
  stage p backwards microbatch b = t - 2*(P-1) + p      while 0 <= b < M

  * activations hop one stage right per tick (ppermute), cotangents hop
    one stage left — both produced and consumed on consecutive ticks;
  * the LAST stage's f and b coincide (b = f), so its loss head runs
    fused with the forward slot and no cotangent is ever stored;
  * total ticks T = M + 2*(P-1); in-flight activations at stage p are
    f - b = 2*(P-1-p) <= 2*(P-1), kept in a ring buffer of 2P slots.

Gradient exactness: the backward slot RECOMPUTES its stage's forward from
the saved boundary input (remat-style, same trade as jax.checkpoint) and
applies ``jax.vjp`` — no approximation anywhere; the parity tests pin the
grads against ``jax.grad`` of the sequential composition. Non-participating
slots compute on finite garbage (zero-initialized buffers) and are masked
out of every accumulator, the standard SPMD-uniform trick.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _tree_add_masked(acc, new, mask):
    return jax.tree.map(lambda a, n: a + n * mask.astype(n.dtype), acc, new)


def pipeline_1f1b_loss_and_grads(
    fn_pre: Callable,
    block_fn: Callable,
    fn_loss: Callable,
    params_pre,
    stacked_params,
    params_post,
    tokens: jnp.ndarray,
    *,
    mesh: Mesh,
    axis: str,
    n_microbatches: int,
    data_axis: str | None = "data",
):
    """One 1F1B pass: mean microbatch loss + grads for all three param
    groups.

    fn_pre(params_pre, ids) -> h          : embedding etc., runs on stage 0
      (ids = tokens[..., :-1], the model inputs).
    block_fn(one_layer_params, h) -> h    : one uniform layer.
    fn_loss(params_post, h, tokens_mb) -> scalar : trailing layers + head +
      loss for ONE microbatch, runs fused with the last stage's forward.
    stacked_params: leaves with leading axis L, sharded over ``axis`` into
      P stages of L/P layers (the scan_layers layout).
    tokens: (B, L+1) int rows (inputs+targets), B % n_microbatches == 0.
    data_axis: optional mesh axis to ALSO shard each microbatch's row dim
      over (PP x DP composition): every data row pipelines its own 1/D
      slice of each microbatch and grads/loss psum-mean over the axis.
      None or a size-1 axis = pure pipeline.

    Returns (loss, (g_pre, g_stack, g_post)): loss is the mean over
    microbatches; g_stack leaves keep the stacked (L, ...) layout;
    g_pre/g_post are replicated (psum over the stage axis of the one
    participating stage's accumulation).
    """
    n_stages = mesh.shape[axis]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    if L % n_stages:
        raise ValueError(f"{L} layers not divisible by {n_stages} stages")
    B = tokens.shape[0]
    M = n_microbatches
    if B % M:
        raise ValueError(f"batch {B} not divisible by {M} microbatches")
    mb = B // M
    dp = (data_axis is not None and data_axis in mesh.shape
          and mesh.shape[data_axis] > 1)
    n_data = mesh.shape[data_axis] if dp else 1
    if dp and mb % n_data:
        raise ValueError(
            f"microbatch rows {mb} not divisible by data axis {n_data}"
        )
    tokens_mb = tokens.reshape((M, mb) + tokens.shape[1:])

    def stage_fn(params_pre, local_params, params_post, tokens_mb):
        p = jax.lax.axis_index(axis)
        last = n_stages - 1
        ring_slots = 2 * n_stages
        T = M + 2 * (n_stages - 1)

        def local_apply(lp, h):
            def body(h_, layer):
                return block_fn(layer, h_), None

            return jax.lax.scan(body, h, lp)[0]

        # probe shapes with one dummy application (trace-time only)
        h_shape = jax.eval_shape(
            lambda pp: fn_pre(pp, tokens_mb[0][..., :-1]), params_pre
        )
        zero_h = jnp.zeros(h_shape.shape, h_shape.dtype)
        # under DP composition every carried value mixes with data-varying
        # token shards inside the loop, so the scan carry's vma must carry
        # BOTH axes from the start (scan requires a fixed carry type)
        vaxes = (axis, data_axis) if dp else (axis,)
        varying = lambda x: jax.lax.pcast(x, vaxes, to="varying")

        # CRITICAL: differentiate against VARYING copies of the replicated
        # param groups. vjp wrt an invariant input with a varying cotangent
        # makes jax insert a cross-stage psum in the transpose — which
        # would sum every stage's masked-out garbage head/embed gradients
        # into the real one. Varying copies keep d_pre/d_post per-stage;
        # the single participating stage's accumulation is psum'd once,
        # explicitly, at the end.
        params_pre = jax.tree.map(varying, params_pre)
        params_post = jax.tree.map(varying, params_post)
        # same trap under DP composition: local_params arrive varying over
        # ``axis`` only, so a data-varying cotangent would make the vjp
        # implicitly psum d_local over data — and the explicit psum at the
        # end would then double-count by exactly n_data. A data-varying
        # copy keeps d_local per-shard. (pcast rejects already-varying
        # axes, so cast over data alone.)
        if dp:
            data_varying = lambda x: jax.lax.pcast(
                x, (data_axis,), to="varying"
            )
            local_params = jax.tree.map(data_varying, local_params)

        perm_right = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        perm_left = [(i, (i - 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            (act_in, ct_in, ring, g_stack, g_pre, g_post, loss_acc) = carry
            f = t - p
            b = t - 2 * (n_stages - 1) + p
            f_valid = (f >= 0) & (f < M)
            b_valid = (b >= 0) & (b < M)
            f_idx = jnp.clip(f, 0, M - 1)
            b_idx = jnp.clip(b, 0, M - 1)

            # ---- forward slot: stage 0 injects, others consume the hop
            toks_f = jax.lax.dynamic_index_in_dim(
                tokens_mb, f_idx, axis=0, keepdims=False
            )
            pre_out = fn_pre(params_pre, toks_f[..., :-1])
            h_in = jnp.where(p == 0, pre_out, act_in)
            h_out = local_apply(local_params, h_in)
            # invalid forward slots (warmup/drain) write to the dead slot
            # ``ring_slots`` — a clipped f_idx would clobber slot M-1 % R,
            # which trailing stages' backwards still need during drain
            write_idx = jnp.where(f_valid, f_idx % ring_slots, ring_slots)
            ring = jax.lax.dynamic_update_index_in_dim(
                ring, h_in, write_idx, axis=0
            )

            # ---- loss head (meaningful on the last stage, whose b == f):
            # loss + d(post) + the cotangent that starts the backward
            loss_mb, vjp_post = jax.vjp(
                lambda pp, h: fn_loss(pp, h, toks_f), params_post, h_out
            )
            d_post, d_hout = vjp_post(varying(jnp.ones((), loss_mb.dtype)))

            # ---- backward slot: recompute this stage's forward from the
            # saved boundary input, then vjp (remat-style, grad-exact)
            h_saved = jax.lax.dynamic_index_in_dim(
                ring, b_idx % ring_slots, axis=0, keepdims=False
            )
            ct = jnp.where(p == last, d_hout, ct_in)
            _, vjp_local = jax.vjp(local_apply, local_params, h_saved)
            d_local, d_hin = vjp_local(ct)

            # stage 0's d_hin is the gradient at fn_pre's output
            toks_b = jax.lax.dynamic_index_in_dim(
                tokens_mb, b_idx, axis=0, keepdims=False
            )
            _, vjp_pre = jax.vjp(
                lambda pp: fn_pre(pp, toks_b[..., :-1]), params_pre
            )
            (d_pre,) = vjp_pre(d_hin)

            g_stack = _tree_add_masked(g_stack, d_local, b_valid)
            g_pre = _tree_add_masked(g_pre, d_pre, b_valid & (p == 0))
            head_valid = f_valid & (p == last)
            g_post = _tree_add_masked(g_post, d_post, head_valid)
            loss_acc = loss_acc + loss_mb * head_valid.astype(loss_mb.dtype)

            act_in = jax.lax.ppermute(h_out, axis, perm=perm_right)
            ct_in = jax.lax.ppermute(d_hin, axis, perm=perm_left)
            return (
                (act_in, ct_in, ring, g_stack, g_pre, g_post, loss_acc),
                None,
            )

        zeros_like_f32 = lambda tree: jax.tree.map(
            lambda x: varying(jnp.zeros(x.shape, x.dtype)), tree
        )
        init = (
            varying(zero_h),                                   # act_in
            varying(zero_h),                                   # ct_in
            varying(
                # +1: the dead slot absorbing invalid-slot writes
                jnp.zeros((ring_slots + 1,) + zero_h.shape, zero_h.dtype)
            ),                                                 # ring
            zeros_like_f32(local_params),                      # g_stack
            zeros_like_f32(params_pre),                        # g_pre
            zeros_like_f32(params_post),                       # g_post
            varying(jnp.zeros((), jnp.float32)),               # loss
        )
        carry, _ = jax.lax.scan(tick, init, jnp.arange(T))
        _, _, _, g_stack, g_pre, g_post, loss_acc = carry

        # only one stage accumulated each of these — psum replicates.
        # grads were accumulated with unit cotangent per microbatch while
        # the reported loss is the MEAN over M (and, under DP, over the
        # n_data per-shard means): scale to match. Under DP the psums also
        # reduce over data — each data row holds grads of ITS 1/D rows.
        inv_m = 1.0 / (M * n_data)
        scale_m = lambda tree: jax.tree.map(
            lambda x: x * jnp.asarray(inv_m, x.dtype), tree
        )
        reduce_axes = vaxes
        g_pre = scale_m(jax.lax.psum(g_pre, reduce_axes))
        g_post = scale_m(jax.lax.psum(g_post, reduce_axes))
        if dp:
            g_stack = jax.tree.map(
                lambda x: jax.lax.psum(x, data_axis), g_stack
            )
        g_stack = scale_m(g_stack)
        loss = jax.lax.psum(loss_acc, reduce_axes) / (M * n_data)
        # g_stack stays stage-local; the (1, ...) leading axis is
        # re-stacked to (L, ...) by the P(axis) out_spec
        g_stack = jax.tree.map(lambda x: x[None], g_stack)
        return loss, g_pre, g_stack, g_post

    loss, g_pre, g_stack, g_post = jax.shard_map(
        stage_fn,
        mesh=mesh,
        in_specs=(P(), P(axis), P(),
                  P(None, data_axis) if dp else P()),
        out_specs=(P(), P(), P(axis), P()),
    )(params_pre, stacked_params, params_post, tokens_mb)
    g_stack = jax.tree.map(
        lambda x: x.reshape((L,) + x.shape[2:]), g_stack
    )
    return loss, (g_pre, g_stack, g_post)


def _split_progen_params(params):
    """ProGen scan_layers param tree -> (pre, stack, post) groups for the
    1F1B schedule (inverse: _join_progen_grads). The stacked 'layers'
    subtree is the pipeline; embed runs on stage 0; everything else —
    trailing gMLP blocks, final norm, logits head — runs in the last
    stage's fused loss head (all O(1) in depth)."""
    if "layers" not in params:
        raise ValueError(
            "1F1B needs the scan_layers stacked param layout "
            "(use models.progen.stack_params to convert)"
        )
    pre = {"embed": params["embed"]}
    stack = params["layers"]
    post = {k: v for k, v in params.items()
            if k not in ("embed", "layers")}
    return pre, stack, post


def _join_progen_grads(g_pre, g_stack, g_post):
    return {"embed": g_pre["embed"], "layers": g_stack, **g_post}


def make_1f1b_train_step(
    model,
    optimizer,
    *,
    mesh: Mesh,
    axis: str = "model",
    n_microbatches: int,
):
    """The production train step with forward AND backward scheduled by
    the 1F1B pipeline: same loss / accumulation / clip / masked-AdamW
    semantics as training/step.make_train_step (grads are exact — parity
    test-locked against the plain step), but a stage's live activations
    are bounded by 2*(stages-1) microbatch boundaries instead of GPipe's
    O(n_microbatches). ``config.remat`` additionally checkpoints each
    layer inside the stage recompute. Composes with data parallelism: on
    a mesh with ``data > 1`` each microbatch's rows are sharded over the
    data axis (every chip does 1/D of the work; grads psum over data)."""
    import optax
    from flax import linen as nn

    from progen_tpu.models.layers import (
        FeedForwardBlock,
        LocalAttentionBlock,
        ScaleNorm,
    )
    from progen_tpu.models.progen import UniformBlock
    from progen_tpu.ops.rotary import fixed_pos_embedding
    from progen_tpu.training.loss import cross_entropy

    c = model.config
    n_uniform = c.depth - c.global_mlp_depth
    sin, cos = fixed_pos_embedding(c.seq_len, c.dim_head)
    block = UniformBlock(c, glu=c.ff_glu)

    def fn_pre(pre, ids):
        return nn.Embed(
            c.num_tokens,
            c.dim,
            dtype=c.compute_dtype,
            param_dtype=c.params_dtype,
            name="embed",
        ).apply({"params": pre["embed"]}, ids)

    def block_fn(layer_params, h):
        h, _ = block.apply({"params": layer_params}, h, sin, cos)
        return h

    if c.remat:
        block_fn = jax.checkpoint(block_fn)

    def fn_loss(post, h, toks_mb):
        x = h
        for i in range(n_uniform, c.depth):
            use_gmlp = (c.depth - i) <= c.global_mlp_depth
            x = x + LocalAttentionBlock(c).apply(
                {"params": post[f"attn{i}"]}, x, sin, cos, None
            )
            x = x + FeedForwardBlock(
                c, glu=(not use_gmlp) and c.ff_glu, spatial_gate=use_gmlp
            ).apply({"params": post[f"ff{i}"]}, x, None)
        x = ScaleNorm(
            c.layer_norm_epsilon, c.compute_dtype, c.params_dtype
        ).apply({"params": post["ScaleNorm_0"]}, x)
        logits = nn.Dense(
            c.num_tokens,
            dtype=c.compute_dtype,
            param_dtype=c.params_dtype,
            name="to_logits",
        ).apply({"params": post["to_logits"]}, x)
        labels = toks_mb[..., 1:]
        return cross_entropy(logits.astype(jnp.float32), labels).mean()

    def train_step(state, batch):
        pre, stack, post = _split_progen_params(state.params)

        def micro(grads_acc, mb_rows):
            loss, (g_pre, g_stack, g_post) = pipeline_1f1b_loss_and_grads(
                fn_pre, block_fn, fn_loss, pre, stack, post, mb_rows,
                mesh=mesh, axis=axis, n_microbatches=n_microbatches,
            )
            grads = _join_progen_grads(g_pre, g_stack, g_post)
            grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
            return grads_acc, loss

        zero_grads = jax.tree.map(jnp.zeros_like, state.params)
        grads, losses = jax.lax.scan(micro, zero_grads, batch)
        grads = jax.tree.map(lambda g: g / batch.shape[0], grads)

        updates, opt_state = optimizer.update(
            grads, state.opt_state, state.params
        )
        params = optax.apply_updates(state.params, updates)
        new_state = state.replace(
            step=state.step + 1, params=params, opt_state=opt_state
        )
        metrics = {
            "loss": losses.mean(),
            "last_micro_loss": losses[-1],
            "grad_norm": optax.global_norm(grads),
        }
        return new_state, metrics

    return train_step


def compile_1f1b_train_step(
    model,
    optimizer,
    shardings,
    mesh: Mesh,
    *,
    axis: str = "model",
    n_microbatches: int,
):
    """jit ``make_1f1b_train_step`` with explicit state/batch shardings and
    a donated state — the 1F1B twin of
    ``parallel/pipeline.compile_pipeline_train_step`` (same PIPELINE_RULES
    state layout; only the schedule differs)."""
    from progen_tpu.parallel.partition import batch_sharding

    step = make_1f1b_train_step(
        model, optimizer, mesh=mesh, axis=axis,
        n_microbatches=n_microbatches,
    )
    return jax.jit(
        step,
        in_shardings=(shardings, batch_sharding(mesh, accum_axis=True)),
        out_shardings=(shardings, None),
        donate_argnums=(0,),
    )
