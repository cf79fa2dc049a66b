"""The fused, donated, mesh-sharded train step.

Capability target (/root/reference/progen_transformer/utils.py:61-93 +
/root/reference/train.py:113-121,179-222): per-sequence EOS-masked cross
entropy averaged over the batch, gradient accumulation, global-norm clip,
masked AdamW.

TPU-first design, where the reference differs:
  * ONE jitted step per optimizer update: `lax.scan` over micro-batches
    accumulates gradients on-device (the reference runs a separate
    jit+host-optimizer round trip per micro-step, train.py:185-190).
  * The TrainState is donated — params/opt-state never leave the device, and
    under pjit the GSPMD partitioner inserts the gradient reductions over
    the mesh's ``data`` axis (the reference relies on the implicit transpose
    of pmap's broadcast, utils.py:70-91).
  * Batch layout is (grad_accum, micro_batch, seq_len+1), micro-batch dim
    sharded over ``data``; the [:-1]/[1:] input/label shift happens inside
    the step (utils.py:63).
"""

from __future__ import annotations

from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import linen as nn

from progen_tpu.parallel.partition import (
    DEFAULT_RULES,
    batch_sharding,
    state_shardings,
)
from progen_tpu.training.loss import cross_entropy
from progen_tpu.training.state import TrainState

Metrics = dict


def batch_loss(model, params, data: jnp.ndarray, forward_fn=None) -> jnp.ndarray:
    """data: (mb, seq_len+1) int tokens. Mean over per-sequence masked CE
    (matches vmap-then-mean of utils.py:67,77). ``forward_fn(params, ids)
    -> logits`` overrides the plain ``model.apply`` (e.g. the pipelined
    forward, parallel/pipeline.make_pipeline_train_step)."""
    with jax.named_scope("head"):
        ids, labels = data[..., :-1], data[..., 1:]
    if forward_fn is None:
        logits = model.apply({"params": params}, ids)
    else:
        logits = forward_fn(params, ids)
    with jax.named_scope("head"):
        return cross_entropy(logits, labels).mean()


def make_train_step(
    model, optimizer, rules=DEFAULT_RULES, *, forward_fn=None
) -> Callable[[TrainState, jnp.ndarray], Tuple[TrainState, Metrics]]:
    """Returns train_step(state, batch) -> (state, metrics).

    batch: (grad_accum, micro_batch, seq_len+1) ints. Gradients are averaged
    over the accumulation axis *before* clipping (see optimizer.py for why
    this deliberately differs from the reference's apply_every placement).

    ``forward_fn`` swaps the model forward while keeping the loss /
    accumulation / clip / AdamW machinery identical (pipeline path passes
    ``rules=()`` — explicit shard_map sharding instead of GSPMD
    annotations, which cannot apply inside manual axes).
    """

    def train_step(state: TrainState, batch: jnp.ndarray):
        # the model files its forward and backward under its mechanism
        # classes; what the step adds around them — gradient
        # accumulation, the update, the finite gate — is ``optimizer``
        with nn.logical_axis_rules(rules):
            grad_fn = jax.value_and_grad(
                lambda p, mb: batch_loss(model, p, mb, forward_fn)
            )

            def micro(grads_acc, mb):
                loss, grads = grad_fn(state.params, mb)
                with jax.named_scope("optimizer"):
                    grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
                return grads_acc, loss

            with jax.named_scope("optimizer"):
                zero_grads = jax.tree.map(jnp.zeros_like, state.params)
            grads, losses = jax.lax.scan(micro, zero_grads, batch)
            with jax.named_scope("optimizer"):
                grads = jax.tree.map(lambda g: g / batch.shape[0], grads)
                updates, opt_state = optimizer.update(
                    grads, state.opt_state, state.params
                )
                params = optax.apply_updates(state.params, updates)

                # finite gate: the state is DONATED, so a poisoned update
                # can never be undone host-side — refuse it on-device
                # instead. When any micro-loss or the grad norm is
                # non-finite the step re-emits the incoming state (step
                # counter included), and the anomaly sentinel
                # (resilience/anomaly.py) sees the bad metrics and decides
                # skip vs rollback.
                grad_norm = optax.global_norm(grads)
                ok = jnp.isfinite(losses).all() & jnp.isfinite(grad_norm)
                gate = lambda new, old: jnp.where(ok, new, old)
                params = jax.tree.map(gate, params, state.params)
                opt_state = jax.tree.map(gate, opt_state, state.opt_state)
                # step still advances on a refusal — the batch was
                # consumed, and the data cursor must agree with the step
                # count on resume
                new_state = state.replace(
                    step=state.step + 1, params=params, opt_state=opt_state
                )
                metrics = {
                    "loss": losses.mean(),
                    "last_micro_loss": losses[-1],
                    "grad_norm": grad_norm,
                    "skipped": (~ok).astype(jnp.int32),
                }
            return new_state, metrics

    return train_step


def make_eval_step(model, rules=DEFAULT_RULES):
    """eval_step(state, data(mb, L+1)) -> scalar loss. Unlike the reference
    (which re-runs the grad fn and discards gradients, train.py:209), this is
    a forward-only program."""

    def eval_step(state: TrainState, data: jnp.ndarray):
        with nn.logical_axis_rules(rules):
            return batch_loss(model, state.params, data)

    return eval_step


def _boxed_init_fn(model, optimizer, seq_len):
    def init_fn(rng):
        dummy = jnp.zeros((1, seq_len), jnp.int32)
        variables = model.init(rng, dummy)
        return TrainState.create(variables["params"], optimizer)

    return init_fn


def abstract_train_state(model, optimizer, seq_len: int) -> Tuple[Any, Any]:
    """(boxed, unboxed) abstract TrainState pytrees. The boxed one carries
    the flax Partitioned metadata (feed to partition.state_shardings); the
    unboxed one is the plain-array template matching the live state (feed to
    checkpoint restore)."""
    from flax.core import meta

    boxed = jax.eval_shape(
        _boxed_init_fn(model, optimizer, seq_len), jax.random.PRNGKey(0)
    )
    return boxed, meta.unbox(boxed)


def train_state_shardings(boxed_abstract, mesh, rules=DEFAULT_RULES,
                          zero1: bool = False):
    """The ONE place a TrainState's shardings tree is built (cold init and
    checkpoint resume must agree on the layout): base logical-rule
    shardings, with the ZeRO-1 moment upgrade applied when asked."""
    shardings = state_shardings(boxed_abstract, mesh, rules)
    if zero1:
        from progen_tpu.parallel.partition import zero1_opt_shardings

        shardings = shardings.replace(
            opt_state=zero1_opt_shardings(
                boxed_abstract.opt_state, shardings.opt_state, mesh
            )
        )
    return shardings


def init_train_state(
    model,
    optimizer,
    rng: jax.Array,
    seq_len: int,
    mesh=None,
    rules=DEFAULT_RULES,
    zero1: bool = False,
) -> Tuple[TrainState, Any]:
    """Initialize a TrainState of PLAIN arrays (flax Partitioned boxes are
    stripped — sharding metadata lives in the returned shardings tree, not
    in the state, so optax/orbax/donation see ordinary pytrees). With a
    mesh, every leaf is created directly into its NamedSharding via jit
    out_shardings — the full model never materializes on one host.

    ``zero1`` additionally shards the optimizer moments over the ``data``
    axis (parallel/partition.zero1_opt_shardings); params keep their base
    layout, so every compiled step/eval/decode fn is unchanged except for
    the shardings tree it is given.

    Returns (state, shardings); shardings is None without a mesh.
    """
    from flax.core import meta

    init_fn = _boxed_init_fn(model, optimizer, seq_len)

    def init_unboxed(rng):
        return meta.unbox(init_fn(rng))

    if mesh is None:
        return jax.jit(init_unboxed)(rng), None

    abstract = jax.eval_shape(init_fn, rng)
    shardings = train_state_shardings(abstract, mesh, rules, zero1=zero1)
    with mesh:
        state = jax.jit(init_unboxed, out_shardings=shardings)(rng)
    return state, shardings


def compile_train_step(
    model,
    optimizer,
    state: TrainState,
    shardings,
    mesh,
    rules=DEFAULT_RULES,
):
    """jit the train step with explicit state/batch shardings and a donated
    state argument. Returns the compiled-on-first-call step fn; call it
    inside ``with mesh`` (or rely on the shardings carrying the mesh)."""
    step = make_train_step(model, optimizer, rules)
    return jax.jit(
        step,
        in_shardings=(shardings, batch_sharding(mesh, accum_axis=True)),
        out_shardings=(shardings, None),
        donate_argnums=(0,),
    )


def compile_eval_step(model, shardings, mesh, rules=DEFAULT_RULES):
    """jit the forward-only eval step with the same state shardings."""
    step = make_eval_step(model, rules)
    return jax.jit(
        step,
        in_shardings=(shardings, batch_sharding(mesh)),
        out_shardings=None,
    )
