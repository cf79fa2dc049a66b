"""Training cells: one optimizer step after another through
``progen_tpu.training.step``, on the mesh the traffic file names, fed and
fenced the way ``cli.train`` does it (step N's loss is fetched after step
N+1 is dispatched, so one step is always in flight)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import traffic as traffic_mod
from benchmark.reference import progen_ref

# The system computes in bfloat16 (8 bits of mantissa) and the reference
# in float32. The loss is a mean of log-probabilities over a whole row, so
# roundings average out: measured on the chip (PR 24) the two differ by
# 0.00001-0.00042 on losses near 6.04 (long8k, 7 seeds) and by 0.0007-
# 0.0022 for large on four chips (5 seeds); a 64-token toy row on the CPU
# averages less and reads 0.0034. 0.01 is four times the largest measured, and
# far inside what a wrong mask, a dropped layer or a shifted label would
# cause (> 0.1).
LOSS_TOLERANCE = 0.01


def run(run) -> dict:
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen
    from progen_tpu.parallel.partition import make_mesh, put_batch
    from progen_tpu.training.optimizer import make_optimizer
    from progen_tpu.training.step import compile_train_step, init_train_state

    t, cfg = run.traffic, run.config
    config = ProGenConfig.from_dict(cfg)
    model = ProGen(config)
    mesh = make_mesh(devices=run.devices, **t["mesh"])
    optimizer = make_optimizer(
        t["learning_rate"], t["weight_decay"], t["max_grad_norm"]
    )
    state, shardings = init_train_state(
        model, optimizer, traffic_mod.fold_key(run.seed), config.seq_len, mesh=mesh,
        zero1=t["zero1"],
    )
    batches = traffic_mod.train_batches(t, config.seq_len, run.seed)
    tokens_per_step = int(np.prod(batches[0].shape[:2])) * config.seq_len
    if tokens_per_step != t["tokens_per_step"]:
        raise ValueError(
            f"traffic file states {t['tokens_per_step']} tokens a step, "
            f"its batch has {tokens_per_step}"
        )

    with mesh:
        step = compile_train_step(model, optimizer, state, shardings, mesh)
        # correctness, and the warm-up that compiles the one program: a
        # batch whose rows are all the same row, so the step's loss IS that
        # row's loss; the reference computes it first (the step donates
        # the parameters)
        row = batches[0][0, 0]
        ref = float(jax.jit(
            lambda p, r: progen_ref.row_loss(p, r, cfg)
        )(state.params, jnp.asarray(row)))
        same = np.broadcast_to(row, batches[0].shape)
        state, metrics = step(state, put_batch(same, mesh, accum_axis=True))
        got = float(metrics["loss"])
        check = {"reference_loss": ref, "system_loss": got,
                 "abs_err": abs(got - ref), "tolerance": LOSS_TOLERANCE}

        run.open_window()
        losses, pending, steps = [], None, 0
        while True:
            with run.span("train.feed"):
                batch = put_batch(batches[steps % len(batches)], mesh,
                                  accum_axis=True)
            with run.span("train.step"):
                state, metrics = step(state, batch)
            if pending is not None:
                with run.span("train.fence"):
                    losses.append(float(pending["loss"]))
            pending = metrics
            steps += 1
            if run.due():
                break
        with run.span("train.fence"):
            losses.append(float(pending["loss"]))
            jax.block_until_ready(state)
        run.close_window()

    finite = bool(np.isfinite(losses).all())
    check["losses_finite"] = finite
    check["first_losses"] = losses[:3]
    run.counters.update(
        steps=steps, tokens=steps * tokens_per_step,
        tokens_per_step=tokens_per_step,
        engine_compiles_in_window=step._cache_size() - 1,
    )
    return {
        "correct": check["abs_err"] <= LOSS_TOLERANCE and finite
        and step._cache_size() == 1,
        "attempted": steps, "failed": 0 if finite else int(
            (~np.isfinite(losses)).sum()),
        "check": check,
        "compared": {
            "first_loss_abs_err": [check["abs_err"], LOSS_TOLERANCE],
            "losses_not_finite": [int((~np.isfinite(losses)).sum()), 0],
            "compiles_in_window": [step._cache_size() - 1, 0],
        },
    }
