"""Evaluation CLI — mean EOS-masked loss + perplexity over a data split.

The reference has no offline eval entry point (its only validation is the
in-loop cadence, /root/reference/train.py:207-211); this evaluates the
latest checkpoint over a whole ``train``/``valid`` split in one pass with
the exact training loss semantics (per-sequence masked mean,
progen_tpu/training/loss.py) and reports the mean and ``exp(mean)``
perplexity.

Run: python -m progen_tpu.cli.eval --checkpoint_path ./ckpts \
         --data_path ./train_data --split valid
"""

from __future__ import annotations

from progen_tpu.utils.env import load_env_file

load_env_file()  # XLA/env flags before jax import (ref train.py:1-2)

import sys

import click
import numpy as np

import jax


@click.command()
@click.option("--checkpoint_path", default="./ckpts")
@click.option("--data_path", default="./train_data")
@click.option("--split", default="valid",
              type=click.Choice(["train", "valid"]))
@click.option("--batch_size", default=8)
def main(checkpoint_path, data_path, split, batch_size):
    from progen_tpu.checkpoint import get_checkpoint_fns
    from progen_tpu.data.dataset import iterator_from_tfrecords_folder
    from progen_tpu.models import build_model, require_progen
    from progen_tpu.training.loss import sequence_scores

    _, get_last, _ = get_checkpoint_fns(checkpoint_path)
    pkg = get_last.restore_params()  # params only: no optimizer moments
    if pkg is None:
        sys.exit(f"no checkpoints found at {checkpoint_path}")
    model = require_progen(build_model(pkg.model_config), "cli.eval")
    config = model.config
    params = pkg.state

    num_seqs, iter_fn = iterator_from_tfrecords_folder(data_path, split)
    if num_seqs == 0:
        sys.exit(f"no {split} records under {data_path}")

    @jax.jit
    def per_seq_loss(params, data):
        ids, labels = data[..., :-1], data[..., 1:]
        # the shared scorer (training/loss.py): eval and the batch-score
        # workload reduce the same per-token logprobs, bit-for-bit
        logits = model.apply({"params": params}, ids)
        return sequence_scores(logits, labels)[0]  # (batch,)

    losses = []
    # loop=False walks the split exactly once; the final ragged batch is
    # padded to the static batch shape (one recompile avoided) and the pad
    # rows sliced off the result
    for batch in iter_fn(config.seq_len, batch_size):
        n = batch.shape[0]
        if n < batch_size:
            batch = np.pad(batch, ((0, batch_size - n), (0, 0)))
        losses.append(np.asarray(per_seq_loss(params, batch))[:n])
    per_seq = np.concatenate(losses)
    assert per_seq.shape[0] == num_seqs
    mean = float(per_seq.mean())
    print(f"{split} sequences: {num_seqs:,}")
    print(f"loss: {mean:.4f}")
    print(f"perplexity: {float(np.exp(mean)):.4f}")


if __name__ == "__main__":
    main()
