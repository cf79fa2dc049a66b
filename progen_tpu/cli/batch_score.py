"""Batch scoring CLI — bulk perplexity ranking of candidate sequences.

The protein-design screening workload (progen_tpu/workloads/scoring.py):
stream a FASTA file or a TFRecord split through the training data path,
score every sequence with the shared ``sequence_scores`` reduction, and
write sharded JSONL (per-sequence NLL/perplexity, optional per-token
logprobs) plus a progress journal. Killed mid-run, a re-run with
``--resume`` (the default) skips every durably written id and completes
the remainder — zero duplicates, zero lost work.

Run: python -m progen_tpu.cli.batch_score --checkpoint_path ./ckpts \
         --input candidates.fasta --out_dir ./scores
"""

from __future__ import annotations

from progen_tpu.utils.env import load_env_file

load_env_file()  # XLA/env flags before jax import (ref train.py:1-2)

import json
import os
import sys

import click


@click.command()
@click.option("--checkpoint_path", default="./ckpts")
@click.option("--input", "input_path", required=True,
              help="a FASTA file, or a TFRecord folder (see --split)")
@click.option("--split", default="valid",
              type=click.Choice(["train", "valid"]),
              help="which TFRecord split to score when --input is a folder")
@click.option("--context", default="",
              help="conditioning tag prepended to every FASTA sequence "
                   "(scored as 'context # SEQ', the annotation grammar)")
@click.option("--out_dir", default="./scores",
              help="output dir: scores-*.jsonl shards + score journal")
@click.option("--batch_size", default=8)
@click.option("--shard_size", default=512,
              help="output lines per shard before rotating")
@click.option("--logprobs/--no-logprobs", default=True,
              help="include per-token logprobs in each output record")
@click.option("--resume/--no-resume", default=True,
              help="skip ids already in the output shards (torn tails "
                   "from a kill are truncated first)")
@click.option("--max_batches", default=None, type=int,
              help="stop after N scored batches (deterministic partial "
                   "run for resume tests)")
@click.option("--prom_file", default=None, type=str,
              help="write Prometheus text exposition here "
                   "(progen_score_* families)")
@click.option("--metrics-every", default=0,
              help="rewrite --prom_file every N batches (0 = at end only)")
def main(checkpoint_path, input_path, split, context, out_dir, batch_size,
         shard_size, logprobs, resume, max_batches, prom_file,
         metrics_every):
    from progen_tpu import telemetry
    from progen_tpu.checkpoint import get_checkpoint_fns
    from progen_tpu.models import build_model, require_progen
    from progen_tpu.resilience.chaos import install_from_env
    from progen_tpu.telemetry import MetricsRegistry
    from progen_tpu.tracking import make_tracker
    from progen_tpu.workloads import (
        fasta_records,
        run_batch_score,
        tfrecord_records,
    )

    # the CI resume test drives this process with PROGEN_CHAOS alone
    # (score/batch:kill@N — SIGKILL after the Nth durable batch)
    install_from_env()

    _, get_last, _ = get_checkpoint_fns(checkpoint_path)
    pkg = get_last.restore_params()  # params only: no optimizer moments
    if pkg is None:
        sys.exit(f"no checkpoints found at {checkpoint_path}")
    model = require_progen(build_model(pkg.model_config), "cli.batch_score")

    if os.path.isdir(input_path):
        records = tfrecord_records(input_path, split)
    else:
        records = fasta_records(input_path, context)

    tracker = make_tracker("progen-batch-score")
    # journal records double as telemetry events (ev:"score" grammar,
    # analysis/rules_telemetry.py PGL006) — mirror them to the tracker
    telemetry.configure(sink=tracker.log_event)
    metrics = MetricsRegistry()
    try:
        summary = run_batch_score(
            model, pkg.state, records, out_dir,
            batch_size=batch_size, logprobs=logprobs,
            shard_size=shard_size, resume=resume,
            metrics=metrics, prom_file=prom_file,
            metrics_every=metrics_every, max_batches=max_batches,
        )
    finally:
        telemetry.configure()  # detach before the sink closes
        tracker.finish()
    print(json.dumps(summary))


if __name__ == "__main__":
    main()
