"""Scoring cells: ``workloads.scoring.run_batch_score`` over a seeded
stream of records — the offline ranking job, prefill only, no scheduler
and no decode program."""

from __future__ import annotations

import glob
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import stats
from benchmark import traffic as traffic_mod
from benchmark.reference import progen_ref

# Per-token log-probabilities of one record, system (bfloat16 compute)
# against the float32 reference, judged like the generation cell's logits:
# root-mean-square and largest error over the record, as shares of the
# standard deviation of the reference's log-probabilities. Measured on the
# chip (PR 24): RMS 4.0-4.5%, largest 12-15%; the tolerances are about
# twice that.
RMS_TOLERANCE = 0.08
MAX_TOLERANCE = 0.35


def written(out_dir: str) -> list:
    """Every record the scorer wrote under ``out_dir``, shard by shard."""
    return [json.loads(line)
            for shard in sorted(glob.glob(out_dir + "/scores-*.jsonl"))
            for line in Path(shard).read_text().splitlines()]


def run(run) -> dict:
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen
    from progen_tpu.workloads import scoring

    t, cfg = run.traffic, run.config
    config = ProGenConfig.from_dict(cfg)
    model = ProGen(config)
    params = traffic_mod.seeded_params(model, config.seq_len, run.seed, run.devices[0])
    records = traffic_mod.score_records(t, run.seed)
    kwargs = dict(batch_size=t["batch_size"], logprobs=t["logprobs"],
                  shard_size=t["shard_size"], resume=False)

    # warm-up: one batch through the real entry point compiles the one
    # program; the stream's first record is also the correctness sample,
    # looked up by its id in whatever shard and line the scorer wrote it
    warm = [next(records) for _ in range(t["batch_size"])]
    warm_dir = str(run.tmp / "warm")
    scoring.run_batch_score(model, params, iter(warm), warm_dir, **kwargs)
    first = next((r for r in written(warm_dir) if r["id"] == warm[0][0]), None)
    # the reference sees the row as the scorer pads it (BOS, bytes + 1,
    # zeros to seq_len): one shape for every seed, so one cached program
    raw = np.frombuffer(warm[0][1], dtype=np.uint8).astype(np.int32) + 1
    row = np.zeros((config.seq_len + 1,), np.int32)
    row[1:1 + len(raw)] = raw
    got = np.asarray(first["logprobs"] if first else [], np.float32)
    want = np.asarray(jax.jit(
        lambda p, r: progen_ref.token_logprobs(p, r, cfg)
    )(params, jnp.asarray(row)))[: len(raw) + 1]  # the bytes and the EOS
    check = {"record": warm[0][0], "positions": int(len(got)), "ok": False,
             "tolerances": [RMS_TOLERANCE, MAX_TOLERANCE]}
    if len(got) == len(want):
        check.update(stats.relative_errors(got, want))
        check["ok"] = (check["rms_err_over_std"] <= RMS_TOLERANCE
                       and check["max_err_over_std"] <= MAX_TOLERANCE)

    step_fn = scoring.score_step  # the jitted program, before any wrap
    if run.tracing:
        run.wrap(scoring, "score_step", "score.step")
        run.wrap(scoring._ShardWriter, "write", "score.write")
        run.wrap(scoring._ShardWriter, "flush", "score.write")

    def timed(stream):
        """Whole batches until the window is due."""
        n = 0
        for rec in stream:
            if n % t["batch_size"] == 0 and run.due():
                return
            n += 1
            yield rec

    compiled = step_fn._cache_size()
    run.open_window()
    summary = scoring.run_batch_score(
        model, params, timed(records), str(run.tmp / "out"), **kwargs
    )
    run.close_window()
    # every record the window scored is in the output once, whatever the
    # order and the batches the scorer chose
    ids = [r["id"] for r in written(str(run.tmp / "out"))]
    amiss = abs(len(ids) - summary["n_scored"]) + len(ids) - len(set(ids))
    run.counters.update(
        tokens=summary["tokens"], batches=summary["batches"],
        records=summary["n_scored"], records_written=len(ids),
        records_amiss=amiss,
        # a fresh run_batch_score bills its first batch to "compile" even
        # when the program is cached; it is a step like the others
        step_time_s=summary["times"]["step"] + summary["times"]["compile"],
        write_time_s=summary["times"]["write"],
        data_time_s=summary["times"]["data"],
        engine_compiles_in_window=step_fn._cache_size() - compiled,
    )
    return {
        "correct": check["ok"] and amiss == 0
        and run.counters["engine_compiles_in_window"] == 0,
        "attempted": summary["n_scored"] + summary["n_skipped"],
        "failed": summary["n_skipped"],
        "check": check,
        "compared": {
            "logprobs_rms_over_std": [check.get("rms_err_over_std"), RMS_TOLERANCE],
            "logprobs_max_over_std": [check.get("max_err_over_std"), MAX_TOLERANCE],
            "positions_missing": [len(want) - len(got), 0],
            "records_missing_or_twice": [amiss, 0],
            "compiles_in_window": [run.counters["engine_compiles_in_window"], 0],
        },
    }
