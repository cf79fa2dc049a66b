"""Each driver rehearsed on the CPU at a tiny size for a second, through
the harness's own ``execute``; and the command itself refusing to run
without a TPU. Nothing here is a measurement."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from benchmark import run as bm

ROOT = Path(__file__).resolve().parents[2]
TINY = dict(num_tokens=256, dim=64, depth=3, heads=2, dim_head=32,
            window_size=16, seq_len=64, global_mlp_depth=1, ff_mult=4,
            ff_glu=True, shift_tokens=True, rotate_value=True,
            layer_norm_epsilon=1e-5, sgu_block_size=0, dtype="bfloat16",
            param_dtype="float32", use_pallas_attn=False, remat=True,
            scan_layers=True)


def rehearse(cell_name, tmp_path, trace, chips=1, **traffic_over):
    manifest, cell, _, traffic = bm.load_cell(cell_name)
    traffic.update(trace_seconds=0.4, **traffic_over)
    line = bm.execute(manifest, cell, TINY, traffic, seed=2**31 + 11,
                      seconds=1.0, trace=trace, devices=jax.devices()[:chips],
                      out_dir=tmp_path / "out")
    detail = json.loads((tmp_path / "out" / "detail.json").read_text())
    return line, detail


def assert_contract_line(line, cell_name, trace):
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    json.dumps(line)  # serialisable as it stands
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(line)
    assert set(line["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["device"]["platform"] == "cpu"  # a rehearsal, never reported
    kind = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"] for m in manifest[kind]
               if cell_name in m.get("workloads", [cell_name])}
    for name, m in line["metrics"].items():
        assert m["unit"] == allowed[name] and isinstance(m["value"], float)
    if trace:
        assert {"busy_s", "window_s"} <= set(line["device"])
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(line["metrics"]) == set(allowed)  # every end-to-end metric
        assert all(m["value"] > 0 for m in line["metrics"].values())


GEN_TINY = dict(max_slots=4, max_len=64, clients=4, prompt_lengths=[4, 6, 8],
                output_lengths=[10, 14, 18], ramp_completions=2,
                prefill_chunk=4, check_positions=8)


def test_gen_driver_counts_every_token_and_compiles_nothing_in_the_window(tmp_path):
    line, detail = rehearse("large.gen-closed", tmp_path, 0, **GEN_TINY)
    assert_contract_line(line, "large.gen-closed", 0)
    c = detail["counters"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 4
    assert c["tokens"] == c["decode_tokens"] > 0
    assert c["engine_compiles_in_window"] == 0 and c["xla_compiles_in_window"] == 0
    assert c["window_s"] >= 1.0 and c["requests_completed"] > 0
    assert sum(detail["notes"]["itl_histogram"]["counts"]) <= c["tokens"]
    assert detail["check"]["positions"] == 8 and detail["check"]["ok"]
    # what the served yardsticks count from: positions seen by the window's
    # tokens (a token sees at least its prompt and BOS, at most the request),
    # the steps launched ahead, the prefill passes and their real rows
    assert 5 * c["tokens"] <= c["decode_context_sum"] <= 27 * c["tokens"]
    assert c["decode_window_rows_sum"] == c["decode_context_sum"]  # under 2 windows
    assert 0 < c["prefill_window_rows_sum"] <= c["prefill_context_sum"]
    assert 0 < c["decode_steps_ahead"] <= c["decode_steps"]
    assert c["prefill_blocks"] > 0 and 0 < c["prefill_real_row_share"] <= 1
    assert set(line["compared"]) == {
        "logits_rms_over_std", "logits_max_over_std", "wrong_output_lengths",
        "compiles_in_window"}
    assert all(v <= lim for v, lim in line["compared"].values())
    assert list(line)[-1] == "compared"  # the key comes last


def test_gen_driver_traced_reports_the_per_layer_metrics_it_can(tmp_path):
    line, detail = rehearse("large.gen-closed", tmp_path, 1, **GEN_TINY)
    assert_contract_line(line, "large.gen-closed", 1)
    got = set(line["metrics"])
    assert {"sched.occupancy", "sched.ttft_p50_s", "sched.host_ms_per_step",
            "engine.compiles_in_window", "sched.steps_ahead_share"} <= got
    assert 50 < line["metrics"]["sched.steps_ahead_share"]["value"] <= 100
    # a CPU has no peak: no share of one, whatever the counters say
    assert not {"serve.mfu", "engine.decode_roofline",
                "engine.prefill_roofline"} & got
    # no device in the trace on a CPU: those readers find nothing to read
    assert not {"device.idle_share", "engine.decode_device_ms",
                "engine.prefill_ms_per_tok"} & got
    assert 0 < line["metrics"]["sched.occupancy"]["value"] <= 100
    spans = detail["span_count"]
    assert spans["sched.step"] == spans["engine.decode_step"] > 0
    assert spans["journal"] > spans["sched.step"]  # a line per token


def bucketed(real):
    """A scorer stub with two buckets, as bucketed scoring would have:
    short and long records fill batches of their own through the real
    scorer, the two ragged tails are flushed last, and the output is
    written group by group — so batches are not all full and the
    stream's first record stands wherever its group put it."""
    def run_batch_score(model, params, records, out_dir, *, batch_size, **kw):
        os.makedirs(out_dir, exist_ok=True)
        total = {"tokens": 0, "batches": 0, "n_scored": 0, "n_skipped": 0,
                 "times": {"step": 0.0, "compile": 0.0, "write": 0.0, "data": 0.0}}
        lines, groups, n_calls = {False: [], True: []}, {False: [], True: []}, 0

        def flush(long):
            nonlocal n_calls
            part = os.path.join(out_dir, f"part{n_calls}")
            n_calls += 1
            summary = real(model, params, iter(groups[long]), part,
                           batch_size=batch_size, **kw)
            groups[long] = []
            for shard in sorted(Path(part).glob("scores-*.jsonl")):
                lines[long] += shard.read_text().splitlines()
            for key in ("tokens", "batches", "n_scored", "n_skipped"):
                total[key] += summary[key]
            for key in total["times"]:
                total["times"][key] += summary["times"][key]

        for rid, raw in records:
            long = len(raw) > 35
            groups[long].append((rid, raw))
            if len(groups[long]) == batch_size:
                flush(long)
        for long in (True, False):  # the ragged tails, the long ones first
            if groups[long]:
                flush(long)
        Path(out_dir, "scores-00000.jsonl").write_text(
            "".join(line + "\n" for line in lines[True] + lines[False]))
        return total

    return run_batch_score


@pytest.mark.parametrize("scorer", ["todays", "two_ragged_groups"])
def test_score_driver_counts_real_tokens_only(tmp_path, monkeypatch, scorer):
    from progen_tpu.workloads import scoring

    if scorer == "two_ragged_groups":
        monkeypatch.setattr(scoring, "run_batch_score",
                            bucketed(scoring.run_batch_score))
    line, detail = rehearse("large.score-batch", tmp_path, 0, batch_size=4,
                            lengths=[10, 20, 30, 40, 50, 60])
    assert_contract_line(line, "large.score-batch", 0)
    c = detail["counters"]
    assert line["correct"] and line["failed"] == 0
    # every record once, whatever the batches: full ones from today's
    # scorer, ragged tails allowed from one with buckets
    assert c["records"] == line["attempted"] == c["records_written"] > 0
    assert c["records_amiss"] == 0 and c["records"] % 4 == 0  # whole batches fed
    assert c["records"] <= c["batches"] * 4
    if scorer == "todays":
        assert c["records"] == c["batches"] * 4
    else:
        assert c["batches"] * 4 - c["records"] <= 2 * 3  # two tails at most
    # 35 bytes a record on average plus its EOS; padding to 64 is not counted
    assert 30 * c["records"] < c["tokens"] < 42 * c["records"]
    assert c["engine_compiles_in_window"] == 0
    assert detail["check"]["ok"] and detail["check"]["record"] == "r0"
    assert line["compared"]["records_missing_or_twice"] == [0, 0]


def test_train_driver_counts_whole_steps(tmp_path):
    line, detail = rehearse("long8k.train", tmp_path, 1, micro_batch=2,
                            tokens_per_step=2 * 2 * 64)
    assert_contract_line(line, "long8k.train", 1)
    c = detail["counters"]
    assert line["correct"] and c["tokens"] == c["steps"] * 256 > 0
    assert line["attempted"] == c["steps"]
    assert detail["check"]["abs_err"] < detail["check"]["tolerance"]
    assert "train.step_ms" in line["metrics"]
    assert "train.mfu" not in line["metrics"]  # a CPU has no peak


def test_train_driver_on_a_data_2_by_model_2_mesh(tmp_path):
    line, detail = rehearse("large.train-dp2tp2", tmp_path, 0, chips=4,
                            micro_batch=2, tokens_per_step=2 * 4 * 64)
    assert_contract_line(line, "large.train-dp2tp2", 0)
    assert line["correct"] and line["device"]["count"] == 4
    assert detail["counters"]["chips"] == 4


def test_the_command_refuses_to_run_without_a_tpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": str(ROOT)}
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "long8k.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode not in (0, None)
    assert p.stdout.strip() == ""  # no result line, no metric
    assert "refused" in p.stderr


def test_the_command_fails_where_the_program_is_absent(tmp_path):
    import shutil

    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    p = subprocess.run(
        [sys.executable, "-m", "benchmark.run", "--workload", "long8k.train"],
        cwd=tmp_path, env={**env, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
