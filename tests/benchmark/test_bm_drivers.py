"""Each driver rehearsed on the CPU at a tiny size for a second, through
the harness's own ``execute``; and the command itself refusing to run
without a TPU. Nothing here is a measurement."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax

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


def test_gen_driver_traced_reports_the_per_layer_metrics_it_can(tmp_path):
    line, detail = rehearse("large.gen-closed", tmp_path, 1, **GEN_TINY)
    assert_contract_line(line, "large.gen-closed", 1)
    got = set(line["metrics"])
    assert {"sched.occupancy", "sched.ttft_p50_s", "sched.host_ms_per_step",
            "engine.compiles_in_window"} <= got
    # no device in the trace on a CPU: those readers find nothing to read
    assert not {"device.idle_share", "engine.decode_device_ms",
                "engine.prefill_ms_per_tok"} & got
    assert 0 < line["metrics"]["sched.occupancy"]["value"] <= 100
    spans = detail["span_count"]
    assert spans["sched.step"] == spans["engine.decode_step"] > 0
    assert spans["journal"] > spans["sched.step"]  # a line per token


def test_score_driver_counts_real_tokens_only(tmp_path):
    line, detail = rehearse("large.score-batch", tmp_path, 0, batch_size=4,
                            lengths=[10, 20, 30, 40, 50, 60])
    assert_contract_line(line, "large.score-batch", 0)
    c = detail["counters"]
    assert line["correct"] and line["failed"] == 0
    assert c["records"] == c["batches"] * 4 == line["attempted"]
    # 35 bytes a record on average plus its EOS; padding to 64 is not counted
    assert 30 * c["records"] < c["tokens"] < 42 * c["records"]
    assert c["engine_compiles_in_window"] == 0
    assert detail["check"]["ok"]


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
