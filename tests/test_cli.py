"""CLI-level tests via click's CliRunner: the full ETL -> train -> resume
-> sample loop inside the suite (tiny config, a few seconds per stage)."""

import random

import numpy as np
import pytest
from click.testing import CliRunner

TOML = """num_tokens = 256
dim = 32
depth = 2
heads = 2
dim_head = 16
window_size = 8
seq_len = 32
global_mlp_depth = 1
ff_mult = 2
dtype = "float32"
"""

DATA_TOML = """read_from = "{fasta}"
write_to = "{out}"
num_samples = 30
max_seq_len = 28
prob_invert_seq_annotation = 0.5
fraction_valid_data = 0.2
num_sequences_per_file = 50
sort_annotations = true
"""


@pytest.fixture(scope="module")
def workspace(tmp_path_factory):
    root = tmp_path_factory.mktemp("cli")
    (root / "configs" / "model").mkdir(parents=True)
    (root / "configs" / "data").mkdir(parents=True)
    (root / "configs" / "model" / "default.toml").write_text(TOML)

    rng = random.Random(0)
    aas = "ACDEFGHIKLMNPQRSTVWY"
    fasta = root / "toy.fasta"
    with fasta.open("w") as f:
        for i in range(40):
            tax = rng.choice(["Homo sapiens", "Acinetobacter"])
            seq = "".join(rng.choice(aas) for _ in range(rng.randint(8, 24)))
            f.write(f">U{i:03d} toy n=1 Tax={tax} TaxID=1 RepID=T\n{seq}\n")
    (root / "configs" / "data" / "default.toml").write_text(
        DATA_TOML.format(fasta=fasta, out=root / "train_data")
    )
    # build train_data here so every test in this module is runnable in
    # isolation (no ordering dependency on test_full_cli_loop's ETL run —
    # that test still exercises the CLI ETL itself, idempotently)
    from progen_tpu.cli.generate_data import main as gen_main

    res = CliRunner().invoke(
        gen_main, ["--data_dir", str(root / "configs" / "data")]
    )
    assert res.exit_code == 0, res.output
    return root


def test_full_cli_loop(workspace, monkeypatch):
    monkeypatch.chdir(workspace)
    runner = CliRunner()

    from progen_tpu.cli.generate_data import main as gen_main

    res = runner.invoke(
        gen_main, ["--data_dir", str(workspace / "configs" / "data")]
    )
    assert res.exit_code == 0, res.output
    assert "tfrecord shard" in res.output

    from progen_tpu.cli.train import main as train_main

    args = [
        "--wandb_off", "--batch_size", "4", "--grad_accum_every", "1",
        "--num_steps", "2", "--validate_every", "1", "--sample_every", "100",
        "--checkpoint_every", "100", "--seq_len", "32",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(workspace / "ckpts"),
    ]
    res = runner.invoke(train_main, args)
    assert res.exit_code == 0, res.output
    assert "loss:" in res.output and "valid_loss:" in res.output

    # resume: config comes from the checkpoint, training continues
    res = runner.invoke(train_main, args)
    assert res.exit_code == 0, res.output

    from progen_tpu.cli.sample import main as sample_main

    res = runner.invoke(
        sample_main,
        ["--checkpoint_path", str(workspace / "ckpts"), "--prime",
         "[tax=Homo sapiens] #", "--top_k", "5"],
    )
    assert res.exit_code == 0, res.output
    assert "params:" in res.output and "*" * 40 in res.output


def test_train_missing_config_errors(workspace, tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    from progen_tpu.cli.train import main as train_main

    res = CliRunner().invoke(
        train_main, ["--config_path", str(tmp_path / "nope")]
    )
    assert res.exit_code != 0


def test_combined_features_loop(workspace, monkeypatch):
    """All round-3 features in ONE run — ring attention over a seq-sharded
    mesh, cosine LR schedule, multi-epoch, async checkpointing, KV-cache
    cadenced sampling — then a flagless resume that reconstructs the
    scheduled optimizer and mesh-independent state from the checkpoint."""
    monkeypatch.chdir(workspace)
    runner = CliRunner()

    from progen_tpu.cli.train import main as train_main

    ckpts = workspace / "ckpts_combined"
    args = [
        "--wandb_off", "--batch_size", "4", "--grad_accum_every", "1",
        "--epochs", "2", "--num_steps", "3",
        "--lr_schedule", "cosine", "--warmup_steps", "1",
        "--mesh_data", "2", "--mesh_seq", "2", "--ring_attn",
        "--async_checkpoint",
        "--validate_every", "1000", "--sample_every", "2",
        "--checkpoint_every", "1000", "--seq_len", "32",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(ckpts),
    ]
    res = runner.invoke(train_main, args)
    assert res.exit_code == 0, res.output
    assert "loss:" in res.output and "sample:" in res.output

    # flagless resume: schedule + config come from the checkpoint
    res = runner.invoke(train_main, [
        "--wandb_off", "--batch_size", "4", "--grad_accum_every", "1",
        "--num_steps", "1", "--validate_every", "1000",
        "--sample_every", "1000", "--checkpoint_every", "1000",
        "--seq_len", "32",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(ckpts),
    ])
    assert res.exit_code == 0, res.output
    assert "loss:" in res.output


PIPE_TOML = """num_tokens = 256
dim = 32
depth = 5
heads = 2
dim_head = 16
window_size = 8
seq_len = 32
global_mlp_depth = 1
ff_mult = 2
dtype = "float32"
scan_layers = true
"""


def test_pipeline_cli_loop(workspace, monkeypatch):
    """--mesh_pipe: the GPipe depth-sharded train path end-to-end on the
    8-virtual-device mesh (4 stages x 2 data), with validation, cadenced
    sampling off the stacked params, and a flagless pipelined resume."""
    monkeypatch.chdir(workspace)
    runner = CliRunner()

    from progen_tpu.cli.train import main as train_main

    (workspace / "configs" / "model" / "pipe.toml").write_text(PIPE_TOML)
    ckpts = workspace / "ckpts_pipe"
    args = [
        "--wandb_off", "--batch_size", "4", "--grad_accum_every", "1",
        "--num_steps", "2", "--mesh_pipe", "4", "--mesh_data", "2",
        "--pipe_microbatches", "2",
        "--model_name", "pipe",
        "--validate_every", "1", "--sample_every", "2",
        "--checkpoint_every", "1000", "--seq_len", "32",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(ckpts),
    ]
    res = runner.invoke(train_main, args)
    assert res.exit_code == 0, res.output
    assert "loss:" in res.output and "valid_loss:" in res.output

    # pipelined resume restores the sharded state into the PIPELINE_RULES
    # layout (stacked layer axis over the stage axis)
    res = runner.invoke(train_main, args[:5] + ["--num_steps", "1"]
                        + args[7:])
    assert res.exit_code == 0, res.output
    assert "loss:" in res.output

    # regression: the sample CLI's params-only restore must accept a
    # checkpoint WRITTEN from a mesh-sharded train state (train on a pod,
    # sample on one host) — orbax refuses a None-sharding skeleton there
    from progen_tpu.cli.sample import main as sample_main

    res = runner.invoke(
        sample_main,
        ["--checkpoint_path", str(ckpts), "--prime",
         "[tax=Homo sapiens] #", "--top_k", "5"],
    )
    assert res.exit_code == 0, res.output
    assert "params:" in res.output


def test_pipeline_cli_1f1b(workspace, monkeypatch):
    """--pipe_schedule 1f1b composed with DP and ZeRO-1: the interleaved
    schedule end-to-end from the CLI (2 stages x 2 data, 2 microbatches,
    AdamW moments additionally sharded over the data axis)."""
    monkeypatch.chdir(workspace)
    runner = CliRunner()

    from progen_tpu.cli.train import main as train_main

    (workspace / "configs" / "model" / "pipe.toml").write_text(PIPE_TOML)
    res = runner.invoke(train_main, [
        "--wandb_off", "--batch_size", "4", "--grad_accum_every", "1",
        "--num_steps", "2", "--mesh_pipe", "2", "--mesh_data", "2",
        "--pipe_microbatches", "2", "--pipe_schedule", "1f1b", "--zero1",
        "--model_name", "pipe",
        "--validate_every", "1", "--sample_every", "1000",
        "--checkpoint_every", "1000", "--seq_len", "32",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(workspace / "ckpts_pipe_1f1b"),
    ])
    assert res.exit_code == 0, res.output
    assert "loss:" in res.output and "valid_loss:" in res.output

    # row-divisibility guard: 4-row batch / 4 microbatches = 1 row per
    # microbatch, not shardable over data=2
    res = runner.invoke(train_main, [
        "--wandb_off", "--batch_size", "4", "--mesh_pipe", "2",
        "--mesh_data", "2", "--pipe_microbatches", "4",
        "--model_name", "pipe",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(workspace / "ckpts_pipe_1f1b_guard"),
    ])
    assert res.exit_code != 0
    assert "PPxDP" in res.output


def test_pipeline_cli_guards(workspace, monkeypatch):
    monkeypatch.chdir(workspace)
    runner = CliRunner()

    from progen_tpu.cli.train import main as train_main

    # default.toml has no scan_layers: the stage axis needs the stacked
    # param layout, so the flag must refuse with a pointed message
    res = runner.invoke(train_main, [
        "--wandb_off", "--mesh_pipe", "2",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(workspace / "ckpts_pipe_guard"),
    ])
    assert res.exit_code != 0
    assert "scan_layers" in res.output

    res = runner.invoke(train_main, [
        "--wandb_off", "--mesh_pipe", "2", "--mesh_model", "2",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(workspace / "ckpts_pipe_guard"),
    ])
    assert res.exit_code != 0
    assert "mutually exclusive" in res.output


def test_eval_cli(workspace, monkeypatch):
    """Offline eval: mean per-sequence loss + perplexity over a split from
    the latest checkpoint (uses the checkpoints the train test wrote)."""
    monkeypatch.chdir(workspace)
    runner = CliRunner()

    from progen_tpu.cli.eval import main as eval_main

    if not (workspace / "ckpts").exists():  # standalone-selection safety
        from progen_tpu.cli.generate_data import main as gen_main
        from progen_tpu.cli.train import main as train_main

        if not (workspace / "train_data").exists():
            res = runner.invoke(
                gen_main, ["--data_dir", str(workspace / "configs" / "data")]
            )
            assert res.exit_code == 0, res.output

        res = runner.invoke(train_main, [
            "--wandb_off", "--batch_size", "4", "--grad_accum_every", "1",
            "--num_steps", "1", "--validate_every", "1000",
            "--sample_every", "1000", "--checkpoint_every", "1000",
            "--seq_len", "32",
            "--config_path", str(workspace / "configs" / "model"),
            "--data_path", str(workspace / "train_data"),
            "--checkpoint_path", str(workspace / "ckpts"),
        ])
        assert res.exit_code == 0, res.output

    res = runner.invoke(eval_main, [
        "--checkpoint_path", str(workspace / "ckpts"),
        "--data_path", str(workspace / "train_data"),
        "--split", "valid", "--batch_size", "4",
    ])
    assert res.exit_code == 0, res.output
    assert "perplexity:" in res.output
    loss = float(res.output.split("loss: ")[1].split()[0])
    ppl = float(res.output.split("perplexity: ")[1].split()[0])
    np.testing.assert_allclose(ppl, np.exp(loss), rtol=1e-4)


def test_train_telemetry_events(workspace, monkeypatch):
    """Acceptance for the telemetry layer: a CPU train run through the
    real CLI (JsonlTracker, not --wandb_off) leaves an events.jsonl span
    trail and a goodput record whose buckets sum to wall clock with
    >=95% attributed."""
    import json
    import sys

    monkeypatch.chdir(workspace)
    # force the JsonlTracker path deterministically: wandb unimportable
    monkeypatch.setitem(sys.modules, "wandb", None)
    runner = CliRunner()

    from progen_tpu.cli.train import main as train_main

    res = runner.invoke(train_main, [
        "--batch_size", "4", "--grad_accum_every", "1",
        "--num_steps", "2", "--validate_every", "1", "--sample_every", "100",
        "--checkpoint_every", "1", "--seq_len", "32",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(workspace / "ckpts_telemetry"),
    ])
    assert res.exit_code == 0, res.output
    assert "goodput:" in res.output
    assert "step " in res.output  # step-stamped lines, not bare prints

    runs = sorted((workspace / "runs" / "progen-training").iterdir())
    assert runs, "JsonlTracker run dir missing"
    run_dir = runs[-1]

    events = [
        json.loads(line)
        for line in (run_dir / "events.jsonl").read_text().splitlines()
    ]
    spans = {r["span"] for r in events if r.get("ev") == "B"}
    assert "train/compile" in spans
    assert "ckpt/save" in spans
    # every span opened in a completed run also closed
    opened = [r["id"] for r in events if r.get("ev") == "B"]
    closed = [r["id"] for r in events if r.get("ev") == "E"]
    assert sorted(opened) == sorted(closed)

    metrics = [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
    ]
    goodput = [m for m in metrics if "goodput_pct" in m]
    assert goodput, "no goodput record logged"
    rep = goodput[-1]
    bucket_total = sum(
        v for k, v in rep.items() if k.startswith("bucket_s/")
    )
    assert bucket_total == pytest.approx(rep["wall_s"], rel=0.01)
    assert rep["coverage_pct"] >= 95.0


def test_train_prometheus_and_trace_export(workspace, monkeypatch):
    """Observability acceptance: a real CPU train run with --prom_file
    leaves a Prometheus textfile carrying goodput %, step-time quantiles,
    MFU and the resilience counter families, and its events.jsonl round-
    trips through `telemetry export-trace` + `summarize`."""
    import json
    import sys

    monkeypatch.chdir(workspace)
    monkeypatch.setitem(sys.modules, "wandb", None)  # JsonlTracker path
    runner = CliRunner()

    from progen_tpu.cli.train import main as train_main

    runs_root = workspace / "runs" / "progen-training"
    before = set(runs_root.iterdir()) if runs_root.exists() else set()
    prom = workspace / "train.prom"
    # 5 steps: StepTimer discards 2 warmup ticks, so step_s/tokens get
    # real post-warmup samples and the gauges land in the prom file
    res = runner.invoke(train_main, [
        "--batch_size", "4", "--grad_accum_every", "1",
        "--num_steps", "5", "--validate_every", "2", "--sample_every", "100",
        "--checkpoint_every", "100", "--seq_len", "32",
        "--config_path", str(workspace / "configs" / "model"),
        "--data_path", str(workspace / "train_data"),
        "--checkpoint_path", str(workspace / "ckpts_prom"),
        "--prom_file", str(prom),
    ])
    assert res.exit_code == 0, res.output

    text = prom.read_text()
    assert "progen_train_goodput_pct " in text
    assert 'progen_train_step_seconds{quantile="0.5"}' in text
    assert "progen_train_step_seconds_count " in text
    assert "progen_train_tokens_per_sec_per_chip " in text
    # this run is on the CPU platform, which has no peak: a utilization
    # against a guessed (v5e) peak must not appear
    assert "progen_train_mfu" not in text
    # resilience counter families are pre-declared (0 on a clean run) so
    # dashboards can rate() them before the first incident
    for fam in ("retries", "anomalies", "anomaly_rollbacks",
                "chaos_injections", "stalls", "ckpt_commit_failures"):
        assert f"# TYPE progen_train_{fam}_total counter" in text
        assert f"progen_train_{fam}_total " in text

    (new_run,) = set(runs_root.iterdir()) - before
    ev = new_run / "events.jsonl"
    assert ev.exists()

    from progen_tpu.cli.telemetry import main as telemetry_cli

    res = runner.invoke(telemetry_cli, ["export-trace", str(ev)])
    assert res.exit_code == 0, res.output
    trace = json.loads((new_run / "trace.json").read_text())
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "C"}
    assert "step_ms" in names  # metrics.jsonl picked up as sibling
    assert "goodput_pct" in names  # end-of-run goodput_host record
    spans = {e["name"] for e in trace["traceEvents"] if e["ph"] == "B"}
    assert "train/compile" in spans and "ckpt/save" in spans

    res = runner.invoke(telemetry_cli, ["summarize", str(ev)])
    assert res.exit_code == 0, res.output
    assert "goodput (per host)" in res.output
    assert "span latency" in res.output
