"""REAL multi-host integration: two jax.distributed processes (4 virtual
CPU devices each, Gloo collectives between them) train the sharded step on
a data=8 mesh with per-process record dealing, save one collective sharded
checkpoint, restore it, and must reproduce the single-process losses."""

import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from progen_tpu.data.tfrecord import tfrecord_writer

REPO = Path(__file__).parents[1]


def _write_data(data_dir: Path, n=24, seq_chars=12):
    rng = np.random.default_rng(0)
    path = data_dir / f"0.{n}.train.tfrecord.gz"
    with tfrecord_writer(str(path)) as write:
        for _ in range(n):
            s = bytes(rng.integers(65, 90, seq_chars).astype(np.uint8))
            write(b"# " + s)


def test_two_process_training_matches_single(tmp_path):
    data_dir = tmp_path / "data"
    ckpt_dir = tmp_path / "ckpts"
    data_dir.mkdir()
    _write_data(data_dir)

    import socket

    with socket.socket() as s:  # free port: no collision with leaked runs
        s.bind(("localhost", 0))
        port = s.getsockname()[1]

    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(REPO),
    }
    procs = [
        subprocess.Popen(
            [
                sys.executable,
                str(REPO / "tests" / "multihost_worker.py"),
                str(i),
                str(data_dir),
                str(ckpt_dir),
                str(port),
            ],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            env=env,
        )
        for i in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=900)
            outs.append(out.decode())
    finally:
        for p in procs:  # never leak workers (they hold the port + CPU)
            if p.poll() is None:
                p.kill()
    # capability gate, not an error gate: some jax builds ship a CPU
    # backend without cross-process (Gloo) collectives at all — the
    # workers then die with this exact message before any assertion this
    # test makes is reachable. Anything else still fails below.
    if any("aren't implemented on the CPU backend" in o for o in outs):
        pytest.skip("this jax build lacks multi-process CPU collectives")
    for i, out in enumerate(outs):
        assert "WORKER_OK" in out, f"proc {i} failed:\n{out[-2000:]}"

    # both processes observed identical global losses
    def losses(text):
        return [
            float(line.split()[2])
            for line in text.splitlines()
            if line.startswith("LOSS ")
        ]

    l0, l1 = losses(outs[0]), losses(outs[1])
    assert len(l0) == 3
    np.testing.assert_allclose(l0, l1, rtol=1e-6)

    def tagged_loss(text, tag):
        return [
            float(line.split()[1])
            for line in text.splitlines()
            if line.startswith(tag)
        ]

    # cross-host TENSOR-parallel phase: model axis spans both processes
    # (every block's all-reduce crosses hosts); same first batch and same
    # fresh init as step 0 of the DP phase -> identical loss
    (tp0,), (tp1,) = (tagged_loss(o, "LOSS_TP") for o in outs)
    np.testing.assert_allclose(tp0, tp1, rtol=1e-6)
    np.testing.assert_allclose(tp0, l0[0], rtol=1e-5)

    # cross-host RING-attention phase: the seq axis spans the two
    # processes, so every block's k/v halo ppermute crosses hosts; same
    # init + batch as the TP phase -> identical loss
    (r0,), (r1,) = (tagged_loss(o, "LOSS_RING") for o in outs)
    np.testing.assert_allclose(r0, r1, rtol=1e-6)
    np.testing.assert_allclose(r0, l0[0], rtol=1e-5)

    # single-process baseline on the SAME global batches (the loss is a
    # mean over the batch — row order from record dealing is irrelevant)
    import jax

    from progen_tpu.config import ProGenConfig
    from progen_tpu.data.dataset import iterator_from_tfrecords_folder
    from progen_tpu.models.progen import ProGen
    from progen_tpu.training.optimizer import make_optimizer
    from progen_tpu.training.step import init_train_state, make_train_step

    CFG = ProGenConfig(
        num_tokens=32, dim=16, seq_len=16, depth=2, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2, dtype="float32",
    )
    model = ProGen(CFG)
    optimizer = make_optimizer(1e-3)
    state, _ = init_train_state(
        model, optimizer, jax.random.PRNGKey(0), CFG.seq_len
    )
    step = jax.jit(make_train_step(model, optimizer))
    _, iter_fn = iterator_from_tfrecords_folder(str(data_dir))
    ds = iter_fn(CFG.seq_len, batch_size=8, loop=True)
    first_batch = next(ds)[None]
    baseline = []
    batch = first_batch
    for _ in range(3):
        state, metrics = step(state, batch)
        baseline.append(float(metrics["loss"]))
        batch = next(ds)[None]
    np.testing.assert_allclose(l0, baseline, rtol=1e-5)

    # cross-host 1F1B PIPELINE phase: stage ppermutes hop between the two
    # processes (interleaved stage axis) with DP-sharded microbatch rows;
    # 1F1B grads/loss are exact, so the loss must equal the plain step's
    # on the same scan_layers init + first global batch
    (p0,), (p1,) = (tagged_loss(o, "LOSS_PIPE") for o in outs)
    np.testing.assert_allclose(p0, p1, rtol=1e-6)

    import dataclasses

    cfg_pipe = dataclasses.replace(CFG, depth=5, scan_layers=True)
    model_pipe = ProGen(cfg_pipe)
    state_p, _ = init_train_state(
        model_pipe, optimizer, jax.random.PRNGKey(0), CFG.seq_len
    )
    step_p = jax.jit(make_train_step(model_pipe, optimizer))
    _, metrics_p = step_p(state_p, first_batch)
    np.testing.assert_allclose(p0, float(metrics_p["loss"]), rtol=1e-5)

    # --- per-host goodput: each worker emitted the allgathered 2-host
    # table into its own event file, so EITHER file alone reconstructs
    # the cross-host skew; worker 1 booked +0.5s of data wait, and the
    # summarize report must finger it as the data straggler
    import json

    from click.testing import CliRunner

    from progen_tpu.cli.telemetry import main as telemetry_cli

    ev = tmp_path / "events_p0.jsonl"
    assert ev.exists(), "worker 0 left no event stream"
    hosts = {
        rec["host"]
        for rec in map(json.loads, ev.read_text().splitlines())
        if rec.get("ev") == "goodput_host"
    }
    assert hosts == {0, 1}
    res = CliRunner().invoke(telemetry_cli, ["summarize", str(ev)])
    assert res.exit_code == 0, res.output
    assert "straggler table" in res.output
    straggler_lines = [
        ln for ln in res.output.splitlines()
        if ln.startswith("data") and "straggler host 1" in ln
    ]
    assert straggler_lines, res.output

    # --- fleet stitch: both hosts' event files merge into ONE trace on
    # a common corrected clock, anchored on the per-step clock_beacon
    # records each worker emitted after its loss fetch
    ev1 = tmp_path / "events_p1.jsonl"
    assert ev1.exists(), "worker 1 left no event stream"
    stitched = tmp_path / "stitched.json"
    res = CliRunner().invoke(
        telemetry_cli,
        ["stitch", str(ev), str(ev1), "--out", str(stitched)],
    )
    assert res.exit_code == 0, res.output
    assert "clock offset" in res.output
    trace = json.loads(stitched.read_text())
    timed = [e for e in trace["traceEvents"] if e["ph"] != "M"]
    assert timed, "stitched trace has no events"
    # both host tracks present, corrected timestamps monotone
    assert {e["pid"] for e in timed} >= {0, 1}
    ts = [e["ts"] for e in timed]
    assert ts == sorted(ts)
    # both hosts aligned: per-host offsets recovered (host 0 = 0 by
    # construction), beacons for the 3 steps, cross-host arrows
    assert set(trace["progenClockOffsets"]) == {"0", "1"}
    assert trace["progenClockOffsets"]["0"] == 0.0
    beacons = [
        e for e in timed
        if e.get("name") == "clock_beacon" and e["ph"] == "X"
    ]
    assert {(e["pid"], e["args"]["step"]) for e in beacons} == {
        (h, s) for h in (0, 1) for s in (0, 1, 2)
    }
    flows = [e for e in timed if e.get("name") == "step_sync"]
    assert len([e for e in flows if e["ph"] == "s"]) == 3
    assert len([e for e in flows if e["ph"] == "f"]) == 3
    # fleet goodput skew rode the merged stream: both hosts, host 1
    # still the data straggler
    skew = trace["progenGoodputSkew"]
    assert skew["hosts"] == 2
    assert skew["data"]["straggler"] == 1
