"""Training CLI.

Flag-set parity with /root/reference/train.py:36-57 (same names, same
defaults), plus TPU-native mesh knobs (--mesh_data/--mesh_seq/--mesh_model)
the reference's single-host pmap had no equivalent for
(--data_parallel maps to "shard the data axis over every device").

Loop semantics (/root/reference/train.py:179-222): iterate sequence indices
in effective-batch strides; checkpoint / validate / sample on their
cadences; resume from the latest checkpoint (config-in-checkpoint overrides
the TOML, train.py:94-100); --new wipes after interactive confirmation.

Run: python -m progen_tpu.cli.train [flags]
"""

from __future__ import annotations

from progen_tpu.utils.env import load_env_file

load_env_file()  # XLA/env flags before jax import (ref train.py:1-2)

import sys
from pathlib import Path

import click
import numpy as np

import jax


def confirm(question: str) -> bool:
    """Interactive y/n guard for --new (train.py:85-88 semantics)."""
    return input(f"{question} (y/n) ").strip().lower() == "y"


class AnomalyRollback(Exception):
    """Raised by the metrics flush when the loss sentinel escalates
    (``patience`` consecutive anomalies); caught by the train loop, which
    restores the last good checkpoint and skips ahead in the data stream
    past the offending window."""


@click.command()
@click.option("--seed", default=42)
@click.option("--batch_size", default=4)
@click.option("--grad_accum_every", default=4)
@click.option("--learning_rate", default=2e-4)
@click.option("--weight_decay", default=1e-3)
@click.option("--data_parallel", default=False, is_flag=True)
@click.option("--max_grad_norm", default=0.5)
@click.option("--validate_every", default=100)
@click.option("--sample_every", default=500)
@click.option("--checkpoint_every", default=1000)
@click.option("--checkpoint_path", default="./ckpts")
@click.option("--checkpoint_keep_n", default=500)
@click.option("--config_path", default="./configs/model")
@click.option("--model_name", default="default")
@click.option("--prime_length", default=25)
@click.option("--seq_len", default=1024)
@click.option("--mixed_precision", default=False, is_flag=True)
@click.option("--data_path", default="./train_data")
@click.option("--wandb_off", default=False, is_flag=True)
@click.option("--wandb_project_name", default="progen-training")
@click.option("--new", default=False, is_flag=True)
@click.option("--mesh_data", default=0, help="data-parallel mesh axis size (0 = auto)")
@click.option("--mesh_seq", default=1, help="sequence-parallel mesh axis size")
@click.option("--mesh_model", default=1, help="tensor-parallel mesh axis size")
@click.option("--num_steps", default=0, help="stop after N optimizer steps (0 = full data)")
@click.option("--epochs", default=1,
              help="passes over the training data (reference semantics: 1)")
@click.option("--lr_schedule", default="constant",
              type=click.Choice(["constant", "cosine"]),
              help="constant (reference parity) or warmup+cosine decay "
                   "over the whole run")
@click.option("--warmup_steps", default=0,
              help="linear warmup steps for --lr_schedule cosine")
@click.option("--shuffle_seed", default=None, type=int,
              help="deterministic per-epoch training-data reshuffle "
                   "(resume-exact; unset = ETL order, reference parity)")
@click.option("--profile_dir", default="", help="jax.profiler trace dir for steps 2-4")
@click.option("--hardware_rng", default=False, is_flag=True,
              help="TPU-fast partitionable rbg PRNG (ref: set_hardware_rng_)")
@click.option("--naive_sample", default=False, is_flag=True,
              help="cadenced samples via the full-forward-per-token decoder "
                   "(reference parity path) instead of the KV-cache decode")
@click.option("--ring_attn", default=False, is_flag=True,
              help="explicit ring halo-exchange attention over the seq mesh "
                   "axis (requires --mesh_seq > 1) instead of GSPMD-inferred "
                   "collectives")
@click.option("--async_checkpoint", default=False, is_flag=True,
              help="overlap checkpoint writes with training (device arrays "
                   "are snapshotted to host synchronously; the storage "
                   "commit runs in the background and finalizes at the next "
                   "save)")
@click.option("--zero1", default=False, is_flag=True,
              help="ZeRO-1: shard the AdamW moments over the data mesh axis "
                   "(1/data-size the optimizer memory; forward/backward "
                   "layout unchanged)")
@click.option("--mesh_pipe", default=0,
              help="GPipe pipeline stages over the model mesh axis (the "
                   "depth-sharded path when the layer stack outgrows one "
                   "chip even after TP; repurposes the model axis, so "
                   "mutually exclusive with --mesh_model > 1). Requires "
                   "scan_layers=true in the model TOML. Composes with "
                   "--mesh_data: microbatch rows shard over the data axis "
                   "inside the pipeline. NOTE: backward is the GPipe "
                   "autodiff transpose — O(microbatches) activation "
                   "memory; pair with remat=true")
@click.option("--pipe_microbatches", default=0,
              help="GPipe microbatches per micro-step (0 = same as "
                   "--mesh_pipe); bubble fraction = (P-1)/(M+P-1), so "
                   "larger M amortizes the bubble at the cost of "
                   "activation memory")
@click.option("--pipe_schedule", default="gpipe",
              type=click.Choice(["gpipe", "1f1b"]),
              help="pipeline schedule: gpipe (autodiff transpose, "
                   "O(microbatches) boundary activations) or 1f1b "
                   "(interleaved fwd/bwd, O(stages) in-flight activations "
                   "— the large-microbatch-count deployment)")
@click.option("--stall_timeout", default=900.0,
              help="stall-watchdog deadline (seconds): when no optimizer "
                   "step completes within it, dump all-thread stacks and "
                   "the open/recent telemetry spans to stderr, then keep "
                   "running (0 = off)")
@click.option("--stall_escalate_after", default=3,
              help="after N consecutive stall reports for ONE stall, "
                   "snapshot per-device memory_stats + the open-span list "
                   "into the event stream before the surrounding timeout "
                   "kills the run (0 = legacy single report per stall)")
@click.option("--anomaly_factor", default=6.0,
              help="loss-spike threshold: anomalous when loss exceeds the "
                   "EMA baseline by this many deviations (0 = non-finite "
                   "detection only)")
@click.option("--anomaly_patience", default=3,
              help="consecutive anomalous steps before rolling back to the "
                   "last good checkpoint and skipping ahead in the data "
                   "stream; isolated spikes are skipped (the train step's "
                   "finite gate already refused any non-finite update)")
@click.option("--prom_file", default=None, type=str,
              help="write train-loop Prometheus text exposition here "
                   "(goodput %, step_ms quantiles, tokens/s/chip, MFU on a "
                   "TPU, HBM gauges, resilience counters; atomic rewrite on the "
                   "--validate_every cadence and at exit; node-exporter "
                   "textfile-collector compatible)")
@click.option("--prom_port", default=0,
              help="serve the same train-loop exposition over HTTP on "
                   "this localhost port (0 = off)")
@click.option("--flight_dir", default=None, type=str,
              help="arm the flight recorder: bounded in-memory ring of "
                   "recent telemetry, dumped atomically here on stall "
                   "escalation, anomaly rollback, chaos kill, or an "
                   "unhandled exception")
@click.option("--profile_pin", "profile_pin_path", default=None, type=str,
              help="profile.pin control file: a token written here "
                   "starts a bounded jax.profiler window on the LIVE "
                   "loop (acked through FILE.ack) — unlike "
                   "--profile_dir's fixed steps 2-4, this profiles the "
                   "moment something looks wrong")
def main(
    seed,
    batch_size,
    grad_accum_every,
    learning_rate,
    weight_decay,
    data_parallel,
    max_grad_norm,
    validate_every,
    sample_every,
    checkpoint_every,
    checkpoint_path,
    checkpoint_keep_n,
    config_path,
    model_name,
    prime_length,
    seq_len,
    mixed_precision,
    data_path,
    wandb_off,
    wandb_project_name,
    new,
    mesh_data,
    mesh_seq,
    mesh_model,
    num_steps,
    epochs,
    lr_schedule,
    warmup_steps,
    shuffle_seed,
    profile_dir,
    hardware_rng,
    naive_sample,
    ring_attn,
    async_checkpoint,
    zero1,
    mesh_pipe,
    pipe_microbatches,
    pipe_schedule,
    stall_timeout,
    stall_escalate_after,
    anomaly_factor,
    anomaly_patience,
    prom_file,
    prom_port,
    flight_dir,
    profile_pin_path,
):
    from progen_tpu.checkpoint import Package, get_checkpoint_fns
    from progen_tpu.config import ProGenConfig, load_toml_config
    from progen_tpu.data.dataset import iterator_from_tfrecords_folder
    from progen_tpu.data.tokenizer import decode_tokens
    from progen_tpu.models.progen import ProGen
    from progen_tpu.parallel.partition import (
        initialize_distributed,
        is_coordinator,
        make_mesh,
        put_batch,
    )
    # KV-cache decode by default: O(2w*d) attention per emitted token, so a
    # cadenced sample costs seconds, not (at long context) thousands of full
    # forwards blocking the train loop. Bit-identical to the naive path
    # (tests/test_sampling.py); --naive_sample keeps the parity decoder.
    from progen_tpu.sampling import sample, sample_fast

    sample_tokens = sample if naive_sample else sample_fast
    from progen_tpu.tracking import make_tracker, render_sample_html
    from progen_tpu.training import emit_clock_beacon
    from progen_tpu.training.optimizer import make_optimizer
    from progen_tpu.training.step import (
        abstract_train_state,
        compile_train_step,
        init_train_state,
        compile_eval_step,
        train_state_shardings,
    )

    from progen_tpu.resilience import chaos
    from progen_tpu.resilience.anomaly import (
        ROLLBACK,
        SPIKE,
        LossSentinel,
        PoisonBisector,
        consistent_flag,
    )

    if hardware_rng:
        from progen_tpu.utils.rng import use_hardware_rng

        use_hardware_rng()
    initialize_distributed()
    # fault injection (PROGEN_CHAOS="ckpt/save:0.3,data/read:kill"): no-op
    # unless the env asks for it; uninstalled in the finally below so an
    # in-process caller (tests) never leaks rules into the next run
    chaos.install_from_env()

    # shared metrics registry: resilience wiring (retry/chaos/watchdog/
    # checkpoint/anomaly) increments counters here as a side effect of the
    # run; reset keeps in-process reruns (tests) from bleeding counts, and
    # pre-seeding declares every resilience family at 0 so the Prometheus
    # exposition always carries them (an absent counter and a zero counter
    # are different dashboards)
    from progen_tpu.telemetry import get_registry

    reg = get_registry()
    reg.reset()
    for _c in (
        "retries", "anomalies", "anomaly_rollbacks", "chaos_injections",
        "stalls", "stall_escalations", "ckpt_quarantines",
        "ckpt_commit_failures",
    ):
        reg.inc(_c, 0)

    reset_ckpt, get_last, save_ckpt = get_checkpoint_fns(
        checkpoint_path, keep_last_n=checkpoint_keep_n,
        async_save=async_checkpoint,
    )
    if new:
        if not confirm(
            "are you sure you want to clear all your checkpoints and "
            "restart training?"
        ):
            sys.exit(0)
        reset_ckpt()

    # --- model config: checkpoint overrides TOML on resume (train.py:94-100)
    last_meta = get_last.peek()  # metadata only; arrays restored sharded below
    if last_meta is None:
        toml_path = Path(config_path) / f"{model_name}.toml"
        assert toml_path.exists(), f"model config not found: {toml_path}"
        model_kwargs = load_toml_config(str(toml_path))
    else:
        model_kwargs = last_meta.model_config
    if model_kwargs.get("family", "progen") != "progen":
        sys.exit(
            f"cli.train trains the progen family only: "
            f"{model_kwargs['family']!r} is served by cli.serve alone "
            f"(training/step.py has neither a grouped backward for expert "
            f"layers nor a backward through a recurrent state or a block "
            f"selection; ROADMAP.md, Reach 3)"
        )
    model_kwargs.setdefault("seq_len", seq_len)
    # reference semantics (train.py:53,106): full f32 unless --mixed_precision
    # opts into the fast dtype; an explicit TOML dtype wins when flag absent
    config = ProGenConfig.from_dict(
        {**model_kwargs, "dtype": "bfloat16" if mixed_precision
         else model_kwargs.get("dtype", "float32")}
    )

    # --- optimizer structure follows the checkpoint on resume: a schedule
    # mismatch would change the optax state pytree and break the sharded
    # restore, so train_config overrides the flags like model_config does
    saved_tc = getattr(last_meta, "train_config", None) if last_meta else None
    total_steps = 0
    if saved_tc:
        lr_schedule = saved_tc.get("lr_schedule", lr_schedule)
        warmup_steps = saved_tc.get("warmup_steps", warmup_steps)
        total_steps = saved_tc.get("total_steps", 0)
        # data order must also survive a flagless resume: the resume skip
        # indexes the SHUFFLED stream, so the seed rides the checkpoint
        shuffle_seed = saved_tc.get("shuffle_seed", shuffle_seed)
    if lr_schedule == "cosine" and not total_steps:
        # the cosine horizon needs the run length; the counts come from the
        # filename contract, so this early peek costs one glob
        n_total, _ = iterator_from_tfrecords_folder(data_path)
        total_steps = max(
            (n_total * max(epochs, 1)) // (batch_size * grad_accum_every), 1
        )
        if num_steps:
            # a capped run decays over the steps that will actually happen
            total_steps = min(total_steps, num_steps)
    optimizer = make_optimizer(
        learning_rate, weight_decay, max_grad_norm,
        schedule=lr_schedule, warmup_steps=warmup_steps,
        total_steps=total_steps,
    )
    train_config = {
        "lr_schedule": lr_schedule,
        "warmup_steps": warmup_steps,
        "total_steps": total_steps,
        "shuffle_seed": shuffle_seed,
    }

    # --- pipeline stages ride the model mesh axis (parallel/pipeline.py)
    pipe_m = 0
    if mesh_pipe > 1:
        if mesh_model > 1:
            raise click.UsageError(
                "--mesh_pipe repurposes the model mesh axis as the stage "
                "axis; it is mutually exclusive with --mesh_model > 1"
            )
        if ring_attn:
            raise click.UsageError(
                "--mesh_pipe and --ring_attn are separate deployment "
                "paths (stages run inside shard_map; the ring rides the "
                "seq axis of the GSPMD step)"
            )
        if not config.scan_layers:
            raise click.UsageError(
                "--mesh_pipe needs scan_layers=true in the model TOML: "
                "the stacked 'layers' param axis IS the stage axis "
                "(models/progen.stack_params converts old checkpoints)"
            )
        n_uniform = config.depth - config.global_mlp_depth
        if n_uniform % mesh_pipe:
            raise click.UsageError(
                f"{n_uniform} uniform layers not divisible by "
                f"{mesh_pipe} pipeline stages"
            )
        pipe_m = pipe_microbatches or mesh_pipe
        if batch_size % pipe_m:
            raise click.UsageError(
                f"--batch_size {batch_size} not divisible by "
                f"{pipe_m} pipeline microbatches"
            )
        mesh_model = mesh_pipe

    # --- mesh: data_parallel -> absorb all devices on the data axis
    if mesh_data == 0:
        mesh_data = -1 if (data_parallel or mesh_seq * mesh_model > 1) else 1
    mesh = make_mesh(data=mesh_data, seq=mesh_seq, model=mesh_model)

    # the one line that says what this run is on (chip_smoke.py asserts
    # platform == "tpu" from it); the same record joins the event stream
    # once the tracker exists below
    from progen_tpu import profiling
    from progen_tpu.data import _native

    startup = profiling.announce_startup(
        "train", mesh,
        codec="native" if _native.load() is not None else "python",
    )

    if mesh_pipe > 1 and (batch_size // pipe_m) % mesh.shape["data"]:
        raise click.UsageError(
            f"PPxDP composition shards each {batch_size // pipe_m}-row "
            f"microbatch over the data axis; not divisible by "
            f"data={mesh.shape['data']}"
        )
    if ring_attn and mesh.shape["seq"] < 2:
        raise click.UsageError(
            "--ring_attn needs a sequence-parallel mesh (--mesh_seq > 1)"
        )
    if ring_attn or config.use_ring_attn:
        # config.use_ring_attn may also arrive via a resumed checkpoint's
        # config; on a topology without a seq axis the model falls back to
        # the local path by itself (mesh guard in LocalAttentionBlock)
        import dataclasses

        config = dataclasses.replace(config, use_ring_attn=True)
        model = ProGen(config, mesh=mesh)
    else:
        model = ProGen(config)

    # --- state: cold init or sharded restore (never both). Pipeline mode
    # lays the state out by PIPELINE_RULES (stacked layer axis = stages;
    # TP rules off) — same checkpoint format either way, only placement.
    from progen_tpu.parallel.partition import DEFAULT_RULES, PIPELINE_RULES

    rules = PIPELINE_RULES if mesh_pipe > 1 else DEFAULT_RULES
    start_seq_index, run_id = 0, None
    if last_meta is None:
        state, shardings = init_train_state(
            model, optimizer, jax.random.PRNGKey(seed), config.seq_len,
            mesh=mesh, rules=rules, zero1=zero1,
        )
    else:
        from progen_tpu.checkpoint import sharded_abstract_state

        boxed, abstract = abstract_train_state(
            model, optimizer, config.seq_len
        )
        shardings = train_state_shardings(boxed, mesh, rules, zero1=zero1)
        pkg = get_last(sharded_abstract_state(abstract, shardings))
        state = pkg.state
        start_seq_index = pkg.next_seq_index
        run_id = pkg.run_id

    tracker = make_tracker(
        wandb_project_name, run_id, disabled=wandb_off
    )
    run_id = tracker.run_id or run_id
    num_params = state.num_params()
    tracker.set_config({**config.to_dict(), "num_params": num_params})

    # --- telemetry: spans ride the tracker's event stream (events.jsonl
    # next to metrics.jsonl; Noop on non-coordinators / --wandb_off), the
    # ledger classifies the loop's wall clock from here on
    from progen_tpu import telemetry
    from progen_tpu.telemetry import (
        GoodputLedger,
        StallWatchdog,
        emit_per_host_goodput,
        hbm_gauges,
        prometheus_text,
        start_prometheus_server,
        step_print,
        write_prometheus,
    )
    from progen_tpu.telemetry.hbm import device_memory_stats

    telemetry.configure(sink=tracker.log_event)
    telemetry.get_telemetry().emit(startup)
    ledger = GoodputLedger()

    # forensics: the black box rides the telemetry tap; the profile pin
    # is polled once per optimizer step alongside the watchdog beat
    from progen_tpu.telemetry import flight as flight_mod

    if flight_dir:
        flight_mod.arm(flight_dir, metrics_fn=reg.snapshot)
    prof_watcher = None
    if profile_pin_path:
        import os as _os

        prof_watcher = flight_mod.ProfilePinWatcher(
            profile_pin_path,
            _os.path.join(
                _os.path.dirname(profile_pin_path) or ".", "profiles"
            ),
        )

    # --- train-loop Prometheus: the registry already carries the
    # resilience counters and step_s reservoir; goodput + HBM ride in as
    # gauges at render time so file and HTTP expositions agree
    def _render_prom() -> str:
        reg.set_gauges({
            k.replace("/", "_"): v
            for k, v in ledger.report().items()
            if isinstance(v, (int, float))
        })
        reg.set_gauges(hbm_gauges())
        return prometheus_text(reg, prefix="progen_train_")

    def publish_prom() -> None:
        if prom_file and is_coordinator():
            write_prometheus(prom_file, _render_prom())

    prom_srv = None
    if prom_port and is_coordinator():
        prom_srv = start_prometheus_server(_render_prom, port=prom_port)
        print(
            f"prometheus on http://127.0.0.1:"
            f"{prom_srv.server_address[1]}/metrics",
            file=sys.stderr,
        )

    # --- data
    num_train, train_iter_fn = iterator_from_tfrecords_folder(data_path)
    num_valid, valid_iter_fn = iterator_from_tfrecords_folder(
        data_path, "valid"
    )
    assert num_train > 0 and num_valid > 0, "no training/validation data"
    proc_kwargs = dict(
        process_index=jax.process_index(), process_count=jax.process_count()
    )
    train_ds = train_iter_fn(
        config.seq_len,
        batch_size,
        skip=start_seq_index,
        loop=True,
        shuffle_seed=shuffle_seed,
        **proc_kwargs,
    )
    valid_ds = valid_iter_fn(
        config.seq_len, batch_size, loop=True, **proc_kwargs
    )
    if is_coordinator():
        print(f"params: {num_params:,}")
        print(f"train sequences: {num_train:,}  valid: {num_valid:,}")

    effective_batch = batch_size * grad_accum_every
    sample_rng = jax.random.PRNGKey(seed + 1)

    local_bs = batch_size // jax.process_count()

    def pad_rows(m):
        # ragged tails (end of data) are padded up to the local batch size
        # with 0-rows so every process contributes identical shapes to the
        # global array; a 0-row adds one EOS position to the loss mask
        return np.pad(m, ((0, local_bs - m.shape[0]), (0, 0)))

    def next_super_batch():
        with ledger.track("data"):
            micro = [
                pad_rows(next(train_ds)) for _ in range(grad_accum_every)
            ]
            return put_batch(np.stack(micro), mesh, accum_axis=True)

    import tqdm

    # preemption-safe shutdown: first SIGTERM/SIGINT finishes the current
    # step, saves a final checkpoint, and exits cleanly (preemptible TPU
    # VMs send SIGTERM before eviction); a second signal kills immediately
    import signal

    stop_requested = {"flag": False}

    def _request_stop(signum, frame):
        if stop_requested["flag"]:
            raise KeyboardInterrupt
        stop_requested["flag"] = True
        if is_coordinator():
            print(f"signal {signum}: finishing step, then checkpoint+exit")

    signal.signal(signal.SIGTERM, _request_stop)
    signal.signal(signal.SIGINT, _request_stop)

    # per-chip numbers divide by the MESH's devices, not the host's: the
    # default mesh is one device even on a four-chip host
    mesh_devices = list(mesh.devices.flat)
    timer = profiling.StepTimer(
        n_chips=len(mesh_devices),
        flops_per_tok=profiling.flops_per_token(config),
        peak=profiling.peak_flops(mesh_devices[0]),
    )
    import time

    # reference parity is ONE pass over the data (train.py:179); --epochs
    # extends the same record-index bookkeeping across passes (the data
    # iterator's skip/loop indices are global across epochs)
    num_total = num_train * max(epochs, 1)
    # the data cursor is a VARIABLE, not a range: an anomaly rollback skips
    # it ahead past the offending window, so the loop is a while over it
    seq_cursor = start_seq_index
    steps_done = 0
    rollbacks_done = 0  # distinct poison WINDOWS rolled back
    max_rollbacks = 3  # a third relapse means skipping isn't fixing it
    # bisection probes inside one window each cost a restore but don't
    # count as a new window; the backstop bounds total restores anyway
    total_rollbacks = 0
    max_total_rollbacks = max_rollbacks * 4
    bisector = None  # PoisonBisector over the current poison window
    bisect_start = 0  # seq_cursor where that window begins
    sentinel = LossSentinel(factor=anomaly_factor, patience=anomaly_patience)
    profiler_active = False
    # metric step continues across resumes (state.step is checkpointed);
    # a restarted loop must not rewind the tracker's step axis
    start_step = int(jax.device_get(state.step))
    # stall watchdog: beaten once per loop iteration below; a wedged
    # collective / device hang then leaves stacks + open spans in stderr
    # instead of a silent timeout kill (BASELINE.md's "dead all window")
    watchdog = (
        StallWatchdog(
            stall_timeout, escalate_after=stall_escalate_after
        ).start()
        if stall_timeout > 0
        else None
    )
    try:
      with mesh:
        # compiled steps live INSIDE the try: a jit failure here must
        # still run the finally that stops the loop=True prefetch workers
        with telemetry.span("train/compile"), ledger.track("compile") as tr:
            if mesh_pipe > 1:
                if pipe_schedule == "1f1b":
                    from progen_tpu.parallel.pipeline_1f1b import (
                        compile_1f1b_train_step,
                    )

                    train_step = compile_1f1b_train_step(
                        model, optimizer, shardings, mesh,
                        n_microbatches=pipe_m,
                    )
                else:
                    from progen_tpu.parallel.pipeline import (
                        compile_pipeline_train_step,
                    )

                    train_step = compile_pipeline_train_step(
                        model, optimizer, shardings, mesh,
                        n_microbatches=pipe_m,
                    )
                # rules=(): GSPMD activation constraints are meaningless
                # when the model axis holds stages, and the step runs
                # without them
                eval_step = compile_eval_step(
                    model, shardings, mesh, rules=()
                )
            else:
                train_step = compile_train_step(
                    model, optimizer, state, shardings, mesh
                )
                eval_step = compile_eval_step(model, shardings, mesh)
        # post-compile HBM is the first OOM-relevant reading: weights +
        # optimizer state + compiled-program reservations are all resident
        tracker.log(
            {"compile_s": round(tr.seconds, 3), **hbm_gauges()},
            step=start_step,
        )
        if watchdog is not None:
            watchdog.beat()  # compile done; the step clock starts now
        # pre-fetch only when the loop will actually run: resuming a
        # completed run (cursor already past the data) must fall through, not block
        # on a skip-exhausted iterator
        if seq_cursor < num_total and not (num_steps and num_steps <= 0):
            batch = next_super_batch()

        # deferred metrics: the host logs step N-1's loss AFTER step N is
        # dispatched, so the device always has a step in flight instead of
        # idling while the host prints/tracks (the reference fetches every
        # step, train.py:192). Cadence steps flush synchronously so the
        # non-finite gate always precedes a checkpoint write.
        pending = None

        def flush_metrics():
            nonlocal pending
            if pending is None:
                return
            p_step, p_metrics, p_bucket = pending
            pending = None
            with ledger.track(p_bucket):
                # host sync fence: the wait here IS the device step time
                # (or, for the first step under lazy jit, the compile)
                loss = float(p_metrics["last_micro_loss"])
            # the fetch above is the post-collective barrier every host
            # just crossed together: beacon it so `telemetry stitch`
            # can align the fleet's clocks on this step boundary
            emit_clock_beacon(p_step)
            grad_norm = float(p_metrics["grad_norm"])
            skipped = int(p_metrics.get("skipped", 0))
            # chaos perturbation point: PROGEN_CHAOS="train/loss:spike@2"
            # feeds the sentinel a poisoned value without touching the
            # device state — the rollback path rehearsed in-process
            loss = chaos.perturb("train/loss", loss)
            # failure TOLERANCE, not just detection (SURVEY §5): the
            # step's finite gate already refused a non-finite update, so
            # an isolated anomaly is skipped; ``patience`` consecutive
            # ones escalate to checkpoint rollback + data skip-ahead.
            # The verdict must bind every host (one host rolling back
            # alone deadlocks the next collective) — allgather-max, the
            # same pattern as the stop flag below.
            verdict = sentinel.observe(loss, grad_norm)
            if consistent_flag(verdict == ROLLBACK):
                raise AnomalyRollback(p_step, loss)
            if verdict == SPIKE or skipped:
                if is_coordinator():
                    step_print(
                        p_step,
                        f"anomaly: loss {loss:.4g} grad_norm "
                        f"{grad_norm:.4g}"
                        + (" (update refused on-device)" if skipped else "")
                        + f"; {sentinel.consecutive}/{sentinel.patience} "
                        "consecutive before rollback",
                    )
                reg.inc("anomalies")
                telemetry.get_telemetry().emit({
                    "ev": "anomaly", "ts": time.time(), "step": p_step,
                    "loss": loss, "grad_norm": grad_norm,
                    "skipped": skipped,
                    "consecutive": sentinel.consecutive,
                })
            perf = timer.tick(effective_batch * config.seq_len)
            if perf is not None:
                # the step_s reservoir is what the Prometheus summary
                # quantiles render from; throughput/MFU ride as gauges
                reg.observe("step_s", perf["step_ms"] / 1000.0)
                reg.set_gauges({
                    k: perf[k]
                    for k in ("tokens_per_sec_per_chip", "mfu")
                    if k in perf  # no mfu without a device peak (CPU)
                })
            with ledger.track("log"):
                if is_coordinator():
                    step_print(p_step, f"loss: {loss:.4f}")
                tracker.log(
                    {"loss": loss, "grad_norm": grad_norm,
                     **({"anomaly": 1} if verdict != "ok" else {}),
                     **(perf or {}), **hbm_gauges()},
                    step=p_step,
                )
                if p_step == start_step + 1 and is_coordinator():
                    # balance check: with the state placed and one step
                    # run, every device of the mesh must hold its share
                    # (not everything on device 0)
                    step_print(
                        p_step,
                        "bytes_in_use per device: " + " ".join(
                            f"{m['device']}={m.get('bytes_in_use', 'n/a')}"
                            for m in device_memory_stats(mesh_devices)
                        ),
                    )
        pbar = tqdm.tqdm(
            total=num_total, initial=min(seq_cursor, num_total),
            mininterval=10, unit="seq",
        )
        i = 0
        while seq_cursor < num_total:
          try:
            seq_index = seq_cursor
            stop = stop_requested["flag"]
            if jax.process_count() > 1:
                # every host must agree before leaving the collective loop
                # (a lone host breaking into the collective save deadlocks);
                # reduce-max: ANY host's signal stops all hosts
                from jax.experimental import multihost_utils

                stop = bool(
                    multihost_utils.process_allgather(np.int32(stop)).max()
                )
            if stop:
                break
            if num_steps and steps_done >= num_steps:
                break
            if profile_dir and i == 2:
                from jax import profiler as jax_profiler

                jax_profiler.start_trace(profile_dir)
                profiler_active = True
            # the first call of a lazily-jitted step traces and compiles
            # synchronously — that's compile time, not step time
            step_bucket = "compile" if steps_done == 0 else "step"
            with ledger.track(step_bucket):
                # async dispatch: cheap when the device is pipelined, the
                # full wait shows up at flush_metrics' host sync instead
                state, metrics = train_step(state, batch)
            steps_done += 1
            # prepare the NEXT batch while the device is busy (async
            # dispatch): host input pipeline overlaps device compute —
            # skipped when this was the last step
            is_last = (num_steps and steps_done >= num_steps) or (
                seq_index + effective_batch >= num_total
            )
            if not is_last:
                batch = next_super_batch()
            global_step = start_step + steps_done
            # log the PREVIOUS step (already complete — no device stall),
            # then queue this one
            flush_metrics()
            pending = (global_step, metrics, step_bucket)
            if watchdog is not None:
                watchdog.beat()
            if prof_watcher is not None:
                prof_watcher.poll_watch()
            if async_checkpoint:
                # per-step poll of the background commit thread: a fatal
                # commit error aborts at the NEXT step (with a
                # ckpt_commit_failed event), not minutes later at flush
                save_ckpt.check_error()
            # single source of truth for the cadence triggers: sync_now
            # MUST cover every condition that writes a checkpoint below,
            # or a NaN state could enter the rotation unchecked
            do_ckpt = i % checkpoint_every == 0
            do_valid = i % validate_every == 0
            do_sample = i % sample_every == 0
            if is_last or profiler_active or do_ckpt or do_valid or do_sample:
                flush_metrics()
            if profiler_active and i >= 4:
                from jax import profiler as jax_profiler

                jax_profiler.stop_trace()
                profiler_active = False

            next_seq_index = seq_index + effective_batch
            # cadence work below runs between step timings; each block
            # credits its goodput bucket AND excludes itself from the
            # StepTimer window, so step_ms/MFU stay pure step numbers
            # instead of silently absorbing checkpoint/eval/sample time
            if do_ckpt:
                with telemetry.span("train/ckpt", step=global_step), \
                        ledger.track("checkpoint") as tr:
                    save_ckpt(
                        Package(
                            next_seq_index=next_seq_index,
                            state=state,
                            model_config=config.to_dict(),
                            run_id=run_id,
                            train_config=train_config,
                        )
                    )
                timer.exclude(tr.seconds)
            if do_valid:
                with telemetry.span("train/eval", step=global_step), \
                        ledger.track("eval") as tr:
                    vloss = float(
                        eval_step(
                            state, put_batch(pad_rows(next(valid_ds)), mesh)
                        )
                    )
                timer.exclude(tr.seconds)
                if is_coordinator():
                    step_print(global_step, f"valid_loss: {vloss:.4f}")
                tracker.log(
                    {"valid_loss": vloss, **ledger.report()},
                    step=global_step,
                )
                publish_prom()  # same cadence as the goodput log line
            if do_sample:
                with telemetry.span("train/sample", step=global_step), \
                        ledger.track("sample") as tr:
                    valid_batch = np.asarray(next(valid_ds))
                    prime = valid_batch[0, 1 : prime_length + 1]  # skip BOS
                    if jax.process_count() > 1:
                        # every process must feed the IDENTICAL prime into
                        # the jitted decode over globally-sharded params
                        from jax.experimental import multihost_utils

                        prime = multihost_utils.broadcast_one_to_all(prime)
                    sampled = sample_tokens(
                        jax.random.fold_in(sample_rng, i),
                        model,
                        state.params,
                        prime,
                        config.seq_len,
                        top_k=25,
                        add_bos=True,
                    )
                    prime_str = decode_tokens(prime)
                    sampled_str = decode_tokens(
                        np.asarray(sampled)[prime_length + 1 :]
                    )
                timer.exclude(tr.seconds)
                if is_coordinator():
                    step_print(global_step, f"sample: {sampled_str[:120]}")
                tracker.log_html(
                    "samples",
                    render_sample_html(prime_str, sampled_str),
                    step=global_step,
                )
            seq_cursor = next_seq_index
            i += 1
            pbar.update(effective_batch)
          except AnomalyRollback as exc:
            total_rollbacks += 1
            pending = None  # the queued step's metrics are the anomaly
            step_at, bad_loss = exc.args
            # same poison window re-spiking (the resume landed before
            # the poison), or a NEW window? Re-spikes near the current
            # window feed the bisector; anything else opens a fresh one
            same_window = (
                bisector is not None
                and not bisector.exhausted
                and seq_cursor < bisect_start + 3 * effective_batch
            )
            if same_window:
                bisector.observe_respike()
            else:
                rollbacks_done += 1
                bisect_start = seq_cursor
                bisector = PoisonBisector(
                    effective_batch, min_step=batch_size
                )
            if (
                rollbacks_done > max_rollbacks
                or total_rollbacks > max_total_rollbacks
            ):
                raise RuntimeError(
                    f"{total_rollbacks} anomaly rollbacks without recovery "
                    f"(last loss {bad_loss} at step {step_at}); skipping "
                    "data is not fixing this — inspect the stream/hparams"
                ) from exc
            with telemetry.span("train/rollback", step=step_at), \
                    ledger.track("checkpoint") as tr:
                from progen_tpu.checkpoint import sharded_abstract_state

                # publish any in-flight async save so the restore walk
                # sees the newest COMPLETE checkpoint
                save_ckpt.flush()
                _, abstract = abstract_train_state(
                    model, optimizer, config.seq_len
                )
                pkg = get_last(sharded_abstract_state(abstract, shardings))
                if pkg is None:
                    raise RuntimeError(
                        f"anomaly rollback requested at step {step_at} "
                        "but no checkpoint exists to roll back to"
                    ) from exc
                state = pkg.state
                # skip ahead INTO the offending window, not past it:
                # the bisector proposes the smallest prefix-skip worth
                # trying (half the remaining window, aligned to one
                # per-device batch); if the poison is past the resume
                # point the window re-spikes and the next probe skips
                # more — exhaustion degrades to the legacy whole-window
                # discard, so clean tail data is salvaged, never lost
                skip = bisector.propose()
                seq_cursor = bisect_start + skip
                train_ds.close()
                train_ds = train_iter_fn(
                    config.seq_len,
                    batch_size,
                    skip=seq_cursor,
                    loop=True,
                    shuffle_seed=shuffle_seed,
                    **proc_kwargs,
                )
                sentinel.reset()
                if seq_cursor < num_total:
                    batch = next_super_batch()
            timer.exclude(tr.seconds)
            restored_step = int(jax.device_get(state.step))
            if is_coordinator():
                step_print(
                    step_at,
                    f"anomaly rollback {rollbacks_done}/{max_rollbacks}: "
                    f"restored checkpoint (state step {restored_step}), "
                    f"data skipped ahead to sequence {seq_cursor} "
                    f"(bisect: {skip}/{bisector.window} of the window "
                    f"discarded, {bisector.salvaged} salvaged)",
                )
            reg.inc("anomaly_rollbacks")
            telemetry.get_telemetry().emit({
                "ev": "anomaly_rollback", "ts": time.time(),
                "step": step_at, "loss": bad_loss,
                "restored_step": restored_step,
                "next_seq_index": seq_cursor,
                "rollbacks_done": rollbacks_done,
                "total_rollbacks": total_rollbacks,
                "bisect_skip": skip,
                "bisect_window": bisector.window,
                "bisect_salvaged": bisector.salvaged,
            })
            pbar.update(effective_batch)
            if watchdog is not None:
                watchdog.beat()
        pbar.close()
        # stop-flag / exhausted-iterator exits leave the last step queued:
        # its loss (and the sentinel verdict) must land before the final
        # save; a rollback verdict HERE just ends the run — the state
        # below passed the finite gate, so saving it is safe
        try:
            flush_metrics()
        except AnomalyRollback:
            pass
        # goodput closes the books on the loop: MFU said how fast the
        # steps were, this says how often the loop was actually stepping
        report = ledger.report()
        tracker.log(report, step=start_step + steps_done)
        if is_coordinator():
            step_print(
                start_step + steps_done,
                f"goodput: {report['goodput_pct']:.1f}% of "
                f"{report['wall_s']:.1f}s wall "
                f"(attributed {report['coverage_pct']:.1f}%)",
            )
        # per-host goodput (COLLECTIVE — every host reaches this line on
        # every exit path of the while loop above): each host's ledger
        # vector is allgathered and the full table lands in every host's
        # event stream, so one events.jsonl reconstructs the straggler
        # skew (`telemetry summarize`)
        host_reports = emit_per_host_goodput(ledger)
        if is_coordinator() and len(host_reports) > 1:
            from progen_tpu.telemetry import goodput_skew

            skew = goodput_skew(host_reports)
            worst = max(
                (
                    (row["skew"], name, row["straggler"])
                    for name, row in skew.items()
                    if isinstance(row, dict) and name != "goodput_pct"
                ),
                default=None,
            )
            if worst is not None:
                step_print(
                    start_step + steps_done,
                    f"goodput skew across {skew['hosts']} hosts: worst "
                    f"bucket '{worst[1]}' +{worst[0]:.2f}s on host "
                    f"{worst[2]}",
                )
        publish_prom()  # final exposition includes the end-of-run books

    finally:
        # nested so each cleanup runs even if an earlier one raises
        try:
            chaos.uninstall()  # rules must not leak into a later in-process run
            if prom_srv is not None:
                prom_srv.shutdown()
            if watchdog is not None:
                watchdog.stop()
            if prof_watcher is not None:
                prof_watcher.close()  # flush an in-flight window
            flight_mod.disarm()
            # detach the span sink BEFORE the tracker closes its files:
            # a later span in this process must not write to a dead fd
            telemetry.configure()
            if profiler_active:
                from jax import profiler as jax_profiler

                jax_profiler.stop_trace()
        finally:
            try:
                # async mode: publish any committed-but-unfinalized
                # checkpoint and stop the background thread even on aborts
                # (e.g. the non-finite-loss raise) — every periodic save's
                # state was verified finite before it was saved, so the
                # pending snapshot is always good
                save_ckpt.close()
            finally:
                # stop the prefetch workers (loop=True streams never
                # exhaust); nested again so one close failing cannot
                # leak the other worker
                try:
                    train_ds.close()
                finally:
                    valid_ds.close()

    # final checkpoint so short runs (e.g. --num_steps) always persist;
    # the cursor counts exactly the records consumed by executed steps
    # PLUS any rollback skip-ahead — resume must not re-read either
    save_ckpt(
        Package(
            next_seq_index=seq_cursor,
            state=state,
            model_config=config.to_dict(),
            run_id=run_id,
            train_config=train_config,
        )
    )
    save_ckpt.close()  # async mode: publish the final save before exit
    tracker.finish()


if __name__ == "__main__":
    main()
