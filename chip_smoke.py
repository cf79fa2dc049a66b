#!/usr/bin/env python3
"""On-chip smoke: ETL -> train -> checkpoint -> serve through the normal CLIs.

The quickest proof that the system still starts on the chip. Drives the main
path once at the full width of ProGen-tiny (configs/model/tiny.toml, bf16,
the reference recipe batch 4 x accum 4), each stage its own
``python -m progen_tpu.cli.*`` process run one after another, then two kernel
legs through the same ``cli.train`` entry point:

  etl         cli.generate_data on a FASTA written from a seed
  train       cli.train --model_name tiny, 8 steps, writes a checkpoint
  serve       cli.serve on that checkpoint, 5 stdin JSONL requests, then EOF
  long8k      cli.train --model_name long8k (Pallas attention, scan + remat)
  fused       cli.train on tiny + use_fused_layer_kernels; its first-step
              loss must equal the main leg's to a bf16 tolerance

This parent never initialises a JAX backend: a chip belongs to one process
at a time, so it reads platform and device facts from each child's
``startup: {json}`` stderr line. A stage that fails, times out, or reports a
platform other than "tpu" makes the run exit non-zero with no result line;
there is no CPU mode. Everything it writes goes under
``chiprun_out/chip_smoke/`` (the heavy work directory inside it is removed at
the end); the compile cache is wherever ``JAX_COMPILATION_CACHE_DIR`` points,
else ``<checkout>/runs/xla_cache``.

Last stdout line on success:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import json
import math
import os
import random
import shutil
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
OUT = REPO / "chiprun_out" / "chip_smoke"
WORK = OUT / "work"
BUDGET_S = 1140.0  # the contract allows 1200 s, compilation included
TRAIN_STEPS = 8  # StepTimer discards the first ticks: step_ms needs >= 4

# jax-free by construction (progen_tpu/__init__.py is lazy); with the
# script alone in a directory this import is what fails
sys.path.insert(0, str(REPO))
from progen_tpu.utils.env import load_env_file  # noqa: E402


class SmokeFailure(Exception):
    pass


def _write_fasta(path: Path, n_records: int, seed: int) -> None:
    rng = random.Random(seed)
    taxa = ("Homo sapiens", "Mus musculus", "Escherichia coli",
            "Saccharomyces cerevisiae")
    with path.open("w") as f:
        for i in range(n_records):
            seq = "".join(
                rng.choice("ACDEFGHIKLMNPQRSTVWY")
                for _ in range(rng.randint(60, 900))
            )
            f.write(
                f">UniRef50_S{i:05d} Smoke protein n=1 "
                f"Tax={rng.choice(taxa)} TaxID={9000 + i} RepID=S{i}\n"
            )
            for j in range(0, len(seq), 60):
                f.write(seq[j:j + 60] + "\n")


def _startup_record(stderr_path: Path):
    """The child's ``startup: {json}`` line, once it has printed it."""
    try:
        text = stderr_path.read_text(errors="replace")
    except OSError:
        return None
    for line in text.splitlines():
        if line.startswith("startup: "):
            try:
                return json.loads(line[len("startup: "):])
            except ValueError:
                return None  # still being written
    return None


def _tail(path: Path, n: int = 3000) -> str:
    try:
        return path.read_text(errors="replace")[-n:]
    except OSError:
        return ""


def run_stage(name, module, args, *, deadline, cap_s, stdin_text=None,
              needs_chip=True):
    """One CLI child, cwd = the work directory. Returns its facts; raises
    SmokeFailure on a non-zero exit, a timeout, or a non-TPU platform —
    the last one as soon as the startup line says so, without waiting for
    a CPU run of a chip-sized model."""
    out_path, err_path = OUT / f"{name}.stdout", OUT / f"{name}.stderr"
    timeout = min(cap_s, deadline - time.monotonic())
    if timeout <= 0:
        raise SmokeFailure(f"{name}: no time left in the {BUDGET_S:.0f}s budget")
    env = {
        **os.environ,
        "PYTHONPATH": os.pathsep.join(
            [str(REPO)] + ([os.environ["PYTHONPATH"]]
                           if os.environ.get("PYTHONPATH") else [])
        ),
    }

    def on_the_chip(startup):
        if startup and startup.get("platform") != "tpu":
            raise SmokeFailure(
                f"{name}: platform is {startup.get('platform')!r}, not "
                "'tpu' — this smoke needs the chip"
            )
        return startup

    t0 = time.monotonic()
    startup = None
    with out_path.open("w") as out, err_path.open("w") as err:
        proc = subprocess.Popen(
            [sys.executable, "-m", module, *args],
            cwd=WORK, env=env, stdout=out, stderr=err,
            stdin=subprocess.PIPE if stdin_text is not None
            else subprocess.DEVNULL,
            text=True,
        )
        try:
            if stdin_text is not None:
                proc.stdin.write(stdin_text)
                proc.stdin.close()  # EOF drains the queue and exits
            while proc.poll() is None:
                if time.monotonic() - t0 > timeout:
                    raise SmokeFailure(
                        f"{name}: timed out after {timeout:.0f}s\n"
                        + _tail(err_path)
                    )
                if needs_chip and startup is None:
                    startup = on_the_chip(_startup_record(err_path))
                time.sleep(0.25)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
    wall = time.monotonic() - t0
    if proc.returncode != 0:
        raise SmokeFailure(
            f"{name}: exit code {proc.returncode}\n" + _tail(err_path)
        )
    if needs_chip:
        startup = startup or on_the_chip(_startup_record(err_path))
        if not startup:
            raise SmokeFailure(f"{name}: no startup line on stderr")
    return {"stage": name, "wall_s": round(wall, 1), "startup": startup}


def _jsonl(path: Path) -> list:
    return [json.loads(ln) for ln in path.read_text().splitlines()
            if ln.strip()]


def _train_facts(project: str, n_steps: int) -> dict:
    """Losses, compile and step times from the child's own metrics.jsonl
    (runs/<project>/<id>/ under the work directory)."""
    runs = sorted((WORK / "runs" / project).glob("*/metrics.jsonl"))
    if len(runs) != 1:
        raise SmokeFailure(f"{project}: expected one run dir, found {len(runs)}")
    recs = _jsonl(runs[0])
    steps = {r["_step"]: r for r in recs if "loss" in r}
    losses = [steps[i]["loss"] for i in sorted(steps)]
    if sorted(steps) != list(range(1, n_steps + 1)):
        raise SmokeFailure(
            f"{project}: logged steps {sorted(steps)}, wanted 1..{n_steps}"
        )
    if not all(math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{project}: non-finite loss in {losses}")
    step_ms = [steps[i]["step_ms"] for i in sorted(steps)
               if "step_ms" in steps[i]]
    books = next((r for r in reversed(recs) if "bucket_s/compile" in r), {})
    peak = max((r.get("hbm/peak_gb", 0.0) for r in recs), default=0.0)
    return {
        "losses": [round(x, 4) for x in losses],
        # trace + compile + the first step, which runs inside it
        "compile_and_first_step_s": books.get("bucket_s/compile"),
        "first_timed_step_ms": round(step_ms[0], 1) if step_ms else None,
        "last_step_ms": round(step_ms[-1], 1) if step_ms else None,
        "peak_hbm_gb": peak,
    }


def _checkpoint_written(path: Path) -> None:
    if not any(path.glob("ckpt_*")):
        raise SmokeFailure(f"no checkpoint written under {path}")


# (id, prime, total length): Request.length counts prime + BOS + generated
_PLAIN_REQUESTS = (
    ("r-short", "MKV", 64),
    ("r-annot", "[tax=Homo sapiens] # MSTA", 200),
    ("r-long-prime", "# " + "ACDEFGHIKLMNPQRSTVWY" * 12, 513),
    ("r-nucleus", "# MK", 96),
)
# the full 1,024: an infill template cannot stop early (EOS drawn at a free
# position becomes the best non-EOS token), so its count is exact
_FULL_TEMPLATE = "MKT" + "?" * 500 + "G" + "?" * 519  # 1023 chars + BOS


def _serve_requests() -> str:
    lines = [
        {"id": rid, "prime": prime, "length": length, "seed": 7 + i,
         **({"top_p": 0.9, "temperature": 0.8} if rid == "r-nucleus" else {})}
        for i, (rid, prime, length) in enumerate(_PLAIN_REQUESTS)
    ]
    lines.append({"id": "r-full", "template": _FULL_TEMPLATE, "seed": 3})
    return "".join(json.dumps(x) + "\n" for x in lines)


def _serve_facts() -> dict:
    events = _jsonl(OUT / "serve.stdout")
    wanted = {rid: length - len(prime.encode()) - 1
              for rid, prime, length in _PLAIN_REQUESTS}
    hoisted = _FULL_TEMPLATE.index("?")  # the frozen prefix is the prime
    wanted["r-full"] = len(_FULL_TEMPLATE) - hoisted
    settled = {}
    for rid, n_want in wanted.items():
        done = [e for e in events if e["event"] == "done" and e["id"] == rid]
        if len(done) != 1:
            raise SmokeFailure(f"serve: {rid} settled {len(done)} times")
        toks = [e for e in events if e["event"] == "token" and e["id"] == rid]
        n = done[0]["n_generated"]
        if n != len(toks):
            raise SmokeFailure(
                f"serve: {rid} reports {n} tokens, streamed {len(toks)}"
            )
        # a plain request may end early only by the stop rule (EOS drawn)
        hit_eos = rid != "r-full" and toks and toks[-1]["token"] == 0
        if n != n_want and not hit_eos:
            raise SmokeFailure(
                f"serve: {rid} generated {n} tokens, requested {n_want}"
            )
        settled[rid] = {"n_generated": n, "requested": n_want,
                        "ttft_s": done[0]["ttft_s"],
                        "latency_s": done[0]["latency_s"]}
    stray = [e for e in events if e["event"] in ("rejected", "embedding")]
    if stray:
        raise SmokeFailure(f"serve: unexpected events {stray[:3]}")
    # the frozen 'G' of the template lands verbatim (text = generated suffix)
    full = next(e for e in events
                if e["event"] == "done" and e["id"] == "r-full")
    if (len(full["text"]) != wanted["r-full"]
            or full["text"][_FULL_TEMPLATE.index("G") - hoisted] != "G"):
        raise SmokeFailure("serve: r-full lost its frozen template position")
    # flat decode compile count across the whole session (_match_placement)
    runs = sorted((WORK / "runs" / "progen-serve").glob("*/metrics.jsonl"))
    counts = [r["serve/decode_compile_count"] for r in _jsonl(runs[-1])
              if "serve/decode_compile_count" in r]
    if not counts or len(set(counts)) != 1:
        raise SmokeFailure(f"serve: decode compile count moved: {counts}")
    return {"requests": settled, "decode_compile_count": counts[-1],
            "compile_count_samples": len(counts)}


def _cache_entries(cache_dir: str) -> int:
    try:
        return sum(1 for _ in Path(cache_dir).iterdir())
    except OSError:
        return 0


def main() -> int:
    load_env_file(compile_counters=False)  # the children's LIBTPU_INIT_ARGS and compile-cache dir; no jax here
    cache_dir = os.environ["JAX_COMPILATION_CACHE_DIR"]
    cache_before = _cache_entries(cache_dir)
    t_start = time.monotonic()
    deadline = t_start + BUDGET_S

    shutil.rmtree(OUT, ignore_errors=True)
    (WORK / "configs" / "data").mkdir(parents=True)
    (WORK / "configs" / "model").mkdir(parents=True)
    _write_fasta(WORK / "smoke.fasta", n_records=600, seed=0)
    (WORK / "configs" / "data" / "smoke.toml").write_text(
        'read_from = "./smoke.fasta"\nwrite_to = "./train_data"\n'
        "num_samples = 600\nmax_seq_len = 1024\n"
        "prob_invert_seq_annotation = 0.5\nfraction_valid_data = 0.05\n"
        "num_sequences_per_file = 100000\nsort_annotations = true\n"
    )
    shipped = REPO / "configs" / "model"
    (WORK / "configs" / "model" / "tiny_fused.toml").write_text(
        (shipped / "tiny.toml").read_text()
        + "use_fused_layer_kernels = true\n"
    )

    stages = []

    def train_leg(name, model_name, config_dir, steps, batch, accum, cap_s):
        stages.append(run_stage(
            name, "progen_tpu.cli.train",
            ["--model_name", model_name, "--config_path", str(config_dir),
             "--mixed_precision", "--batch_size", str(batch),
             "--grad_accum_every", str(accum), "--num_steps", str(steps),
             "--data_path", "./train_data",
             "--checkpoint_path", f"./ckpts/{name}",
             "--checkpoint_keep_n", "1",
             "--wandb_project_name", f"smoke-{name}"],
            deadline=deadline, cap_s=cap_s,
        ))
        stages[-1].update(_train_facts(f"smoke-{name}", steps))
        return stages[-1]

    print(f"compile cache: {cache_dir} "
          f"({'empty' if cache_before == 0 else f'{cache_before} entries'}"
          " before the run)", flush=True)
    try:
        stages.append(run_stage(
            "etl", "progen_tpu.cli.generate_data",
            ["--data_dir", "./configs/data", "--name", "smoke", "--seed", "0"],
            deadline=deadline, cap_s=120, needs_chip=False,
        ))
        main_leg = train_leg("train", "tiny", shipped, TRAIN_STEPS, 4, 4, 420)
        _checkpoint_written(WORK / "ckpts" / "train")
        stages.append(run_stage(
            "serve", "progen_tpu.cli.serve",
            ["--checkpoint_path", "./ckpts/train", "--metrics-every", "64"],
            deadline=deadline, cap_s=300, stdin_text=_serve_requests(),
        ))
        stages[-1].update(_serve_facts())
        train_leg("long8k", "long8k", shipped, 5, 2, 1, 480)
        fused = train_leg("fused", "tiny_fused", "./configs/model", 5, 4, 4,
                          420)
        main_loss, fused_loss = main_leg["losses"][0], fused["losses"][0]
        tol = 2.0 ** -8 * abs(main_loss)  # one bf16 ulp of the loss
        if abs(fused_loss - main_loss) > tol:
            raise SmokeFailure(
                f"fused first-step loss {fused_loss} != main leg's "
                f"{main_loss} (bf16 tolerance {tol:.4f})"
            )
        fused["first_loss_delta_vs_train"] = round(fused_loss - main_loss, 5)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        # checkpoints and data are large; logs and run dirs are what a
        # reader wants back
        shutil.rmtree(WORK / "ckpts", ignore_errors=True)
        shutil.rmtree(WORK / "train_data", ignore_errors=True)
        (WORK / "smoke.fasta").unlink(missing_ok=True)

    chip = [s["startup"] for s in stages if s["startup"]]
    if len({(s["platform"], s["device_kind"], s["device_count"])
            for s in chip}) != 1:
        print("chip_smoke FAILED: stages disagree on the device",
              file=sys.stderr)
        return 1
    cache_after = _cache_entries(cache_dir)
    summary = {
        "stages": [{k: v for k, v in s.items() if k != "startup"}
                   for s in stages],
        "versions": {k: chip[0][k] for k in ("jax", "jaxlib", "libtpu")},
        "compile_cache": {
            "dir": cache_dir, "empty_before": cache_before == 0,
            "entries_before": cache_before, "entries_after": cache_after,
            # a warm run adds nothing: every compile was a cache hit
            "new_entries": cache_after - cache_before,
        },
        "total_wall_s": round(time.monotonic() - t_start, 1),
    }
    (OUT / "summary.json").write_text(json.dumps(summary, indent=1))
    for s in summary["stages"]:
        print(json.dumps(s), flush=True)
    print(json.dumps({k: summary[k] for k in
                      ("versions", "compile_cache", "total_wall_s")}),
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": chip[0]["platform"], "kind": chip[0]["device_kind"],
        "count": chip[0]["device_count"],
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
