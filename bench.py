"""Throughput benchmark suite — on-chip evidence, in one run.

Driver contract: ``python bench.py`` prints a JSON line
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N, ...}``.
It needs a TPU: with no chip, or when the headline phase fails, it exits
non-zero and prints no metric. A number from a CPU run is never written
under a device metric's name.

The default run is a PHASED SUITE, each phase in its own subprocess (a
chip belongs to one process at a time, so the parent never initialises a
JAX backend; a hung phase is killed without taking the parent down):

  1. train-tiny       — headline: donated train step, ProGen-tiny (README
                        example config, BASELINE.md config 1), bf16,
                        reference recipe 4x4. tokens/sec/chip + MFU.
                        The headline JSON line is printed (and flushed) the
                        moment this phase finishes.
  2. kernel-w256/512  — Pallas local-attention kernel vs the XLA path,
                        fwd+bwd, Mosaic-compiled, including on-chip
                        max-abs-error vs the golden.
  3. train-tiny-pallas— the flagship with use_pallas_attn + scan_layers
                        (one scanned body = few Mosaic instances). Its
                        controlled comparison is train-tiny-scan, the XLA
                        twin with the same layer structure — train-tiny
                        (phase 1) differs in two variables.
  4. train-long8k[-xla]— long-context config (8192/512, remat+scan),
                        Pallas per its TOML vs forced-XLA, side by side.
  5. train-default / train-base — remaining BASELINE.md configs.
  6. large-projection — ProGen-large (1.2B) HBM/flops sharding study
                        (closed-form, no chip).

Every phase result is appended to runs/bench_detail.json as it lands (no
run writes into a tracked file). At the end one FINAL line (same headline
metric/value + per-phase summary) is printed — drivers that parse the last
line get the rich record, drivers that parse the first still get the
headline.

vs_baseline: the reference publishes no numbers (BASELINE.md), so the
denominator is this repo's own newest prior-round TPU record when present,
else 1.0 (the value itself establishes the baseline).

MFU: profiling.flops_per_token (PaLM convention, SGU spatial mix charged
by actual per-token work) / the device's bf16 peak (profiling.peak_flops;
an unknown TPU kind is an error).

Extra CLIs:
  python bench.py kernel           — kernel phases only, one line (TPU).
  python bench.py --config base    — one train phase in-process (TPU).
  python bench.py _phase NAME      — one phase in-process, on whatever
                                     platform jax reports (stamped in the
                                     result; train phases need the chip).
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

_REPO = Path(__file__).resolve().parent
_DETAIL_PATH = _REPO / "runs" / "bench_detail.json"
# kernel phases record their measured winners here, in the shape of
# ops/pallas_policy.json; promoting a row into that tracked input is a
# reviewed change, never a side effect of a run
_MEASURED_POLICY_PATH = _REPO / "runs" / "pallas_policy_measured.json"
_LOG_DIR = _REPO / "runs" / "bench_logs"


_WATCHDOG = None  # phase-child stall watchdog; beaten by _mark

# phase-child goodput ledger: the _phase entry point owns one per run and
# benches credit compile/step/checkpoint time through _account; the phase
# result then carries a ``goodput`` report (and a ``goodput`` event lands
# in the phase child's events.jsonl) so a slow bench is attributable —
# compile-bound vs step-bound vs checkpoint-bound — straight from the JSON
_PHASE_LEDGER = None


def _account(bucket: str, seconds) -> None:
    if _PHASE_LEDGER is not None and seconds is not None:
        _PHASE_LEDGER.account(bucket, float(seconds))


def _mark(msg: str) -> None:
    """Progress marker on stderr (streamed to the phase log by the
    orchestrator): when a phase is timeout-killed, the trail shows how far
    it got — init, compile, or iteration N. Doubles as the stall-watchdog
    heartbeat in phase children, so "marks stopped" is exactly the
    condition that triggers a stack dump."""
    print(f"[bench-mark +{time.perf_counter() - _T0:.1f}s] {msg}",
          file=sys.stderr, flush=True)
    if _WATCHDOG is not None:
        _WATCHDOG.beat()


_T0 = time.perf_counter()


def _hbm_stats() -> dict:
    """Per-device memory stats where the backend exposes them (TPU does;
    CPU returns nothing) — peak HBM in use is the per-config memory
    evidence next to each throughput row. Reads through the shared
    telemetry gauge helper; output keys stay the legacy bench-schema
    names that ADVICE/VERDICT parsers grep for."""
    from progen_tpu.telemetry import hbm_gauges

    g = hbm_gauges()
    out = {}
    if "hbm/peak_gb" in g:
        out["peak_hbm_gb"] = round(g["hbm/peak_gb"], 2)
    if "hbm/limit_gb" in g:
        out["hbm_limit_gb"] = round(g["hbm/limit_gb"], 2)
    return out


def _suspect_fields(flops: float, seconds: float, peak: float) -> dict:
    """Honesty-guard fields for ANY timed phase: implied device FLOP/s and
    a flag when it exceeds physical peak — a number past peak means the
    measurement (not the chip) is broken and must not be read as real."""
    if peak is None:  # CPU smoke of a phase: no peak to be suspect against
        return {"timing_suspect": False}
    implied = flops / max(seconds, 1e-12)
    return {
        "implied_device_tflops": round(implied / 1e12, 1),
        "timing_suspect": bool(implied > 1.1 * peak),
    }

# (name, timeout_sec) in execution order; budget cuts from the tail, so
# the headline runs first.
_PHASES = (
    ("train-tiny", 720),
    ("calib-matmul", 300),  # fence calibration: known-FLOPs matmul chain
    ("train-tiny-bs32", 420),  # ceiling companion: bs=32, no accum
    ("train-tiny-scan", 720),  # XLA twin of train-tiny-pallas's structure
    ("kernel-w256", 420),
    ("kernel-w512", 420),
    # long8k-shape kernel row (w=512 n=8192 bh=16): runs BEFORE the long8k
    # train phases so their policy lookup is backed by a measurement at the
    # shape they actually run, writing ops/pallas_policy.json on a clean run
    ("kernel-w512-n8192", 600),
    # fused layer kernels (standalone Mosaic compiles like kernel-w*,
    # not the slow whole-program train-step embedding): writes the
    # layer_entries policy rows the fused-flag train runs read
    ("kernel-fused-w256", 420),
    ("kernel-fused-w512", 420),
    ("train-default", 600),
    ("train-base", 720),
    ("train-long8k-xla", 1080),
    ("sgu-mix", 420),
    ("train-long8k", 1500),
    ("train-tiny-pallas", 1500),
    ("decode-tiny", 600),
    # serving engine under staggered arrivals (steady-state tokens/s +
    # TTFT); two jits only, shapes shared with decode-tiny's policy
    ("decode-serve", 600),
    # admission stall under mixed traffic: decode ITL p99 while a long
    # prompt admits, monolithic vs chunked, plus the prefix-cache TTFT
    # speedup — the two gated serving ratios (bench.py gate --metric
    # serve_admit_stall_ratio / serve_prefix_cache_speedup)
    ("decode-admit-stall", 600),
    # framed-TCP loopback vs unix socket on real serve subprocesses
    # (pinned to CPU: host-side transport parity, no chip claim) — the
    # gated serve_transport_parity ratio
    ("transport-overhead", 600),
    # armed vs disarmed flight recorder on real serve subprocesses
    # (pinned to CPU: host-side forensics parity, no chip claim) — the
    # gated flight_overhead_ratio; the always-on black box must stay
    # within ~1% of free
    ("flight-overhead", 600),
    # int8 weight-quantized decode vs fp on the same params (quant
    # compile cost rides the engine build; two decode jits total)
    ("decode-int8", 600),
    # protein-design workloads: bulk scoring throughput (bucketed
    # compile-once score_step) and the vmapped L x 20 mutant scan
    ("batch-score", 600),
    ("mutagenesis", 600),
    # sustained base run: 100+ steps + async ckpt + exactness-checked
    # restore (the production-claim proxy); long, so late in the order
    ("sustain-base", 1200),
    ("profile-tiny", 420),  # artifact-only; last, fully expendable
)

# per-config bench recipes: (grad_accum, micro_batch, iters)
_RECIPES = {
    "tiny": (4, 4, 10),      # reference train recipe, train.py:38-43
    "default": (4, 4, 10),
    "base": (2, 4, 6),
    "long8k": (1, 2, 5),
}


def _probe_platform(timeout: float = 180.0) -> str | None:
    """Backend platform as a SUBPROCESS reports it ("tpu"/"cpu"/...), None
    when backend init fails or hangs. The orchestrating parent must never
    initialise a backend itself: a chip belongs to one process at a time,
    and the phase children need it (the probe process releases its claim
    on exit)."""
    try:
        r = subprocess.run(
            [sys.executable, "-c",
             "import jax; print(jax.devices()[0].platform)"],
            timeout=timeout,
            capture_output=True,
            text=True,
        )
    except subprocess.TimeoutExpired:
        return None
    return r.stdout.strip() if r.returncode == 0 else None


def _is_tpu_platform(platform: str | None) -> bool:
    return platform == "tpu"


def _require_tpu() -> None:
    """In-process entry points (kernel, --config) need the chip: exit
    non-zero, printing no metric, anywhere else."""
    import jax

    platform = jax.devices()[0].platform
    if not _is_tpu_platform(platform):
        sys.exit(f"bench.py needs a TPU backend; jax reports {platform!r}")


def _prior_round_value() -> float | None:
    best = None
    for path in sorted(glob.glob(str(_REPO / "BENCH_r*.json"))):
        try:
            rec = json.loads(open(path).read())
        except (OSError, json.JSONDecodeError):
            continue
        parsed = rec.get("parsed") if isinstance(rec, dict) else None
        if not isinstance(parsed, dict):
            continue
        if (
            parsed.get("metric", "").startswith("train_tokens")
            and parsed.get("platform", "tpu") == "tpu"
        ):
            best = parsed.get("value", best)
    return best


# "smoke" pseudo-config: the shapes phases shrink to when run off-TPU
# (CI drives a few of them that way to prove their records parse)
_SMOKE_CONFIG = dict(
    num_tokens=256, dim=64, depth=2, heads=2, dim_head=32, window_size=32,
    seq_len=128, global_mlp_depth=1, ff_mult=2, dtype="float32",
)


def _load_config(name: str, **overrides):
    from progen_tpu.config import ProGenConfig, load_toml_config

    if name == "smoke":
        kwargs = dict(_SMOKE_CONFIG)
    else:
        toml = _REPO / "configs" / "model" / f"{name}.toml"
        kwargs = load_toml_config(str(toml))
    kwargs.update(overrides)
    kwargs.setdefault("dtype", "bfloat16")
    return ProGenConfig.from_dict(kwargs)


# --------------------------------------------------------------------------
# phases (each runs in its own process via `bench.py _phase <name>`)
# --------------------------------------------------------------------------


def _train_bench(config_name: str, *, use_pallas=None, recipe=None,
                 phase_suffix: str = "", profile_dir: str | None = None,
                 extra_overrides: dict | None = None) -> dict:
    """One measured train-step benchmark for a named config. Returns the
    result dict (also JSON-printed by the _phase entry point). ``recipe``
    overrides the (grad_accum, micro_batch, iters) table — used by the
    ceiling phases that lift the reference-parity batch. ``profile_dir``
    wraps the timed loop in a jax.profiler trace (the profile phase)."""
    import contextlib

    import jax

    from progen_tpu import profiling
    from progen_tpu.models.progen import ProGen
    from progen_tpu.parallel.partition import make_mesh, put_batch
    from progen_tpu.training.optimizer import make_optimizer
    from progen_tpu.training.step import compile_train_step, init_train_state

    peak = profiling.peak_flops(jax.devices()[0])
    if peak is None:
        raise RuntimeError(
            "train phases report tokens/s/chip and MFU and need a TPU; "
            f"jax reports {jax.devices()[0].platform!r}"
        )
    overrides = dict(extra_overrides or {})
    if use_pallas is not None:
        overrides["use_pallas_attn"] = use_pallas
    config = _load_config(config_name, **overrides)
    grad_accum, micro_bs, n_iters = recipe or _RECIPES[config_name]

    n_chips = len(jax.devices())
    _mark(f"devices ok: {n_chips} chip(s)")
    micro_bs *= n_chips
    mesh = make_mesh()
    model = ProGen(config)
    optimizer = make_optimizer()
    state, shardings = init_train_state(
        model, optimizer, jax.random.PRNGKey(0), config.seq_len, mesh=mesh
    )
    _mark("train state initialized")
    step = compile_train_step(model, optimizer, state, shardings, mesh)

    rng = np.random.default_rng(0)
    batch = rng.integers(
        1, config.num_tokens, size=(grad_accum, micro_bs, config.seq_len + 1)
    ).astype(np.int32)

    with mesh:
        device_batch = put_batch(batch, mesh, accum_axis=True)
        _mark("batch on device; compiling train step")
        t0 = time.perf_counter()
        # AOT-compile ONCE and run the same executable for warmup, timing,
        # and cost_analysis — .lower().compile() does NOT share the traced
        # jit call's executable cache, so mixing the two paths would
        # compile the step twice inside the phase timeout
        compiled = step.lower(state, device_batch).compile()
        state, metrics = compiled(state, device_batch)  # warmup
        jax.block_until_ready(metrics["loss"])
        compile_s = time.perf_counter() - t0
        _account("compile", compile_s)
        _mark(f"compile+first step done in {compile_s:.1f}s; timing "
              f"{n_iters} iters")

        tracing = (
            jax.profiler.trace(profile_dir)
            if profile_dir
            else contextlib.nullcontext()
        )
        t0 = time.perf_counter()
        with tracing:
            for _ in range(n_iters):
                state, metrics = compiled(state, device_batch)
            loss_val = float(metrics["loss"])
        dt = time.perf_counter() - t0
        _account("step", dt)
        _mark(f"timed loop done in {dt:.1f}s")

    tokens_per_step = grad_accum * micro_bs * config.seq_len
    per_chip = tokens_per_step * n_iters / dt / n_chips
    per_chip_flops = per_chip * profiling.flops_per_token(config)
    mfu = per_chip_flops / peak

    # XLA's own accounting for the compiled step: how many FLOPs/bytes the
    # schedule actually executes vs the PaLM-convention model count — the
    # ratio localizes an MFU gap (masked-window attention waste, remat
    # recompute, optimizer elementwise traffic) without a trace viewer.
    xla_cost = None
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        model_flops_step = profiling.flops_per_token(config) * tokens_per_step
        xla_flops = float(ca.get("flops", 0.0))
        xla_bytes = float(ca.get("bytes accessed", 0.0))
        if xla_flops > 0:
            xla_cost = {
                "flops_per_step": xla_flops,
                "bytes_accessed_per_step": xla_bytes,
                "arithmetic_intensity": round(xla_flops / xla_bytes, 1)
                if xla_bytes else None,
                # >1.0 means XLA schedules more FLOPs than the model
                # convention counts (bwd of fwd-only ops, masked waste…)
                "flops_vs_model_count": round(
                    xla_flops / model_flops_step, 3
                ),
            }
    except Exception as e:  # diagnostic only: never fail a timed phase
        _mark(f"cost_analysis unavailable: {e!r}")
    # which measured kernel combo this config's attention actually traced
    # under (ADVICE r3: make the silently-applied policy visible per phase)
    attn_policy = None
    if config.use_pallas_attn:
        from progen_tpu.ops.pallas_attention import policy_decision

        attn_policy = policy_decision(
            config.window_size, n=config.seq_len, bh=micro_bs * config.heads
        )
    return {
        "phase": f"train-{config_name}"
        + ("-pallas" if use_pallas else "-xla" if use_pallas is False else "")
        + phase_suffix,
        "config": config_name,
        "tokens_per_sec_per_chip": round(per_chip, 1),
        "mfu": round(mfu, 4),
        "step_ms": round(1000 * dt / n_iters, 1),
        "compile_s": round(compile_s, 1),
        "num_params": state.num_params(),
        "batch": f"{grad_accum}x{micro_bs}x{config.seq_len}",
        "dtype": config.dtype,
        "use_pallas_attn": config.use_pallas_attn,
        "scan_layers": config.scan_layers,
        "loss": round(loss_val, 4),
        "chips": n_chips,
        **({"attn_policy": attn_policy} if attn_policy else {}),
        **({"xla_cost": xla_cost} if xla_cost else {}),
        **_suspect_fields(per_chip_flops, 1.0, peak),  # per_chip_flops is /s
        **_hbm_stats(),
        "platform": jax.devices()[0].platform,
    }


def _price_kernel_combos(fwd_cands: dict, bwd_only: dict, t_xb: float):
    """Pick the deployed (fwd, bwd) kernel combo by pricing the FULL grid,
    each candidate with the forward time of the forward impl it ACTUALLY
    pairs (t_xf for xla-fwd combos, the g-batched fwd time for pallas_gN)
    — a global argmin, so near-tie winners aren't decided greedily on the
    forward alone.

    fwd_cands: {"xla": t_xf, "pallas_g1": t, "pallas_g<N>": t, ...} fwd
      times (s). bwd_only: {impl: t} pallas backward-only costs (the
      measured grad pipelines are pallas-g1-fwd + that bwd, so bwd-only =
      t_pb[impl] - t_pf). t_xb: the PLAIN XLA autodiff grad pipeline
      (fwd+bwd total).

    Special cases: fwd=xla + bwd=xla is plain local_attention by the model
    dispatch (no custom-VJP recompute), priced at t_xb; a bwd="xla" escape
    hatch under a pallas-fwd custom VJP re-runs the whole XLA forward
    inside the backward (~t_xb on top of the deployed forward, not
    t_xb - t_xf).

    Returns (best_fwd_key, fwd_win, bwd_win)."""
    combos = {("xla", "xla"): t_xb}
    for fkey, ftime in fwd_cands.items():
        for impl, bcost in bwd_only.items():
            combos[(fkey, impl)] = ftime + bcost
        if fkey != "xla":
            combos[(fkey, "xla")] = ftime + t_xb
    best_fwd_key, bwd_win = min(combos, key=combos.get)
    return best_fwd_key, ("xla" if best_fwd_key == "xla" else "pallas"), bwd_win


def _kernel_bench(window: int, n: int = 1024) -> dict:
    """Pallas windowed-attention kernel vs the XLA path, fwd+bwd, at the
    flagship shapes. On TPU the kernel is Mosaic-COMPILED (interpret only
    off-TPU) and the on-chip error vs the XLA golden is recorded — the
    non-interpret correctness evidence VERDICT round-2 asked for.

    A clean on-chip run records its winners, keyed by the measured
    (window, n, batch*heads), in a policy-shaped table under runs/
    (_MEASURED_POLICY_PATH) — the evidence a change promoting a row into
    the tracked ops/pallas_policy.json cites."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.ops.attention import local_attention
    from progen_tpu.ops.pallas_attention import pallas_local_attention

    # phase label = the SCHEDULED name (requested shape), even when the
    # off-TPU smoke shrinks the shapes
    phase_name = f"kernel-w{window}" + (f"-n{n}" if n != 1024 else "")
    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    if on_tpu:
        # n=1024: the tiny/default train shapes (bh=128). n=8192: the
        # long8k shapes — batch shrinks to the long8k recipe's micro-batch
        # so bh matches what the train step actually runs (bh=16).
        b, h, d = (16, 8, 64) if n <= 2048 else (2, 8, 64)
        iters_f, iters_b = 20, 10
        w = window
    else:
        # interpret-mode Pallas is minutes/call at TPU shapes — keep the
        # off-TPU path a functional smoke, not a perf claim
        b, h, n, d = 2, 2, 128, 32
        iters_f, iters_b = 2, 1
        w = min(window, 32)
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (b, h, n, d), jnp.bfloat16) for kk in ks)

    def time_fn(fn, iters):
        out = fn(q, k, v)  # compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(q, k, v)
        jax.block_until_ready(out)  # in-order device stream: all iters must finish
        return (time.perf_counter() - t0) / iters, out

    xla_fwd = jax.jit(lambda q, k, v: local_attention(q, k, v, window_size=w))
    pl_fwd = jax.jit(
        lambda q, k, v: pallas_local_attention(q, k, v, w, None, not on_tpu)
    )
    xla_bwd = jax.jit(
        jax.grad(lambda q, k, v: local_attention(q, k, v, window_size=w)
                 .astype(jnp.float32).sum(), argnums=(0, 1, 2))
    )

    def pl_bwd(impl):
        return jax.jit(
            jax.grad(
                lambda q, k, v: pallas_local_attention(
                    q, k, v, w, None, not on_tpu, impl
                ).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )
        )

    t_xf, o_x = time_fn(xla_fwd, iters_f)
    t_pf, o_p = time_fn(pl_fwd, iters_f)
    fwd_err = float(
        jnp.abs(o_x.astype(jnp.float32) - o_p.astype(jnp.float32)).max()
    )
    # forward bh_block variants: g batch-heads per program (fatter blocks,
    # fewer programs — the small-window perf lever). VMEM caps g at w=512.
    from progen_tpu.ops.pallas_attention import _safe_bh_block

    fwd_ms_g = {}
    timed_gs = {1}  # the plain pallas row above is g=1
    for g_try in (4, 8):
        g_eff = _safe_bh_block(g_try, b * h, w)  # VMEM cap / divisibility
        if g_eff in timed_gs:  # e.g. w=512 caps 8 -> 4: don't re-time
            continue
        timed_gs.add(g_eff)
        pl_fwd_g = jax.jit(
            lambda q, k, v, g_=g_eff: pallas_local_attention(
                q, k, v, w, None, not on_tpu, "kv", g_
            )
        )
        t_g, o_g = time_fn(pl_fwd_g, iters_f)
        err_g = float(
            jnp.abs(o_x.astype(jnp.float32) - o_g.astype(jnp.float32)).max()
        )
        fwd_ms_g[f"pallas_g{g_eff}"] = {  # label = EFFECTIVE g
            "ms": round(t_g * 1e3, 3),
            "max_err": err_g,
        }
    t_xb, g_x = time_fn(xla_bwd, iters_b)
    # both pallas backwards: kv (combined-in-register) vs halo (f32
    # scratch + shifted add) — the on-chip winner informs the default
    t_pb = {}
    bwd_err = {}
    bwd_impls = ["kv", "halo"]
    # batched kv variants (same lever as the forward's bh_block; VMEM cap
    # uses n_probs=2 — two probability tensors live per program)
    timed_bwd_gs = {1}
    for g_try in (4, 8):
        g_eff = _safe_bh_block(g_try, b * h, w, n_probs=2)
        if g_eff not in timed_bwd_gs:
            timed_bwd_gs.add(g_eff)
            bwd_impls.append(f"kv_g{g_eff}")
    for impl in bwd_impls:
        t_pb[impl], g_p = time_fn(pl_bwd(impl), iters_b)
        bwd_err[impl] = max(
            float(
                jnp.abs(a.astype(jnp.float32) - b_.astype(jnp.float32)).max()
            )
            for a, b_ in zip(g_x, g_p)
        )
    best = min(t_pb, key=t_pb.get)
    from progen_tpu import profiling as _prof

    peak = _prof.peak_flops(jax.devices()[0])
    # score + value einsums, 2 FLOP/MAC, ctx = 2w per query
    fwd_flops = 2 * 2 * b * h * n * (2 * w) * d
    bwd_flops = 2 * fwd_flops  # dq,dk,dv reuse both einsums (lower bound)
    t_pf_best = min([t_pf] + [v["ms"] / 1e3 for v in fwd_ms_g.values()])
    fwd_guard = _suspect_fields(fwd_flops, min(t_xf, t_pf_best), peak)
    bwd_guard = _suspect_fields(bwd_flops, min(t_xb, *t_pb.values()), peak)
    suspect = fwd_guard["timing_suspect"] or bwd_guard["timing_suspect"]

    fwd_cands = {"xla": t_xf, "pallas_g1": t_pf,
                 # fwd_ms_g keys are already "pallas_g<N>"
                 **{k: v["ms"] / 1e3 for k, v in fwd_ms_g.items()}}
    bwd_only = {impl: max(t - t_pf, 1e-9) for impl, t in t_pb.items()}
    best_fwd_key, fwd_win, bwd_win = _price_kernel_combos(
        fwd_cands, bwd_only, t_xb
    )
    policy_entry = {
        "window": w, "n": n, "bh": b * h,
        "fwd": fwd_win,
        "bwd": bwd_win,  # "xla" / "kv" / "halo" / "kv_g<N>"
        "bh_block": (1 if best_fwd_key in ("xla", "pallas_g1")
                     else int(best_fwd_key.rsplit("_g", 1)[1])),
    }
    # never adopt a fast-but-WRONG kernel: the policy only learns from
    # runs whose on-chip error vs the XLA golden is within bf16 tolerance
    max_bwd_err = max(bwd_err.values()) if bwd_err else 0.0
    numerics_ok = fwd_err <= 1e-2 and max_bwd_err <= 5e-2
    policy_recorded = False
    if on_tpu and not suspect and numerics_ok:
        from progen_tpu.ops.pallas_attention import record_policy_entry

        record_policy_entry({
            **policy_entry,
            "fwd_ms": {k: round(v * 1e3, 3) for k, v in fwd_cands.items()},
            "bwd_ms": {"xla_full": round(t_xb * 1e3, 3),
                       **{k: round(v * 1e3, 3)
                          for k, v in bwd_only.items()}},
            "source": f"bench {phase_name}"
                      + time.strftime(" %Y-%m-%d", time.gmtime()),
        }, _MEASURED_POLICY_PATH)
        policy_recorded = True
    return {
        "phase": phase_name,
        "fwd_ms": {
            "xla": round(t_xf * 1e3, 3),
            "pallas": round(t_pf * 1e3, 3),
            **{k: v["ms"] for k, v in fwd_ms_g.items()},
        },
        "fwd_bh_block_err": {k: v["max_err"] for k, v in fwd_ms_g.items()},
        "bwd_ms": {
            "xla": round(t_xb * 1e3, 3),
            **{f"pallas_{impl}": round(t * 1e3, 3)
               for impl, t in t_pb.items()},
        },
        "fwd_speedup": round(t_xf / t_pf_best, 2),  # best pallas variant
        "bwd_speedup": round(t_xb / t_pb[best], 2),
        "bwd_best_impl": best,
        "fwd_max_abs_err": fwd_err,
        "bwd_max_abs_err": bwd_err,  # per impl: a regression in the
                                     # slower one must stay visible
        "shape": f"b{b} h{h} n{n} d{d} w{w} bf16",
        "policy_entry": policy_entry,
        "policy_recorded": policy_recorded,
        "policy_numerics_ok": numerics_ok,
        "timing_suspect": suspect,
        "implied_device_tflops": {
            "fwd_fastest": fwd_guard["implied_device_tflops"],
            "bwd_fastest": bwd_guard["implied_device_tflops"],
        },
        "mosaic_compiled": on_tpu,
        "platform": jax.devices()[0].platform,
    }


def _sgu_mix_bench() -> dict:
    """Dense tril-masked vs recursive block-triangular SGU mix at the
    long8k shapes, fwd+bwd — isolates the sgu_block_size optimization
    (the long8k train phases both run with it on)."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.ops.sgu import causal_sgu_mix

    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    n, d_half, b = (8192, 1024, 2) if on_tpu else (256, 64, 1)
    block = 1024 if on_tpu else 32
    iters = 10 if on_tpu else 3
    gate = jax.random.normal(jax.random.PRNGKey(0), (b, n, d_half),
                             jnp.bfloat16)
    w = jax.random.normal(jax.random.PRNGKey(1), (n, n), jnp.float32) / n
    bias = jnp.ones((n, 1), jnp.float32)

    def timed(block_size, bwd):
        if bwd:
            fn = jax.jit(
                jax.grad(
                    lambda g, w: causal_sgu_mix(g, w, bias, block_size)
                    .astype(jnp.float32).sum(),
                    argnums=(0, 1),
                )
            )
        else:
            fn = jax.jit(
                lambda g, w: causal_sgu_mix(g, w, bias, block_size)
            )
        jax.block_until_ready(fn(gate, w))  # compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = fn(gate, w)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / iters

    t_dense_f, t_block_f = timed(0, False), timed(block, False)
    t_dense_b, t_block_b = timed(0, True), timed(block, True)
    from progen_tpu import profiling as _prof

    peak = _prof.peak_flops(jax.devices()[0])
    dense_fwd_flops = 2 * b * n * n * d_half  # (n,n) mix, 2 FLOP/MAC
    guard = _suspect_fields(
        dense_fwd_flops, min(t_dense_f, t_block_f / 0.6), peak
    )  # blocked does ~0.6x dense MACs at these shapes
    return {
        "phase": "sgu-mix",
        "timing_suspect": guard["timing_suspect"],
        "implied_device_tflops": guard["implied_device_tflops"],
        "shape": f"b{b} n{n} d{d_half} block{block}",
        "fwd_ms": {
            "dense": round(t_dense_f * 1e3, 3),
            "blocked": round(t_block_f * 1e3, 3),
        },
        "bwd_ms": {
            "dense": round(t_dense_b * 1e3, 3),
            "blocked": round(t_block_b * 1e3, 3),
        },
        "fwd_speedup": round(t_dense_f / t_block_f, 2),
        "bwd_speedup": round(t_dense_b / t_block_b, 2),
        "platform": jax.devices()[0].platform,
    }


def _fused_kernel_bench(block: int) -> dict:
    """Fused Pallas layer kernels (ops/pallas_layers.py) vs their
    unfused XLA references, fwd+bwd: the shift->norm halo kernel and the
    SGU mix+gate kernel that keeps the normalized gate VMEM-resident
    across norm/causal-mix/gating and skips the structurally-zero upper
    triangle in-grid. On TPU a clean run (numerics pass, timings not
    suspect) records the measured winners as layer_entries under runs/
    (_MEASURED_POLICY_PATH); off-TPU the kernels run in interpret mode — a
    functional smoke whose timings are never policy evidence."""
    import jax
    import jax.numpy as jnp

    from progen_tpu.ops.pallas_layers import (
        fused_norm_shift,
        fused_sgu_mix_gate,
        norm_shift_reference,
        record_layer_policy_entry,
        sgu_mix_gate_reference,
    )

    phase = f"kernel-fused-w{block}"
    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    interpret = not on_tpu
    if on_tpu:
        b, n, d, d_half, iters, bn = 4, 1024, 512, 1024, 10, block
    else:  # smoke shapes: interpret mode is minutes/iter at TPU shapes
        b, n, d, d_half, iters, bn = 2, 128, 64, 64, 3, min(block, 32)
    eps = 1e-5
    kx, kxg, kg, kw = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(kx, (b, n, d), jnp.bfloat16)
    scale = jnp.full((d,), 1.1, jnp.float32)
    xg = jax.random.normal(kxg, (b, n, d_half), jnp.bfloat16)
    gate = jax.random.normal(kg, (b, n, d_half), jnp.bfloat16)
    gscale = jnp.full((d_half,), 0.9, jnp.float32)
    w = jax.random.normal(kw, (n, n), jnp.float32) / n
    bias = jnp.ones((n, 1), jnp.float32)
    _mark(f"{phase}: b{b} n{n} d{d} dh{d_half} bn{bn} "
          f"interpret={interpret}")

    def ns_fused(x, s):
        return fused_norm_shift(x, s, eps, bn, interpret, "bfloat16")

    def ns_ref(x, s):
        return norm_shift_reference(x, s, eps, "bfloat16")

    def sgu_fused(x, g, w, s):
        return fused_sgu_mix_gate(x, g, w, bias, s, eps, bn, interpret,
                                  "bfloat16")

    def sgu_ref(x, g, w, s):
        return sgu_mix_gate_reference(x, g, w, bias, s, eps, "bfloat16")

    def timed(fn, *args, bwd=False):
        if bwd:
            def loss(*a):
                return fn(*a).astype(jnp.float32).sum()

            run = jax.jit(jax.grad(loss, argnums=tuple(range(len(args)))))
        else:
            run = jax.jit(fn)
        t0 = time.perf_counter()
        out = run(*args)
        jax.block_until_ready(out)
        _account("compile", time.perf_counter() - t0)
        t0 = time.perf_counter()
        for _ in range(iters):
            out = run(*args)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        _account("step", dt)
        return dt / iters

    # numerics BEFORE timing: a fast wrong kernel must never become a
    # policy winner (bf16 paths are bit-identical by construction; the
    # tolerance covers f32-accumulation reassociation only)
    err_ns = float(jnp.max(jnp.abs(
        ns_fused(x, scale).astype(jnp.float32)
        - ns_ref(x, scale).astype(jnp.float32)
    )))
    err_sgu = float(jnp.max(jnp.abs(
        sgu_fused(xg, gate, w, gscale).astype(jnp.float32)
        - sgu_ref(xg, gate, w, gscale).astype(jnp.float32)
    )))
    numerics_ok = err_ns <= 0.05 and err_sgu <= 0.05

    t_ns_ref_f = timed(ns_ref, x, scale)
    t_ns_fused_f = timed(ns_fused, x, scale)
    t_ns_ref_b = timed(ns_ref, x, scale, bwd=True)
    t_ns_fused_b = timed(ns_fused, x, scale, bwd=True)
    _mark(f"{phase}: norm_shift timed "
          f"(fwd {t_ns_ref_f * 1e3:.2f} -> {t_ns_fused_f * 1e3:.2f} ms)")
    t_sgu_ref_f = timed(sgu_ref, xg, gate, w, gscale)
    t_sgu_fused_f = timed(sgu_fused, xg, gate, w, gscale)
    t_sgu_ref_b = timed(sgu_ref, xg, gate, w, gscale, bwd=True)
    t_sgu_fused_b = timed(sgu_fused, xg, gate, w, gscale, bwd=True)
    _mark(f"{phase}: sgu timed "
          f"(fwd {t_sgu_ref_f * 1e3:.2f} -> {t_sgu_fused_f * 1e3:.2f} ms)")

    from progen_tpu import profiling as _prof

    peak = _prof.peak_flops(jax.devices()[0])
    dense_flops = 2 * b * n * n * d_half  # dense (n, n) mix, 2 FLOP/MAC
    guard = _suspect_fields(
        dense_flops, min(t_sgu_ref_f, t_sgu_fused_f / 0.5), peak
    )  # fused does ~0.5x dense MACs (tril-only grid)

    policy_written = False
    if on_tpu and numerics_ok and not guard["timing_suspect"]:
        record_layer_policy_entry({
            "kind": "norm_shift", "n": n, "d": d,
            "impl": "pallas" if t_ns_fused_f <= t_ns_ref_f else "xla",
            "block": bn,
            "fwd_ms": {"xla": round(t_ns_ref_f * 1e3, 3),
                       "pallas": round(t_ns_fused_f * 1e3, 3)},
            "bwd_ms": {"xla": round(t_ns_ref_b * 1e3, 3),
                       "pallas": round(t_ns_fused_b * 1e3, 3)},
            "source": phase,
        }, _MEASURED_POLICY_PATH)
        record_layer_policy_entry({
            "kind": "sgu_mix", "n": n, "d": d_half,
            "impl": "pallas" if t_sgu_fused_f <= t_sgu_ref_f else "xla",
            "block": bn,
            "fwd_ms": {"xla": round(t_sgu_ref_f * 1e3, 3),
                       "pallas": round(t_sgu_fused_f * 1e3, 3)},
            "bwd_ms": {"xla": round(t_sgu_ref_b * 1e3, 3),
                       "pallas": round(t_sgu_fused_b * 1e3, 3)},
            "source": phase,
        }, _MEASURED_POLICY_PATH)
        policy_written = True

    return {
        "phase": phase,
        "timing_suspect": guard["timing_suspect"],
        "implied_device_tflops": guard["implied_device_tflops"],
        "shape": f"b{b} n{n} d{d} dh{d_half} bn{bn}",
        "interpret": interpret,
        # headline speedups = the SGU kernel (the O(n^2) one): the
        # main() summary contract for kernel phases reads these keys
        "fwd_speedup": round(t_sgu_ref_f / t_sgu_fused_f, 2),
        "bwd_speedup": round(t_sgu_ref_b / t_sgu_fused_b, 2),
        "norm_shift": {
            "fwd_ms": {"xla": round(t_ns_ref_f * 1e3, 3),
                       "pallas": round(t_ns_fused_f * 1e3, 3)},
            "bwd_ms": {"xla": round(t_ns_ref_b * 1e3, 3),
                       "pallas": round(t_ns_fused_b * 1e3, 3)},
            "fwd_speedup": round(t_ns_ref_f / t_ns_fused_f, 2),
            "bwd_speedup": round(t_ns_ref_b / t_ns_fused_b, 2),
            "max_abs_err": err_ns,
        },
        "sgu_mix": {
            "fwd_ms": {"xla": round(t_sgu_ref_f * 1e3, 3),
                       "pallas": round(t_sgu_fused_f * 1e3, 3)},
            "bwd_ms": {"xla": round(t_sgu_ref_b * 1e3, 3),
                       "pallas": round(t_sgu_fused_b * 1e3, 3)},
            "fwd_speedup": round(t_sgu_ref_f / t_sgu_fused_f, 2),
            "bwd_speedup": round(t_sgu_ref_b / t_sgu_fused_b, 2),
            "max_abs_err": err_sgu,
        },
        "numerics_ok": numerics_ok,
        "policy_written": policy_written,
        "platform": jax.devices()[0].platform,
        **_hbm_stats(),
    }


def _calib_bench() -> dict:
    """Timing calibration: a chained bf16 matmul with KNOWN FLOPs. Each
    iteration consumes the previous result. On a real v5e the 4096-cube
    matmul should land at a large fraction of the 197 bf16 TFLOP/s peak —
    and NEVER above it: the on-chip check that the suite's timing
    methodology measures compute, not dispatch."""
    import jax
    import jax.numpy as jnp

    from progen_tpu import profiling

    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    n = 4096 if on_tpu else 256
    chain_len, iters = 8, 10

    @jax.jit
    def chain(x, b):
        for _ in range(chain_len):
            x = x @ b
        return x

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    a = jax.random.normal(k1, (n, n), jnp.bfloat16)
    # 1/sqrt(n) keeps the chain magnitude-STABLE (variance-preserving):
    # a 1/n scale underflows bf16 to exact zeros ~21 multiplies in, and a
    # zero-operand chain is a weaker proof that compute actually ran
    b = jax.random.normal(k2, (n, n), jnp.bfloat16) / jnp.sqrt(
        jnp.float32(n)
    ).astype(jnp.bfloat16)
    jax.block_until_ready(chain(a, b))  # compile
    t0 = time.perf_counter()
    x = a
    for _ in range(iters):
        x = chain(x, b)
    jax.block_until_ready(x)
    dt = time.perf_counter() - t0

    flops = iters * chain_len * 2 * n**3
    peak = profiling.peak_flops(jax.devices()[0])
    if peak is None:
        raise RuntimeError("calib-matmul calibrates against a TPU's peak")
    achieved = flops / dt
    return {
        "phase": "calib-matmul",
        "shape": f"{n}x{n} bf16, chain {chain_len} x {iters} iters",
        "achieved_tflops": round(achieved / 1e12, 1),
        "peak_tflops": round(peak / 1e12, 1),
        "mxu_efficiency": round(achieved / peak, 3),
        "timing_suspect": bool(achieved > 1.1 * peak),
        "platform": jax.devices()[0].platform,
    }


def _sustain_bench() -> dict:
    """Sustained training on the ~205M base config with a mid-run async
    checkpoint and an exactness-checked restore — the closest this
    single-chip box gets to the production claim: steady-state
    tokens/sec/chip over 100+ steps under real HBM pressure, checkpoint
    machinery engaged, resume continuing the identical loss trajectory
    (ref train.py:179-222 is the loop this hardens). Artifact:
    runs/sustain_base_metrics.jsonl (per-chunk timings + losses)."""
    import shutil

    import jax

    from progen_tpu import profiling
    from progen_tpu.checkpoint import (
        Package,
        get_checkpoint_fns,
        sharded_abstract_state,
    )
    from progen_tpu.models.progen import ProGen
    from progen_tpu.parallel.partition import make_mesh, put_batch
    from progen_tpu.training.optimizer import make_optimizer
    from progen_tpu.training.step import (
        abstract_train_state,
        compile_train_step,
        init_train_state,
        train_state_shardings,
    )

    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    if on_tpu:
        config = _load_config("base")
        grad_accum, micro_bs = _RECIPES["base"][:2]
        target_steps, ckpt_at, resume_steps, chunk = 120, 60, 10, 10
    else:
        config = _load_config("smoke")
        grad_accum, micro_bs = 2, 2
        target_steps, ckpt_at, resume_steps, chunk = 8, 4, 2, 2
    deadline = float(os.environ.get("BENCH_PHASE_DEADLINE_SEC", 1170))
    t_start = time.perf_counter()

    mesh = make_mesh()
    model = ProGen(config)
    optimizer = make_optimizer()
    state, shardings = init_train_state(
        model, optimizer, jax.random.PRNGKey(0), config.seq_len, mesh=mesh
    )
    _mark("sustain: state initialized")
    step = compile_train_step(model, optimizer, state, shardings, mesh)

    # rotating synthetic batches: zero host input cost, deterministic
    # stream so the post-restore step can replay the EXACT batch the
    # original trajectory saw (turning resume into an on-chip exactness
    # check, not just liveness)
    rng = np.random.default_rng(0)
    n_rot = 4
    host_batches = [
        rng.integers(1, config.num_tokens,
                     size=(grad_accum, micro_bs, config.seq_len + 1)
                     ).astype(np.int32)
        for _ in range(n_rot)
    ]

    ckpt_dir = _REPO / "runs" / "sustain_ckpt"
    shutil.rmtree(ckpt_dir, ignore_errors=True)
    reset_ckpt, get_last, save_ckpt = get_checkpoint_fns(
        str(ckpt_dir), keep_last_n=2, async_save=True
    )

    metrics_path = _LOG_DIR.parent / "sustain_base_metrics.jsonl"
    metrics_path.parent.mkdir(parents=True, exist_ok=True)
    records = []
    tokens_per_step = grad_accum * micro_bs * config.seq_len

    with mesh:
        batches = [
            put_batch(b, mesh, accum_axis=True) for b in host_batches
        ]
        t0 = time.perf_counter()
        state, m = step(state, batches[0])  # compile + step 1
        jax.block_until_ready(m["loss"])
        compile_s = time.perf_counter() - t0
        _account("compile", compile_s)
        _mark(f"sustain: compile+step1 in {compile_s:.1f}s")

        steps_done = 1
        ckpt_block_s = None
        loss_after_ckpt = None  # original trajectory's step ckpt_at+1
        chunk_rows = []
        while steps_done < target_steps:
            if time.perf_counter() - t_start > 0.6 * deadline:
                _mark(f"sustain: wall budget at {steps_done} steps")
                break
            n = min(chunk, target_steps - steps_done)
            t0 = time.perf_counter()
            for _ in range(n):
                state, m = step(state, batches[steps_done % n_rot])
                steps_done += 1
            jax.block_until_ready(m["loss"])
            dt = time.perf_counter() - t0
            _account("step", dt)
            row = {
                "step": steps_done,
                "chunk_steps": n,
                "tokens_per_sec": round(tokens_per_step * n / dt, 1),
                "loss": round(float(m["loss"]), 4),
            }
            chunk_rows.append(row)
            records.append(row)
            if ckpt_block_s is None and steps_done >= ckpt_at:
                t0 = time.perf_counter()
                save_ckpt(Package(
                    next_seq_index=steps_done,
                    state=state,
                    model_config=config.to_dict(),
                    run_id=None,
                ))
                ckpt_block_s = time.perf_counter() - t0
                _account("checkpoint", ckpt_block_s)
                _mark(f"sustain: async ckpt at step {steps_done} "
                      f"(blocked {ckpt_block_s:.2f}s)")
                # the step the restore must reproduce bit-for-bit
                state, m = step(state, batches[steps_done % n_rot])
                steps_done += 1
                jax.block_until_ready(m["loss"])
                loss_after_ckpt = float(m["loss"])

        # steady state = median chunk AFTER warmup/ckpt chunks
        tail = [r["tokens_per_sec"] for r in chunk_rows[1:]] or [
            r["tokens_per_sec"] for r in chunk_rows
        ]
        steady = float(np.median(tail)) if tail else 0.0
        final_loss = float(m["loss"])

        save_ckpt.close()  # publish the pending async snapshot
        restore_ok, resume_delta, restore_s = False, None, None
        if ckpt_block_s is not None:
            t0 = time.perf_counter()
            boxed, abstract = abstract_train_state(
                model, optimizer, config.seq_len
            )
            r_shardings = train_state_shardings(boxed, mesh)
            pkg = get_last(sharded_abstract_state(abstract, r_shardings))
            restore_s = time.perf_counter() - t0
            _account("checkpoint", restore_s)
            _mark(f"sustain: restore in {restore_s:.1f}s from step "
                  f"{pkg.next_seq_index}")
            r_state = pkg.state
            r_step = step(r_state, batches[pkg.next_seq_index % n_rot])
            r_state, r_m = r_step
            jax.block_until_ready(r_m["loss"])
            resume_delta = abs(float(r_m["loss"]) - loss_after_ckpt)
            restore_ok = resume_delta < 1e-5
            for i in range(resume_steps - 1):
                r_state, r_m = step(
                    r_state, batches[(pkg.next_seq_index + 1 + i) % n_rot]
                )
            jax.block_until_ready(r_m["loss"])
            records.append({
                "resumed": True,
                "resume_loss_delta": resume_delta,
                "resume_final_loss": round(float(r_m["loss"]), 4),
            })

    with open(metrics_path, "w") as f:
        for r in records:
            f.write(json.dumps(r) + "\n")
    shutil.rmtree(ckpt_dir, ignore_errors=True)

    peak = profiling.peak_flops(jax.devices()[0])
    per_chip_flops = steady * profiling.flops_per_token(config)
    return {
        "phase": "sustain-base",
        "config": "base" if on_tpu else "smoke",
        "steps": steps_done,
        "steady_tokens_per_sec_per_chip": round(steady, 1),
        "mfu": round(per_chip_flops / peak, 4),
        "compile_s": round(compile_s, 1),
        "final_loss": round(final_loss, 4),
        "ckpt_block_s": (round(ckpt_block_s, 2)
                         if ckpt_block_s is not None else None),
        "restore_s": (round(restore_s, 1) if restore_s is not None
                      else None),
        "resume_loss_delta": resume_delta,
        "resume_exact": restore_ok,
        "metrics_artifact": str(metrics_path),
        **_suspect_fields(per_chip_flops, 1.0, peak),
        **_hbm_stats(),
        "platform": jax.devices()[0].platform,
    }


def _decode_bench() -> dict:
    """Autoregressive decode throughput on the flagship config (BASELINE.md
    config 5): the KV-cache fused decode (sample_fast) vs the
    reference-shaped full-forward-per-token path (sample), same Gumbel
    top-k semantics, annotation-style prime."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from progen_tpu.data.tokenizer import encode_tokens
    from progen_tpu.models.progen import ProGen
    from progen_tpu.sampling import sample, sample_fast, sample_fast_batched

    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    # half-context tiny on TPU: three separate decoder jits compile in this
    # phase, and the full-length naive decode once blew the phase window.
    # The SGU binds the forward to seq_len, so the model itself is built
    # at the shorter length.
    config = (
        _load_config("tiny", seq_len=512)
        if on_tpu
        else _load_config("smoke")
    )
    model = ProGen(config)
    tokens = jnp.zeros((1, config.seq_len), jnp.int32)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    )
    prime = jnp.asarray(encode_tokens("[tax=Mammalia] #"), jnp.int32)
    length = config.seq_len
    key = jax.random.PRNGKey(7)

    def run(fn):
        t0 = time.perf_counter()
        out = fn(key, model, params, prime, length, 25, True)
        jax.block_until_ready(out)
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = fn(jax.random.PRNGKey(8), model, params, prime, length, 25, True)
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        gen = length - int(prime.shape[0]) - 1
        return gen / dt, compile_s, out

    fast_tps, fast_compile, out_fast = run(sample_fast)
    naive_tps, naive_compile, out_naive = run(sample)

    # batched KV-cache decode: aggregate tokens/sec over a batch of primes
    # through ONE shared cache loop (the MXU-throughput decode mode)
    bsz = 8
    primes_b = jnp.tile(prime[None], (bsz, 1))
    batched_tps, _, _ = run(
        lambda k, m, p, pr, ln, tk, ab: sample_fast_batched(
            k, m, p, primes_b, ln, tk, ab
        )
    )
    batched_tps *= bsz
    from progen_tpu import profiling as _prof

    peak = _prof.peak_flops(jax.devices()[0])
    # fwd-only flops/token = (6N convention)/3; the naive path pays a full
    # length-n forward per generated token
    fwd_tok = _prof.flops_per_token(config) / 3
    guard = _suspect_fields(
        max(batched_tps * fwd_tok, naive_tps * length * fwd_tok),
        1.0,
        peak,
    )
    return {
        "phase": "decode-tiny",
        "timing_suspect": guard["timing_suspect"],
        "implied_device_tflops": guard["implied_device_tflops"],
        "config": "tiny-seq512" if on_tpu else "smoke",
        "kv_cache_tokens_per_sec": round(fast_tps, 1),
        "kv_batched8_tokens_per_sec": round(batched_tps, 1),
        "naive_tokens_per_sec": round(naive_tps, 1),
        "speedup": round(fast_tps / naive_tps, 2),
        "batch_scaling": round(batched_tps / fast_tps, 2),
        "bit_identical": bool(jnp.array_equal(out_fast, out_naive)),
        "gen_length": int(length - prime.shape[0] - 1),
        "compile_s": {
            "kv_cache": round(fast_compile, 1),
            "naive": round(naive_compile, 1),
        },
        "platform": jax.devices()[0].platform,
    }


def _decode_serve_bench() -> dict:
    """Continuous-batching serving engine (progen_tpu/serving/) under
    staggered arrivals: steady-state decode tokens/s across the slot
    pool and per-request time-to-first-token. One warmup request pays
    both compiles (prefill + decode step) OUTSIDE the measured window;
    the engine's decode_step reads its outputs back to the host every
    iteration, so the timings are honest host-observed wall clock."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from progen_tpu.data.tokenizer import encode_tokens
    from progen_tpu.models.progen import ProGen
    from progen_tpu.serving import (
        Request,
        Scheduler,
        ServeEngine,
        ServingMetrics,
    )

    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    # same shape policy as decode-tiny: half-context tiny on TPU (three
    # jits already blew a full-length phase window once), smoke on CPU
    config = (
        _load_config("tiny", seq_len=512)
        if on_tpu
        else _load_config("smoke")
    )
    max_slots = 8 if on_tpu else 4
    n_requests = 16 if on_tpu else 8
    model = ProGen(config)
    tokens = jnp.zeros((1, config.seq_len), jnp.int32)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    )
    prime = jnp.asarray(encode_tokens("[tax=Mammalia] #"), jnp.int32)

    _mark(f"serve init: slots={max_slots} seq_len={config.seq_len}")
    engine = ServeEngine(model, params, max_slots=max_slots,
                         max_len=config.seq_len)
    sched = Scheduler(engine, max_queue=2 * n_requests)

    # warmup: one short request end-to-end = both compiles + cache init
    t0 = time.perf_counter()
    ok, _ = sched.submit(
        Request(id="warm", prime=prime, length=int(prime.shape[0]) + 8,
                add_bos=True, key=jax.random.PRNGKey(0))
    )
    assert ok
    sched.run_to_completion(max_steps=2000)
    compile_s = time.perf_counter() - t0
    _mark(f"serve warm in {compile_s:.1f}s")

    # measured window on fresh metrics: staggered arrivals — half the
    # load up front, the rest dripped in one per 4 decode steps, so the
    # pool sees admissions landing mid-flight (the continuous-batching
    # case, not a static batch)
    sched.metrics = metrics = ServingMetrics()
    gen_len = int(config.seq_len) if on_tpu else 96
    reqs = [
        Request(
            id=f"r{i}", prime=prime,
            # mixed lengths: 50%..100% of the window
            length=int(prime.shape[0]) + 1
            + max(8, (gen_len - int(prime.shape[0]) - 1)
                  * (2 + i % 3) // 4),
            add_bos=True, key=jax.random.PRNGKey(100 + i),
            temperature=(0.8 if i % 3 == 1 else 1.0),
            top_p=(0.95 if i % 3 == 2 else None),
        )
        for i in range(n_requests)
    ]
    pending = list(reqs)
    for req in pending[: n_requests // 2]:
        ok, reason = sched.submit(req)
        assert ok, reason
    pending = pending[n_requests // 2:]
    t0 = time.perf_counter()
    steps = 0
    completions = []
    while sched.has_work or pending:
        if pending and steps % 4 == 0:
            ok, reason = sched.submit(pending.pop(0))
            assert ok, reason
        _, comp = sched.step()
        completions.extend(comp)
        steps += 1
        if steps % 100 == 0:
            _mark(f"serve step {steps}: {len(completions)}/{n_requests}")
        if steps > 100000:
            raise RuntimeError("serving bench failed to drain")
    wall = time.perf_counter() - t0
    m = metrics.snapshot()
    _mark(f"serve drained: {steps} steps in {wall:.1f}s")

    from progen_tpu import profiling as _prof

    peak = _prof.peak_flops(jax.devices()[0])
    fwd_tok = _prof.flops_per_token(config) / 3
    guard = _suspect_fields(
        m.get("decode_tokens_per_s", 0.0) * fwd_tok, 1.0, peak
    )
    return {
        "phase": "decode-serve",
        "timing_suspect": guard["timing_suspect"],
        "implied_device_tflops": guard["implied_device_tflops"],
        "config": "tiny-seq512" if on_tpu else "smoke",
        "max_slots": max_slots,
        "n_requests": n_requests,
        "completed": int(m.get("requests_completed", 0)),
        "steady_state_tokens_per_sec": round(
            m.get("decode_tokens_per_s", 0.0), 1
        ),
        "wall_tokens_per_sec": round(
            m.get("decode_tokens", 0.0) / max(wall, 1e-9), 1
        ),
        "prefill_tokens_per_sec": round(
            m.get("prefill_tokens_per_s", 0.0), 1
        ),
        "ttft_mean_s": round(m.get("ttft_s_mean_s", 0.0), 4),
        "ttft_p50_s": round(m.get("ttft_s_p50_s", 0.0), 4),
        "ttft_p95_s": round(m.get("ttft_s_p95_s", 0.0), 4),
        "ttft_p99_s": round(m.get("ttft_s_p99_s", 0.0), 4),
        "ttft_max_s": round(m.get("ttft_s_max_s", 0.0), 4),
        "request_latency_mean_s": round(
            m.get("latency_s_mean_s", 0.0), 4
        ),
        "request_latency_p99_s": round(
            m.get("latency_s_p99_s", 0.0), 4
        ),
        "decode_steps": int(m.get("decode_steps", 0)),
        "mean_occupancy": round(
            m.get("decode_tokens", 0.0)
            / max(m.get("decode_steps", 1.0), 1.0),
            2,
        ),
        "compile_s": round(compile_s, 1),
        "platform": jax.devices()[0].platform,
        **_hbm_stats(),
    }


def _decode_admit_stall_bench() -> dict:
    """The admission-stall number the chunked-prefill work exists to
    move: decode ITL p99 for live requests WHILE a long prompt admits.

    Two runs of the same scenario — live decoders, then a long-prime
    request submitted mid-flight — one on the monolithic scheduler
    (``prefill_chunk=0``: the whole prefill lands inside one step, and
    every live decoder's next token waits behind it) and one chunked
    (at most ``chunk`` prime tokens between decode steps). Headline
    ``value`` = monolithic ITL p99 / chunked ITL p99 — dimensionless,
    >1 means chunking wins, and the bench gate ratchets it
    (``--metric serve_admit_stall_ratio``).

    Second number: ``prefix_cache_speedup`` = cold TTFT / cache-hit
    TTFT for the same scaffold on a quiet engine (``--metric
    serve_prefix_cache_speedup``). Both are ratios of host-observed
    wall clock on the SAME process/platform, so they are honest on CPU
    smoke shapes too — which is why tier1.yml can enforce them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import linen as nn

    from progen_tpu.models.progen import ProGen
    from progen_tpu.serving import (
        PrefixCache,
        Request,
        Scheduler,
        ServeEngine,
    )

    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    # longer window than the other smoke phases: the signal IS the
    # admission stall, and on CPU per-step dispatch overhead (~1-2 ms)
    # would swamp a short prime's prefill. ~270 feed positions makes
    # the monolithic stall step several times a decode step.
    config = (
        _load_config("tiny", seq_len=512)
        if on_tpu
        else _load_config("smoke", seq_len=384)
    )
    chunk = 16 if on_tpu else 8
    n_decoders = 3
    repeats = 3
    model = ProGen(config)
    tokens = jnp.zeros((1, config.seq_len), jnp.int32)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    )
    rng = np.random.RandomState(7)
    # the admission under test: a prime filling ~70% of the window, so
    # its monolithic prefill dwarfs one decode step
    long_prime = rng.randint(
        1, config.num_tokens, size=int(config.seq_len * 0.7)
    ).astype(np.int32)
    short_prime = rng.randint(1, config.num_tokens, size=6).astype(np.int32)

    # fixed-size measurement window covering the WHOLE admission on both
    # paths (monolithic admits in one step; chunked across ~prime/chunk
    # steps) — identical sample counts keep the two p99s comparable
    window = max(28, len(long_prime) // chunk + 8)

    def _measure(prefill_chunk, prefix_cache):
        """ITL samples (s) for live decoders across the admission
        window of the long request, on a fresh engine+scheduler."""
        engine = ServeEngine(model, params, max_slots=n_decoders + 1,
                             max_len=config.seq_len)
        sched = Scheduler(engine, max_queue=16,
                          prefill_chunk=prefill_chunk,
                          prefix_cache=prefix_cache)
        # warmup pays this path's full compile set (prefill or
        # chunk+finish, plus decode) outside the measured window
        ok, _ = sched.submit(Request(
            id="warm", prime=long_prime[:12], length=20,
            key=jax.random.PRNGKey(0),
        ))
        assert ok
        sched.run_to_completion(max_steps=4000)
        # decoders live through the window plus slack, no longer — the
        # post-measurement drain is dead time
        dec_len = min(int(config.seq_len) - 2,
                      len(short_prime) + 1 + window + 24)
        for i in range(n_decoders):
            ok, reason = sched.submit(Request(
                id=f"dec{i}", prime=short_prime, length=dec_len,
                key=jax.random.PRNGKey(100 + i),
            ))
            assert ok, reason
        for _ in range(6):  # decoders provably in steady state
            sched.step()
        ok, reason = sched.submit(Request(
            id="long", prime=long_prime,
            length=len(long_prime) + 16,
            key=jax.random.PRNGKey(999),
        ))
        assert ok, reason
        itl = []
        admitted = False
        while len(itl) < window:
            t0 = time.perf_counter()
            sched.step()
            itl.append(time.perf_counter() - t0)
            admitted = admitted or not (
                sched._queue or sched._pending is not None
            )
        assert admitted, "window too short: admission never completed"
        sched.run_to_completion(max_steps=20000)
        return itl

    # interleaved repeats, median of per-repeat p99s: one stall sample
    # against a machine-noise p99 would be a coin flip on a busy CPU
    # runner; the median of three interleaved pairs is not
    p99s_mono, p99s_chunk = [], []
    itl_mono, itl_chunk = [], []
    for rep in range(repeats):
        _mark(f"admit-stall: repeat {rep + 1}/{repeats} monolithic")
        itl = _measure(0, None)
        p99s_mono.append(float(np.percentile(itl, 99)))
        itl_mono.extend(itl)
        _mark(f"admit-stall: repeat {rep + 1}/{repeats} chunked")
        itl = _measure(chunk, None)
        p99s_chunk.append(float(np.percentile(itl, 99)))
        itl_chunk.extend(itl)
    p99_mono = float(np.median(p99s_mono))
    p99_chunk = float(np.median(p99s_chunk))
    stall_ratio = p99_mono / max(p99_chunk, 1e-9)
    _mark(f"admit-stall: p99 mono={p99_mono:.4f}s "
          f"chunk={p99_chunk:.4f}s ratio={stall_ratio:.2f}")

    # prefix-cache TTFT: same scaffold cold then hot on a quiet engine.
    # Same max_slots as the measurement engines — the finish program's
    # pool shape stays cached, so cold TTFT is admission cost, not a
    # recompile
    cache = PrefixCache(256 << 20)
    engine = ServeEngine(model, params, max_slots=n_decoders + 1,
                         max_len=config.seq_len)
    sched = Scheduler(engine, max_queue=4, prefill_chunk=chunk,
                      prefix_cache=cache)

    def _ttft(rid):
        ok, reason = sched.submit(Request(
            id=rid, prime=long_prime, length=len(long_prime) + 12,
            key=jax.random.PRNGKey(1234),
        ))
        assert ok, reason
        t0 = time.perf_counter()
        while True:
            ev, _ = sched.step()
            if any(e.request_id == rid for e in ev):
                ttft = time.perf_counter() - t0
                break
        sched.run_to_completion(max_steps=20000)
        return ttft

    # compile warmup for THIS engine already paid: same jits, same
    # shapes as the measurement engines above (process-level jit cache)
    ttft_cold = _ttft("cold")
    ttft_hit = _ttft("hot")
    speedup = ttft_cold / max(ttft_hit, 1e-9)
    st = cache.stats()
    _mark(f"admit-stall: ttft cold={ttft_cold:.3f}s hit={ttft_hit:.3f}s "
          f"speedup={speedup:.2f} (cache hits={st['hits']})")

    return {
        "phase": "decode-admit-stall",
        "metric": "serve_admit_stall_ratio",
        "value": round(stall_ratio, 3),
        "prefix_cache_speedup": round(speedup, 3),
        "config": "tiny-seq512" if on_tpu else "smoke",
        "prefill_chunk": chunk,
        "prime_tokens": int(len(long_prime)),
        "n_decoders": n_decoders,
        "itl_p99_monolithic_s": round(p99_mono, 5),
        "itl_p99_chunked_s": round(p99_chunk, 5),
        "itl_mean_monolithic_s": round(float(np.mean(itl_mono)), 5),
        "itl_mean_chunked_s": round(float(np.mean(itl_chunk)), 5),
        "ttft_cold_s": round(ttft_cold, 4),
        "ttft_hit_s": round(ttft_hit, 4),
        "prefix_cache_hits": int(st["hits"]),
        "prefix_cache_hit_tokens": int(
            sched.metrics.snapshot().get("prefix_cache_hit_tokens", 0)
        ),
        "platform": jax.devices()[0].platform,
        **_hbm_stats(),
    }


def _transport_overhead_bench() -> dict:
    """Framed-TCP loopback vs unix-socket serving: the cost of the
    length-prefixed frame envelope (progen_tpu/fleet/transport.py) on
    the two client-visible numbers, TTFT and streamed tokens/s.

    Two REAL ``cli/serve`` subprocesses (smoke shapes, pinned to CPU so
    the phase never fights the suite's chip claim) serve the identical
    request set — once over ``--socket``, once over ``--tcp`` on
    loopback — with one warmup request paying both compiles outside
    each measured window. Model compute is identical on both sides, so
    the ratios isolate the transport. Headline ``value`` =
    min(tcp/unix tokens-per-sec ratio, unix/tcp TTFT ratio) — the
    conservative parity number, ~1.0 when framing is free, and the
    bench gate ratchets it (``--metric serve_transport_parity``).
    Host-side by construction: honest on any runner, which is why
    tier1.yml can enforce it."""
    import re as _re
    import select
    import signal as _signal
    import socket
    import tempfile

    import jax
    import jax.numpy as jnp
    from flax.core import meta

    from progen_tpu.checkpoint import Package, get_checkpoint_fns
    from progen_tpu.config import ProGenConfig
    from progen_tpu.fleet.transport import (
        FrameDecoder,
        encode_frame,
        fleet_token,
        parse_hostport,
    )
    from progen_tpu.models.progen import ProGen

    n_requests = 8
    gen_length = 20
    config = ProGenConfig(
        num_tokens=256, dim=32, seq_len=32, depth=2, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2,
        dtype="float32",
    )

    def _measure(transport, root, ck):
        """One serve subprocess + one client connection; returns TTFT,
        tokens/s, and the full (id -> [(index, token)]) streams."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PROGEN_CHAOS", None)
        env["PYTHONPATH"] = f"{_REPO}{os.pathsep}" + env.get(
            "PYTHONPATH", ""
        )
        spath = str(root / f"{transport}.sock")
        args = [
            sys.executable, "-m", "progen_tpu.cli.serve",
            "--checkpoint_path", str(ck),
            "--max-slots", "4", "--max-queue", "32", "--max-len", "28",
            "--journal_dir", str(root / f"jd_{transport}"),
        ]
        args += (["--socket", spath] if transport == "unix"
                 else ["--tcp", "127.0.0.1:0"])
        err_path = root / f"{transport}.err"
        proc = subprocess.Popen(
            args, stdout=subprocess.DEVNULL,
            stderr=open(err_path, "w"), env=env,
        )
        try:
            # endpoint discovery: serve prints "listening on ..." once
            # the transport is bound (the ephemeral-port handshake)
            endpoint = None
            deadline = time.time() + 180
            while time.time() < deadline and endpoint is None:
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"serve died: {err_path.read_text()[-2000:]}"
                    )
                m = _re.search(
                    r"listening on (?:tcp )?(\S+)",
                    err_path.read_text(),
                )
                if m:
                    endpoint = m.group(1)
                else:
                    time.sleep(0.2)
            if endpoint is None:
                raise RuntimeError(f"{transport} serve never listened")

            auth = fleet_token()
            if transport == "unix":
                sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
                sock.connect(spath)
                dec = None
            else:
                host, port = parse_hostport(endpoint)
                sock = socket.create_connection((host, port), timeout=5)
                sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1
                )
                dec = FrameDecoder(auth=auth, peer="bench")
            state = {"buf": b""}

            def send_req(obj):
                line = json.dumps(obj)
                if dec is None:
                    sock.sendall(line.encode() + b"\n")
                else:
                    sock.sendall(encode_frame(line, auth=auth))

            def pump_until_done(want, timeout_s):
                """Drain events until every id in ``want`` is done;
                each event is stamped with its host arrival time."""
                events, got = [], set()
                stop = time.time() + timeout_s
                while time.time() < stop and not want <= got:
                    r, _, _ = select.select([sock], [], [], 0.5)
                    if not r:
                        continue
                    data = sock.recv(65536)
                    if not data:
                        break
                    if dec is not None:
                        raws = dec.feed(data)
                    else:
                        state["buf"] += data
                        *full, state["buf"] = state["buf"].split(b"\n")
                        raws = [f.decode() for f in full if f.strip()]
                    now = time.perf_counter()
                    for raw in raws:
                        ev = json.loads(raw)
                        ev["_t"] = now
                        events.append(ev)
                        if ev.get("event") == "done":
                            got.add(ev["id"])
                if not want <= got:
                    raise RuntimeError(
                        f"{transport}: undone after {timeout_s}s: "
                        f"{sorted(want - got)}"
                    )
                return events

            # warmup: both compiles + cache init outside the window
            t0 = time.perf_counter()
            send_req({"id": "warm", "prime": "MKV", "length": 12,
                      "seed": 1})
            pump_until_done({"warm"}, 300)
            compile_s = time.perf_counter() - t0
            _mark(f"transport {transport}: warm in {compile_s:.1f}s")

            submits = {}
            for i in range(n_requests):
                rid = f"r{i}"
                submits[rid] = time.perf_counter()
                send_req({"id": rid, "prime": "MKV",
                          "length": gen_length, "seed": 70 + i})
            events = pump_until_done(set(submits), 300)

            first, streams, n_tokens = {}, {}, 0
            for ev in events:
                if ev.get("event") != "token":
                    continue
                n_tokens += 1
                first.setdefault(ev["id"], ev["_t"])
                streams.setdefault(ev["id"], []).append(
                    (ev["index"], ev["token"])
                )
            wall = max(ev["_t"] for ev in events) - min(submits.values())
            ttfts = [first[r] - submits[r] for r in submits]
            sock.close()
            return {
                "ttft_mean_s": sum(ttfts) / len(ttfts),
                "tokens_per_sec": n_tokens / max(wall, 1e-9),
                "tokens": n_tokens,
                "streams": streams,
                "compile_s": compile_s,
            }
        finally:
            if proc.poll() is None:
                proc.send_signal(_signal.SIGTERM)  # graceful drain
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=10)

    with tempfile.TemporaryDirectory() as td:
        root = Path(td)
        model = ProGen(config)
        variables = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, config.seq_len), jnp.int32),
        )
        params = meta.unbox(variables)["params"]
        _, _, save = get_checkpoint_fns(str(root / "ck"))
        save(Package(0, {"params": params}, config.to_dict(),
                     "transport-bench"))
        _mark(f"transport: checkpoint saved, {n_requests} reqs/side")

        unix = _measure("unix", root, root / "ck")
        tcp = _measure("tcp", root, root / "ck")

    tps_ratio = tcp["tokens_per_sec"] / max(unix["tokens_per_sec"], 1e-9)
    ttft_ratio = unix["ttft_mean_s"] / max(tcp["ttft_mean_s"], 1e-9)
    value = min(tps_ratio, ttft_ratio)
    _mark(f"transport: tps_ratio={tps_ratio:.3f} "
          f"ttft_ratio={ttft_ratio:.3f}")
    return {
        "phase": "transport-overhead",
        "metric": "serve_transport_parity",
        "value": round(value, 3),
        "host_side": True,
        "timing_suspect": False,
        "config": "smoke-serve32",
        "n_requests": n_requests,
        "tokens_per_sec_ratio": round(tps_ratio, 3),
        "ttft_ratio": round(ttft_ratio, 3),
        "unix_ttft_mean_s": round(unix["ttft_mean_s"], 4),
        "tcp_ttft_mean_s": round(tcp["ttft_mean_s"], 4),
        "unix_tokens_per_sec": round(unix["tokens_per_sec"], 1),
        "tcp_tokens_per_sec": round(tcp["tokens_per_sec"], 1),
        # transport must not touch the sampled streams: same seeds,
        # same tokens, bit for bit
        "bit_identical": tcp["streams"] == unix["streams"],
        "compile_s": {
            "unix": round(unix["compile_s"], 1),
            "tcp": round(tcp["compile_s"], 1),
        },
        "platform": "host",
    }


def _transport_overhead_safe() -> dict:
    """_transport_overhead_bench that degrades to an error record
    instead of killing the run (it spawns serve subprocesses)."""
    try:
        return _transport_overhead_bench()
    except Exception as e:
        return {"phase": "transport-overhead", "error": repr(e)[:300]}


def _flight_overhead_bench() -> dict:
    """Armed vs disarmed flight recorder on real serving: the cost of
    the always-on black box (progen_tpu/telemetry/flight.py — an
    EMIT_TAPS hook that appends every telemetry record into a bounded
    in-memory ring) on the two client-visible numbers, streamed
    tokens/s and decode ITL p99.

    Two REAL ``cli/serve`` subprocesses (smoke shapes, pinned to CPU so
    the phase never fights the suite's chip claim) serve the identical
    request set over a unix socket — once with ``--flight_dir`` armed,
    once without — with one warmup request paying the compile outside
    each measured window. Model compute and transport are identical on
    both sides, so the ratios isolate the tap. Headline ``value`` =
    min(armed/disarmed tokens-per-sec ratio, disarmed/armed ITL-p99
    ratio) — the conservative parity number, ~1.0 when the recorder is
    free; the forensics contract is that it stays within ~1% of free,
    and the bench gate ratchets it (``--metric flight_overhead_ratio``).
    Host-side by construction: honest on any runner, which is why
    tier1.yml can enforce it."""
    import select
    import signal as _signal
    import socket
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax.core import meta

    from progen_tpu.checkpoint import Package, get_checkpoint_fns
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen

    n_requests = 8
    gen_length = 24
    config = ProGenConfig(
        num_tokens=256, dim=32, seq_len=32, depth=2, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2,
        dtype="float32",
    )

    def _measure(side, armed, root, ck):
        """One serve subprocess + one unix-socket client; returns
        tokens/s, ITL p99, and the (id -> [(index, token)]) streams."""
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env.pop("PROGEN_CHAOS", None)
        env["PYTHONPATH"] = f"{_REPO}{os.pathsep}" + env.get(
            "PYTHONPATH", ""
        )
        spath = str(root / f"{side}.sock")
        args = [
            sys.executable, "-m", "progen_tpu.cli.serve",
            "--checkpoint_path", str(ck),
            "--max-slots", "4", "--max-queue", "32", "--max-len", "32",
            "--journal_dir", str(root / f"jd_{side}"),
            "--socket", spath,
        ]
        if armed:
            args += ["--flight_dir", str(root / f"flight_{side}")]
        err_path = root / f"{side}.err"
        proc = subprocess.Popen(
            args, stdout=subprocess.DEVNULL,
            stderr=open(err_path, "w"), env=env,
        )
        try:
            deadline = time.time() + 180
            while time.time() < deadline and not os.path.exists(spath):
                if proc.poll() is not None:
                    raise RuntimeError(
                        f"serve died: {err_path.read_text()[-2000:]}"
                    )
                time.sleep(0.2)
            if not os.path.exists(spath):
                raise RuntimeError(f"{side} serve never listened")

            sock = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
            sock.connect(spath)
            state = {"buf": b""}

            def send_req(obj):
                sock.sendall(json.dumps(obj).encode() + b"\n")

            def pump_until_done(want, timeout_s):
                events, got = [], set()
                stop = time.time() + timeout_s
                while time.time() < stop and not want <= got:
                    r, _, _ = select.select([sock], [], [], 0.5)
                    if not r:
                        continue
                    data = sock.recv(65536)
                    if not data:
                        break
                    state["buf"] += data
                    *full, state["buf"] = state["buf"].split(b"\n")
                    now = time.perf_counter()
                    for raw in full:
                        if not raw.strip():
                            continue
                        ev = json.loads(raw)
                        ev["_t"] = now
                        events.append(ev)
                        if ev.get("event") == "done":
                            got.add(ev["id"])
                if not want <= got:
                    raise RuntimeError(
                        f"{side}: undone after {timeout_s}s: "
                        f"{sorted(want - got)}"
                    )
                return events

            t0 = time.perf_counter()
            send_req({"id": "warm", "prime": "MKV", "length": 12,
                      "seed": 1})
            pump_until_done({"warm"}, 300)
            compile_s = time.perf_counter() - t0
            _mark(f"flight {side}: warm in {compile_s:.1f}s")

            submits = {}
            for i in range(n_requests):
                rid = f"r{i}"
                submits[rid] = time.perf_counter()
                send_req({"id": rid, "prime": "MKV",
                          "length": gen_length, "seed": 70 + i})
            events = pump_until_done(set(submits), 300)

            arrivals, streams, n_tokens = {}, {}, 0
            for ev in events:
                if ev.get("event") != "token":
                    continue
                n_tokens += 1
                arrivals.setdefault(ev["id"], []).append(ev["_t"])
                streams.setdefault(ev["id"], []).append(
                    (ev["index"], ev["token"])
                )
            wall = max(ev["_t"] for ev in events) - min(submits.values())
            itl = [
                b - a
                for ts in arrivals.values()
                for a, b in zip(ts, ts[1:])
                if b > a  # same-recv batches carry one stamp
            ]
            sock.close()
            return {
                "tokens_per_sec": n_tokens / max(wall, 1e-9),
                "itl_p99_s": (
                    float(np.percentile(itl, 99)) if itl else 0.0
                ),
                "tokens": n_tokens,
                "streams": streams,
                "compile_s": compile_s,
            }
        finally:
            if proc.poll() is None:
                proc.send_signal(_signal.SIGTERM)  # graceful drain
                try:
                    proc.wait(timeout=60)
                except subprocess.TimeoutExpired:
                    proc.kill()
                    proc.wait(timeout=10)

    with tempfile.TemporaryDirectory() as td:
        root = Path(td)
        model = ProGen(config)
        variables = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, config.seq_len), jnp.int32),
        )
        params = meta.unbox(variables)["params"]
        _, _, save = get_checkpoint_fns(str(root / "ck"))
        save(Package(0, {"params": params}, config.to_dict(),
                     "flight-bench"))
        _mark(f"flight: checkpoint saved, {n_requests} reqs/side")

        # interleave-free A/B: disarmed first (the baseline), then armed
        off = _measure("disarmed", False, root, root / "ck")
        on = _measure("armed", True, root, root / "ck")

    tps_ratio = on["tokens_per_sec"] / max(off["tokens_per_sec"], 1e-9)
    itl_ratio = off["itl_p99_s"] / max(on["itl_p99_s"], 1e-9)
    value = min(tps_ratio, itl_ratio)
    _mark(f"flight: tps_ratio={tps_ratio:.3f} itl_ratio={itl_ratio:.3f}")
    return {
        "phase": "flight-overhead",
        "metric": "flight_overhead_ratio",
        "value": round(value, 3),
        "host_side": True,
        "timing_suspect": False,
        "config": "smoke-serve32",
        "n_requests": n_requests,
        "tokens_per_sec_ratio": round(tps_ratio, 3),
        "itl_p99_ratio": round(itl_ratio, 3),
        "disarmed_tokens_per_sec": round(off["tokens_per_sec"], 1),
        "armed_tokens_per_sec": round(on["tokens_per_sec"], 1),
        "disarmed_itl_p99_s": round(off["itl_p99_s"], 5),
        "armed_itl_p99_s": round(on["itl_p99_s"], 5),
        # the ring tap must not touch the sampled streams: same seeds,
        # same tokens, bit for bit
        "bit_identical": on["streams"] == off["streams"],
        "compile_s": {
            "disarmed": round(off["compile_s"], 1),
            "armed": round(on["compile_s"], 1),
        },
        "platform": "host",
    }


def _decode_int8_bench() -> dict:
    """Int8 weight-quantized decode (ops/quant.py, --int8 on the serve
    CLI) vs the full-precision engine built from the SAME params: decode
    tokens/s for each, the speedup, greedy-window token agreement, and
    the calibration report the engine computed at load. Decode is
    HBM-bandwidth-bound, so the win only shows on chip; off-TPU smoke
    shapes prove function and agreement, not the bandwidth claim."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from progen_tpu.data.tokenizer import encode_tokens
    from progen_tpu.models.progen import ProGen
    from progen_tpu.serving import ServeEngine

    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    config = (
        _load_config("tiny", seq_len=512)
        if on_tpu
        else _load_config("smoke")
    )
    max_slots = 8 if on_tpu else 4
    steps = 64 if on_tpu else 16
    model = ProGen(config)
    tokens = jnp.zeros((1, config.seq_len), jnp.int32)
    params = nn.meta.unbox(
        jax.jit(model.init)(jax.random.PRNGKey(0), tokens)["params"]
    )
    prime = jnp.asarray(encode_tokens("[tax=Mammalia] #"), jnp.int32)
    gen_len = min(int(config.seq_len),
                  int(prime.shape[0]) + 1 + steps + 8)

    streams: dict = {}
    results: dict = {}
    engines: dict = {}
    for label in ("fp", "int8"):
        _mark(f"decode-int8: building {label} engine")
        t0 = time.perf_counter()
        eng = ServeEngine(model, params, max_slots=max_slots,
                          max_len=config.seq_len,
                          quantize_int8=(label == "int8"))
        # same keys per slot in both engines -> streams comparable
        for s in range(max_slots):
            eng.prefill(s, prime, gen_len,
                        key=jax.random.PRNGKey(7 + s))
        eng.decode_step()  # warmup: pays the decode-step compile
        _account("compile", time.perf_counter() - t0)
        seq = []
        live_tokens = 0
        t0 = time.perf_counter()
        for _ in range(steps):
            sampled, was_live, _fin = eng.decode_step()
            seq.append((sampled, was_live))
            live_tokens += int(was_live.sum())
        wall = time.perf_counter() - t0
        _account("step", wall)
        streams[label] = seq
        engines[label] = eng
        results[label] = {
            "tokens_per_sec": round(live_tokens / max(wall, 1e-9), 1),
            "live_tokens": live_tokens,
            "wall_s": wall,
        }
        _mark(f"decode-int8: {label} "
              f"{results[label]['tokens_per_sec']} tok/s")

    agree = total = 0
    for (sa, la), (sb, lb) in zip(streams["fp"], streams["int8"]):
        both = la & lb
        total += int(both.sum())
        agree += int((sa[both] == sb[both]).sum())

    report = dict(engines["int8"].quant_report or {})
    report.pop("leaves", None)  # per-leaf detail stays in the engine log

    from progen_tpu import profiling as _prof

    peak = _prof.peak_flops(jax.devices()[0])
    fwd_tok = _prof.flops_per_token(config) / 3
    guard = _suspect_fields(
        results["fp"]["live_tokens"] * fwd_tok,
        results["fp"]["wall_s"], peak,
    )
    return {
        "phase": "decode-int8",
        "timing_suspect": guard["timing_suspect"],
        "implied_device_tflops": guard["implied_device_tflops"],
        "config": "tiny-seq512" if on_tpu else "smoke",
        "max_slots": max_slots,
        "decode_steps": steps,
        "int8_tokens_per_sec": results["int8"]["tokens_per_sec"],
        "fp_tokens_per_sec": results["fp"]["tokens_per_sec"],
        "speedup": round(
            results["int8"]["tokens_per_sec"]
            / max(results["fp"]["tokens_per_sec"], 1e-9), 2
        ),
        "token_agreement": round(agree / max(total, 1), 4),
        "tokens_compared": total,
        "calibration": report,
        "platform": jax.devices()[0].platform,
        **_hbm_stats(),
    }


def _workload_model():
    """(model, params, config) for the protein-design workload phases —
    the decode-tiny sizing rule: half-context tiny on TPU, smoke on CPU,
    random params (throughput does not care what the weights say)."""
    import jax
    import jax.numpy as jnp
    from flax import linen as nn

    from progen_tpu.models.progen import ProGen

    on_tpu = _is_tpu_platform(jax.devices()[0].platform)
    config = (
        _load_config("tiny", seq_len=512)
        if on_tpu
        else _load_config("smoke")
    )
    model = ProGen(config)
    params = nn.meta.unbox(
        jax.jit(model.init)(
            jax.random.PRNGKey(0), jnp.zeros((1, config.seq_len), jnp.int32)
        )["params"]
    )
    return model, params, config, on_tpu


def _batch_score_bench() -> dict:
    """Bulk perplexity-scoring throughput (workloads/scoring.py): a
    synthetic candidate set through the bucketed compile-once score_step
    into sharded JSONL. The workload's own time ledger separates compile
    from steady-state, so seqs/s and goodput are the steady answer a
    screening run would see."""
    import shutil
    import tempfile

    import jax

    from progen_tpu import profiling
    from progen_tpu.workloads import AA_ALPHABET, run_batch_score

    model, params, config, on_tpu = _workload_model()
    rng = np.random.default_rng(0)
    n_seqs = 256 if on_tpu else 64
    aas = np.array(list(AA_ALPHABET))
    records = []
    for i in range(n_seqs):
        n = int(rng.integers(config.seq_len // 4, config.seq_len - 3))
        seq = "".join(rng.choice(aas, size=n))
        records.append((f"b{i}", ("# " + seq).encode("utf-8")))

    out_dir = tempfile.mkdtemp(prefix="bench-score-")
    try:
        summary = run_batch_score(
            model, params, records, out_dir,
            batch_size=8, logprobs=False, resume=False,
        )
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)

    peak = profiling.peak_flops(jax.devices()[0])
    fwd_tok = profiling.flops_per_token(config) / 3  # fwd-only convention
    step_s = max(summary["times"]["step"], 1e-9)
    guard = _suspect_fields(summary["tokens"] * fwd_tok / step_s, 1.0, peak)
    return {
        "phase": "batch-score",
        "config": "tiny-seq512" if on_tpu else "smoke",
        "n_scored": summary["n_scored"],
        "seqs_per_sec": round(summary["n_scored"] / step_s, 1),
        "tokens_per_sec": round(summary["tokens"] / step_s, 1),
        "goodput_pct": summary["goodput_pct"],
        "batches": summary["batches"],
        "times": summary["times"],
        **guard,
        "platform": jax.devices()[0].platform,
        **_hbm_stats(),
    }


def _mutagenesis_bench() -> dict:
    """Vmapped deep-mutational-scan throughput (workloads/mutagenesis.py):
    every L x 20 point mutant of one synthetic protein in one compiled
    program. First call is billed to compile; the re-scan of a different
    region (same shapes, traced operands) is the steady number."""
    import jax

    from progen_tpu import profiling
    from progen_tpu.workloads import AA_ALPHABET, mutagenesis_scan

    model, params, config, on_tpu = _workload_model()
    rng = np.random.default_rng(0)
    L = min(96 if on_tpu else 48, config.seq_len - 8)
    sequence = "".join(rng.choice(np.array(list(AA_ALPHABET)), size=L))
    half = list(range(L // 2))

    t0 = time.perf_counter()
    mutagenesis_scan(model, params, sequence, positions=half, chunk=32)
    compile_s = time.perf_counter() - t0
    # same shapes, different positions: re-executes without retracing
    other = list(range(L // 2, L - (L % 2)))[: len(half)]
    t0 = time.perf_counter()
    report = mutagenesis_scan(model, params, sequence, positions=other,
                              chunk=32)
    dt = time.perf_counter() - t0

    n_mutants = report["nll"].size
    peak = profiling.peak_flops(jax.devices()[0])
    fwd_tok = profiling.flops_per_token(config) / 3
    # every mutant row is a full seq_len forward (padded training layout)
    guard = _suspect_fields(
        n_mutants * config.seq_len * fwd_tok / max(dt, 1e-9), 1.0, peak
    )
    return {
        "phase": "mutagenesis",
        "config": "tiny-seq512" if on_tpu else "smoke",
        "seq_len_scanned": L,
        "n_mutants": n_mutants,
        "mutants_per_sec": round(n_mutants / max(dt, 1e-9), 1),
        "scan_s": round(dt, 3),
        "compile_s": round(compile_s, 1),
        **guard,
        "platform": jax.devices()[0].platform,
        **_hbm_stats(),
    }


def _data_io_bench() -> dict:
    """Host-side input-pipeline throughput: the from-scratch TFRecord
    codec (write + parse) and the C++ engine vs the pure-Python path, plus
    native batch collation — at Uniref50-like record sizes. No chip
    involved (platform "host", exempt from the TPU gate): this is the
    runtime the reference delegates to tf.data, measured as the framework
    component it is."""
    import gzip
    import tempfile

    rng = np.random.default_rng(0)
    n_rec = 20000
    seqs = [
        bytes(rng.integers(65, 90, size=int(L)).astype(np.uint8))
        for L in rng.integers(200, 1024, size=n_rec)
    ]
    total_mb = sum(len(s) for s in seqs) / 1e6

    from progen_tpu.data import _native
    from progen_tpu.data.dataset import collate as py_collate
    from progen_tpu.data.tfrecord import read_tfrecords, tfrecord_writer

    with tempfile.TemporaryDirectory() as td:
        path = f"{td}/bench.{n_rec}.tfrecord.gz"
        t0 = time.perf_counter()
        with tfrecord_writer(path) as write:
            for s in seqs:
                write(s)
        t_write = time.perf_counter() - t0

        t0 = time.perf_counter()
        out = list(read_tfrecords(path))
        t_py = time.perf_counter() - t0
        assert len(out) == n_rec and out[0] == seqs[0]

        lib = _native.load()
        t_cc = None
        if lib is not None:
            with gzip.open(path, "rb") as f:
                raw = f.read()
            t0 = time.perf_counter()
            out_cc = _native.parse_file(raw)
            t_cc = time.perf_counter() - t0
            assert list(out_cc) == out

        t0 = time.perf_counter()
        py_collate(out[:4096], 1024)
        t_collate = time.perf_counter() - t0

    return {
        "phase": "data-io",
        "host_side": True,
        "records": n_rec,
        "payload_mb": round(total_mb, 1),
        "write_mb_s": round(total_mb / t_write, 1),
        "parse_py_records_s": round(n_rec / t_py, 0),
        "parse_py_mb_s": round(total_mb / t_py, 1),
        **(
            {
                "parse_native_records_s": round(n_rec / t_cc, 0),
                "parse_native_mb_s": round(total_mb / t_cc, 1),
                "native_speedup": round(t_py / t_cc, 2),
            }
            if t_cc is not None
            else {"native_speedup": None}
        ),
        "collate_4096x1024_ms": round(t_collate * 1e3, 1),
        "platform": "host",
    }


def _large_projection() -> dict:
    """ProGen-large (1.2B) sharding study — no chip run: the optimizer
    state alone (f32 params + AdamW m/v = 12 B/param) plus transient f32
    grads exceeds one v5e chip's 16 GB HBM, so the BASELINE.md target for
    this config is the v5e-64 plan, reported from closed-form math."""
    from progen_tpu import profiling
    from progen_tpu.config import ProGenConfig, load_toml_config

    cfg = ProGenConfig.from_dict(
        load_toml_config(str(_REPO / "configs" / "model" / "large.toml"))
    )
    p = cfg.num_params()
    state_bytes = 12 * p      # f32 params + Adam m + v
    grads_bytes = 4 * p       # transient f32 grads (donated step)
    fpt = profiling.flops_per_token(cfg)
    peak = 197e12             # v5e bf16
    # v5e-64 mesh plan: model=8 (qkv/mlp/vocab sharded), data=8
    model_ax, data_ax = 8, 8
    per_chip_state = (state_bytes + grads_bytes) / model_ax
    # --zero1: AdamW m+v (8 B/param) shard over data as well
    per_chip_zero1 = (
        4 * p / model_ax            # f32 params
        + 8 * p / (model_ax * data_ax)  # moments
        + grads_bytes / model_ax
    )
    target_mfu = 0.45
    projected_tps_chip = target_mfu * peak / fpt
    return {
        "phase": "large-projection",
        "config": "large",
        "num_params": p,
        "state_plus_grads_gb": round((state_bytes + grads_bytes) / 2**30, 2),
        "hbm_fit_single_chip": False,
        "mesh_plan": {"data": 8, "model": model_ax, "seq": 1},
        "per_chip_state_gb_at_model8": round(per_chip_state / 2**30, 2),
        "per_chip_state_gb_at_model8_zero1": round(
            per_chip_zero1 / 2**30, 2
        ),
        "flops_per_token": fpt,
        "projected_tokens_per_sec_per_chip_at_45pct_mfu": round(
            projected_tps_chip, 1
        ),
        "note": "single v5e chip cannot hold 1.2B x 16B/param; "
                "remat+scan_layers in large.toml; TP rules shard "
                "qkv/mlp/vocab over `model`, GSPMD inserts one all-reduce "
                "per block (partition.py rule table)",
    }


def _data_io_safe() -> dict:
    """_data_io_bench that degrades to an error record instead of killing
    the run (it builds the C++ engine on first use)."""
    try:
        return _data_io_bench()
    except Exception as e:
        return {"phase": "data-io", "error": repr(e)[:300]}


def run_phase(name: str) -> dict:
    if name.startswith("kernel-fused-w"):
        return _fused_kernel_bench(int(name[len("kernel-fused-w"):]))
    if name.startswith("kernel-w"):
        # "kernel-w<W>" or "kernel-w<W>-n<N>" (long-context shape variant)
        spec = name[len("kernel-w"):].split("-n")
        return _kernel_bench(
            int(spec[0]), int(spec[1]) if len(spec) > 1 else 1024
        )
    if name == "train-tiny-pallas":
        # scan_layers: one scanned body = ~3 embedded Mosaic kernel
        # instances instead of the unrolled stack's 12+. Compare against
        # train-tiny-scan, its XLA twin with the same layer structure.
        return _train_bench("tiny", use_pallas=True,
                            extra_overrides={"scan_layers": True})
    if name == "train-tiny-scan":
        return _train_bench("tiny", phase_suffix="-scan",
                            extra_overrides={"scan_layers": True})
    if name == "profile-tiny":
        # on-chip trace artifact for offline schedule analysis (where the
        # step's time actually goes — the MFU-gap question cost_analysis
        # can't answer). Loses its timing honesty to profiler overhead,
        # which is fine: this phase's product is the trace, not a number.
        prof = str(_LOG_DIR.parent / "profiles" / "tiny")
        res = _train_bench("tiny", recipe=(4, 4, 3),
                           phase_suffix="-profile", profile_dir=prof)
        res["phase"] = "profile-tiny"  # match the scheduled phase name
        res["trace_dir"] = prof
        res["timing_suspect"] = True  # profiler overhead: not a baseline
        return res
    if name == "train-tiny-bs32":
        # framework-ceiling companion to the recipe-parity headline: same
        # model, micro-batch 32 / no accumulation — MFU at a batch the
        # chip can actually fill (the reference recipe's 4x4 microbatches
        # underfeed a v5e; both numbers are reported side by side)
        return _train_bench("tiny", recipe=(1, 32, 10),
                            phase_suffix="-bs32")
    if name == "train-long8k-xla":
        return _train_bench("long8k", use_pallas=False)
    if name.startswith("train-"):
        return _train_bench(name[len("train-"):])
    if name == "calib-matmul":
        return _calib_bench()
    if name == "decode-tiny":
        return _decode_bench()
    if name == "decode-serve":
        return _decode_serve_bench()
    if name == "decode-admit-stall":
        return _decode_admit_stall_bench()
    if name == "transport-overhead":
        return _transport_overhead_bench()
    if name == "flight-overhead":
        return _flight_overhead_bench()
    if name == "decode-int8":
        return _decode_int8_bench()
    if name == "batch-score":
        return _batch_score_bench()
    if name == "mutagenesis":
        return _mutagenesis_bench()
    if name == "sustain-base":
        return _sustain_bench()
    if name == "sgu-mix":
        return _sgu_mix_bench()
    if name == "large-projection":
        return _large_projection()
    if name == "data-io":
        return _data_io_bench()
    raise ValueError(f"unknown phase {name}")


# --------------------------------------------------------------------------
# orchestrator
# --------------------------------------------------------------------------


def _write_detail(detail: dict) -> None:
    """Per-phase results so far, under runs/ (never a tracked file)."""
    try:
        _DETAIL_PATH.parent.mkdir(parents=True, exist_ok=True)
        _DETAIL_PATH.write_text(json.dumps(detail, indent=1))
    except OSError as e:  # never let bookkeeping kill the bench
        print(f"[bench] detail write failed: {e}", file=sys.stderr)


def _phase_log_tail(name: str, n: int = 1200) -> str:
    # seek-based tail: a hung phase can spew hundreds of MB of libtpu
    # diagnostics; never load the whole file for 1200 chars
    try:
        with open(_LOG_DIR / f"{name}.log", "rb") as f:
            f.seek(0, os.SEEK_END)
            f.seek(max(f.tell() - n, 0))
            return f.read().decode(errors="replace")
    except OSError:
        return ""


def _run_phase_subprocess(name: str, timeout: float):
    """One phase in its own process (own chip claim, own crash domain).
    SIGTERM then SIGKILL on timeout. The child's stderr streams to
    runs/bench_logs/<name>.log so a killed phase leaves its
    progress-marker trail ([bench-mark] lines from _mark) for
    post-mortem."""
    _LOG_DIR.mkdir(parents=True, exist_ok=True)
    log_path = _LOG_DIR / f"{name}.log"
    with open(log_path, "w") as log:
        proc = subprocess.Popen(
            [sys.executable, str(_REPO / "bench.py"), "_phase", name],
            stdout=subprocess.PIPE,
            stderr=log,
            cwd=str(_REPO),
            text=True,
            env={
                **os.environ,
                "BENCH_REQUIRE_TPU": "1",
                # what the child has before the parent kills it: paces
                # sustain-base and sets the stall watchdog's deadline
                "BENCH_PHASE_DEADLINE_SEC": str(max(int(timeout) - 30, 60)),
            },
        )
        try:
            out, _ = proc.communicate(timeout=timeout)
        except subprocess.TimeoutExpired:
            proc.terminate()
            try:
                proc.wait(timeout=15)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            return {
                "phase": name,
                "error": f"timeout after {timeout:.0f}s",
                "log_tail": _phase_log_tail(name),
            }
    if proc.returncode != 0:
        return {
            "phase": name,
            "error": f"exit {proc.returncode}",
            "log_tail": _phase_log_tail(name),
        }
    for line in reversed(out.strip().splitlines()):
        try:
            res = json.loads(line)
        except json.JSONDecodeError:
            continue
        if "error" in res and "log_tail" not in res:
            res["log_tail"] = _phase_log_tail(name)
        return res
    return {"phase": name, "error": "no JSON in phase output"}


def _headline_from(res: dict, prior: float | None) -> dict:
    per_chip = res["tokens_per_sec_per_chip"]
    return {
        "metric": "train_tokens_per_sec_per_chip",
        "value": per_chip,
        "unit": "tokens/s/chip",
        "vs_baseline": round(per_chip / prior, 3) if prior else 1.0,
        "mfu": res["mfu"],
        "num_params": res["num_params"],
        "chips": res["chips"],
        "step_ms": res["step_ms"],
        "config": "progen-tiny (dim=512 depth=12 seq=1024 w=256) bf16",
        "implied_device_tflops": res.get("implied_device_tflops"),
        "timing_suspect": res.get("timing_suspect", False),
        "platform": "tpu",
    }


def main() -> int:
    budget = float(os.environ.get("BENCH_BUDGET_SEC", "3000"))
    started = time.perf_counter()
    # the parent never initialises a backend: one subprocess probe says
    # what the phase children will find
    platform = _probe_platform()
    if not _is_tpu_platform(platform):
        print(
            f"bench.py needs a TPU backend; a probe process reports "
            f"{platform!r}. No chip, no number.",
            file=sys.stderr,
        )
        return 1
    # span trail for the whole suite: a B with no E in
    # runs/bench_logs/events.jsonl names the phase the run died in
    from progen_tpu import telemetry

    _LOG_DIR.mkdir(parents=True, exist_ok=True)
    telemetry.configure(path=_LOG_DIR / "events.jsonl")

    detail: dict = {
        "schema": "bench-suite-v1",
        "platform": platform,
        "phases": [],
    }
    headline = None
    prior = _prior_round_value()
    for name, timeout in _PHASES:
        remaining = budget - (time.perf_counter() - started)
        if remaining < 90:
            detail["phases"].append(
                {"phase": name, "error": "skipped: budget exhausted"}
            )
            continue
        with telemetry.span(f"bench/{name}", timeout=timeout):
            res = _run_phase_subprocess(name, min(timeout, remaining))
        if "error" not in res and not res.get("host_side") \
                and not _is_tpu_platform(res.get("platform", "tpu")):
            # belt-and-suspenders vs BENCH_REQUIRE_TPU: a result from any
            # other platform must never be recorded as suite evidence
            res = {
                "phase": name,
                "error": f"phase ran on {res.get('platform')}, not tpu",
            }
        detail["phases"].append(res)
        _write_detail(detail)
        print(f"[bench] {name}: {json.dumps(res)[:300]}", file=sys.stderr)

        if name == "train-tiny" and "error" not in res:
            headline = _headline_from(res, prior)
            # print + flush NOW: a driver that kills a later phase's
            # overrun still has the headline on stdout
            print(json.dumps(headline), flush=True)

    detail["phases"].append(_data_io_safe())
    detail["phases"].append(_large_projection())
    _write_detail(detail)

    if headline is None:
        print(
            "bench.py: the headline phase (train-tiny) failed — no metric; "
            f"see {_DETAIL_PATH} and {_LOG_DIR}",
            file=sys.stderr,
        )
        return 1

    summary = {}
    for res in detail["phases"]:
        ph = res.get("phase", "?")
        if "error" in res:
            summary[ph] = res["error"][:60]
        elif ph.startswith("kernel") or ph == "sgu-mix":
            summary[ph] = {
                "fwd_speedup": res["fwd_speedup"],
                "bwd_speedup": res["bwd_speedup"],
            }
        elif ph.startswith("train") and ph != "train-tiny":
            summary[ph] = {
                "tps_chip": res["tokens_per_sec_per_chip"],
                "mfu": res["mfu"],
            }
        elif ph == "decode-tiny":
            summary[ph] = {
                "kv_tps": res["kv_cache_tokens_per_sec"],
                "speedup": res["speedup"],
            }
        elif ph == "decode-admit-stall":
            summary[ph] = {
                "stall_ratio": res["value"],
                "prefix_cache_speedup": res["prefix_cache_speedup"],
            }
            # carry both serving ratios on the headline so the gate
            # chains see them even in rounds whose parsed metric is the
            # train number
            headline["serve_admit_stall_ratio"] = res["value"]
            headline["serve_prefix_cache_speedup"] = res[
                "prefix_cache_speedup"
            ]
        elif ph == "transport-overhead":
            summary[ph] = {
                "parity": res["value"],
                "bit_identical": res["bit_identical"],
            }
            # same carry idiom: keep the transport record on the chain
            # even in rounds whose parsed metric is the train number
            headline["serve_transport_parity"] = res["value"]
        elif ph == "flight-overhead":
            summary[ph] = {
                "parity": res["value"],
                "bit_identical": res["bit_identical"],
            }
            # same carry idiom: keep the forensics record on the chain
            # even in rounds whose parsed metric is the train number
            headline["flight_overhead_ratio"] = res["value"]
        elif ph == "decode-int8":
            summary[ph] = {
                "int8_tps": res["int8_tokens_per_sec"],
                "speedup": res["speedup"],
                "agreement": res["token_agreement"],
            }
        elif ph == "calib-matmul":
            summary[ph] = {
                "achieved_tflops": res["achieved_tflops"],
                "mxu_efficiency": res["mxu_efficiency"],
            }
        elif ph == "data-io":
            summary[ph] = {
                "native_speedup": res.get("native_speedup"),
                "parse_py_mb_s": res.get("parse_py_mb_s"),
            }
    print(json.dumps({**headline, "suite": summary}), flush=True)
    return 0


def kernel_main() -> None:
    _require_tpu()
    results = [_kernel_bench(256), _kernel_bench(512)]
    print(json.dumps({
        "metric": "pallas_vs_xla_local_attention",
        "results": results,
        "platform": results[0]["platform"],
    }))


def gate_main(argv: list) -> int:
    """``python bench.py gate``: ratchet a headline tokens/s value
    against the best prior round in the BENCH_r0N.json trajectory
    (progen_tpu/utils/bench_gate). Value sources, highest precedence
    first: ``--value N`` (synthetic / pre-measured), ``--from-json FILE``
    (a bench headline or phase JSON carrying ``value``); the gate measures
    nothing itself. Exit 0 within tolerance of the best prior (or no
    prior: the value sets the bar), 1 on regression, 2 on usage errors —
    the contract tier1.yml enforces."""
    import argparse

    from progen_tpu.utils.bench_gate import SERVE_CHAINS, run_gate

    ap = argparse.ArgumentParser(prog="bench.py gate")
    ap.add_argument("--value", type=float, default=None)
    ap.add_argument("--from-json", default=None)
    ap.add_argument(
        "--from-json-key", default="value",
        help="key to read from --from-json (default 'value'; e.g. "
             "'prefix_cache_speedup' from the decode-admit-stall phase "
             "JSON, which carries two gated numbers in one record)",
    )
    ap.add_argument("--metric",
                    choices=("cpu", "tpu", "auto") + SERVE_CHAINS,
                    default="cpu")
    ap.add_argument("--tolerance", type=float, default=0.2)
    args = ap.parse_args(argv)

    if args.value is not None:
        value, source = args.value, "--value"
    elif args.from_json:
        try:
            doc = json.loads(Path(args.from_json).read_text())
        except (OSError, ValueError) as e:
            print(f"gate: cannot read {args.from_json}: {e}",
                  file=sys.stderr)
            return 2
        key = args.from_json_key
        raw = doc.get(key) if isinstance(doc, dict) else None
        if raw is None and isinstance(doc, dict) \
                and isinstance(doc.get("parsed"), dict):
            raw = doc["parsed"].get(key)
        if raw is None:
            print(f"gate: no {key!r} in {args.from_json}",
                  file=sys.stderr)
            return 2
        value, source = float(raw), f"{args.from_json}:{key}"
    else:
        print("gate: pass --value or --from-json", file=sys.stderr)
        return 2
    try:
        report = run_gate(value, args.metric, args.tolerance, _REPO)
    except ValueError as e:
        print(f"gate: {e}", file=sys.stderr)
        return 2
    print(json.dumps({"source": source, **report}, indent=1))
    return 0 if report["ok"] else 1


def _load_repo_env() -> None:
    """The shipped .env (LIBTPU_INIT_ARGS etc.) must apply to benches the
    same as to the CLIs — otherwise the recorded numbers measure a
    different libtpu/XLA configuration than production training."""
    from progen_tpu.utils.env import load_env_file

    load_env_file()


if __name__ == "__main__":
    _load_repo_env()
    if len(sys.argv) > 2 and sys.argv[1] == "_phase":
        deadline = int(os.environ.get("BENCH_PHASE_DEADLINE_SEC", "0"))
        if deadline > 0:
            # stall watchdog inside the parent's kill horizon: when the
            # phase hangs, all-thread stacks + the open spans land in this
            # child's stderr — the phase log the parent tails into
            # log_tail on the timeout kill — BEFORE the kill destroys the
            # evidence. _mark() beats it, so it only fires when the
            # progress trail actually stops.
            from progen_tpu.telemetry import StallWatchdog

            # escalate_after=2: if the stall survives two reports, the
            # third event snapshots device memory_stats + open spans
            _WATCHDOG = StallWatchdog(
                max(60.0, deadline * 0.6), file=sys.stderr,
                escalate_after=2,
            ).start()
        # phase-child telemetry: spans + injected faults + the goodput
        # report land in the shared bench event stream (same file the
        # orchestrator writes its bench/<name> spans to — appends from
        # both processes are line-atomic)
        from progen_tpu import telemetry as _tel
        from progen_tpu.telemetry import GoodputLedger as _Ledger

        _LOG_DIR.mkdir(parents=True, exist_ok=True)
        _tel.configure(path=_LOG_DIR / "events.jsonl")
        _PHASE_LEDGER = _Ledger()
        if os.environ.get("BENCH_REQUIRE_TPU") == "1":
            # orchestrated child: a result from any other platform must
            # NOT masquerade as a TPU phase result
            _require_tpu()
        result = run_phase(sys.argv[2])
        # close the phase's goodput books: the report rides the phase
        # JSON (runs/bench_detail.json) and the event stream (export-trace
        # renders it as a counter track on the bench timeline)
        _gp = _PHASE_LEDGER.report()
        if isinstance(result, dict) and "error" not in result:
            result.setdefault("goodput", _gp)
        _tel.get_telemetry().emit({
            "ev": "goodput", "ts": time.time(),
            "phase": sys.argv[2], **_gp,
        })
        print(json.dumps(result))
    elif len(sys.argv) > 1 and sys.argv[1] == "kernel":
        kernel_main()
    elif len(sys.argv) > 1 and sys.argv[1] == "gate":
        sys.exit(gate_main(sys.argv[2:]))
    elif len(sys.argv) > 2 and sys.argv[1] == "--config":
        _require_tpu()
        print(json.dumps(_train_bench(sys.argv[2])))
    else:
        sys.exit(main())
