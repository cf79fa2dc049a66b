"""Profiling / throughput observability (SURVEY §5: absent in the reference
beyond tqdm — /root/reference/train.py:184; the north-star metric is
tokens/sec/chip + MFU, BASELINE.md).

Pieces:
  * ``flops_per_token`` — PaLM-convention accounting: 6*params for the
    dense math (fwd + bwd) + 12*L*H*Dh*ctx for attention score/value
    matmuls, ctx = 2*window for this model's [prev|cur] windowed attention.
  * ``peak_flops`` — bf16 peak per chip by device kind; an unknown TPU
    kind is an error, and a CPU has no peak (so no MFU).
  * ``StepTimer`` — wall-clock per optimizer step -> tokens/sec/chip and
    (where the device has a peak) MFU, with warmup skipping so compile
    time never pollutes the numbers.
  * ``announce_startup`` — what a CLI process runs on, printed once at
    start and logged as one telemetry record.
  * the train CLI starts/stops ``jax.profiler`` traces around steps 2-4
    (``--profile_dir``), viewable in TensorBoard/XProf.

bench.py and the train CLI both consume these so the two always agree on
the FLOPs math.
"""

from __future__ import annotations

import json
import os
import sys
import time
from typing import Optional

# bf16 peak FLOP/s per chip, keyed by a substring of ``device_kind`` as
# jax reports it (lower-cased). Sources: Google Cloud TPU documentation
# ("TPU v4", "TPU v5e", "TPU v5p", "TPU v6e" system-architecture pages).
# A v5e chip reports device_kind "TPU v5 lite" (chip run, PR 21).
PEAK_BF16_FLOPS = {
    "v4": 275e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5p": 459e12,
    "v6e": 918e12,
    "v6 lite": 918e12,
}


def flops_per_token(config) -> int:
    """Training FLOPs per token (fwd+bwd), PaLM MFU convention.

    The SGU's ``(n, n)`` spatial matrix is the one place the ``6*params``
    convention breaks: a per-sequence weight does ``2*n*d_half`` fwd flops
    per *token* (each output token mixes n sequence positions of a
    d_half-wide activation), not the ``2*n*n`` the convention would charge.
    They coincide only when ``d_half == n`` (the default config's
    1024/1024); at long context (n=8192, d_half=1024) the params convention
    overstates the SGU term 8x. So: charge ``6*(params - spatial)`` for the
    dense math and ``6*n*d_half`` per gMLP layer for the spatial mix.
    """
    attn_ctx = 2 * config.window_size
    n = config.seq_len
    d_half = (config.ff_mult * config.dim) // 2
    n_gmlp = min(config.global_mlp_depth, config.depth)
    spatial_params = n_gmlp * n * n
    return (
        6 * (config.num_params() - spatial_params)
        + n_gmlp * 6 * n * d_half
        + 12 * config.depth * config.heads * config.dim_head * attn_ctx
    )


def peak_flops(device) -> Optional[float]:
    """bf16 peak FLOP/s of ``device``. ``None`` on the CPU platform (no
    peak, so callers report no MFU); a TPU whose ``device_kind`` is not in
    the table raises — a utilization against a guessed peak is worse than
    none."""
    if device.platform == "cpu":
        return None
    kind = device.device_kind.lower()
    for key, val in PEAK_BF16_FLOPS.items():
        if key in kind:
            return val
    raise ValueError(
        f"no bf16 peak known for device kind {device.device_kind!r} "
        f"(platform {device.platform!r}); add it to "
        "profiling.PEAK_BF16_FLOPS with its source"
    )


def announce_startup(role: str, mesh=None, **extra) -> dict:
    """Say once what this process runs on: platform, device kind and count
    as jax reports them, the mesh shape (train), jax/jaxlib/libtpu versions
    and the compile-cache directory. Prints the one ``startup: {json}``
    line on stderr (``chip_smoke.py`` parses it) and returns the record for
    the caller to emit once its telemetry sink exists. Initialises the
    backend if nothing has yet — call it where the CLI is about to anyway."""
    import importlib.metadata

    import jax
    import jaxlib

    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = None
    devices = jax.devices()
    record = {
        "ev": "startup",
        "ts": time.time(),
        "role": role,
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
        "compile_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        **extra,
    }
    print("startup: " + json.dumps(record), file=sys.stderr, flush=True)
    return record


class StepTimer:
    """Tracks per-step wall time and derives throughput metrics.

    Call ``tick(tokens)`` once per optimizer step AFTER the step's result
    has been observed on the host (e.g. float(loss) — that sync is the
    timing fence). The first ``warmup`` ticks are discarded (compile)."""

    def __init__(self, n_chips: int, flops_per_tok: int,
                 peak: Optional[float], warmup: int = 2):
        self.n_chips = max(n_chips, 1)
        self.flops_per_tok = flops_per_tok
        self.peak = peak
        self.warmup = warmup
        self._last: Optional[float] = None
        self._steps = 0
        self._time = 0.0
        self._tokens = 0
        self._excluded = 0.0

    def exclude(self, seconds: float) -> None:
        """Subtract known non-step work (checkpoint/eval/sample between
        ticks) from the next ``tick``'s window, so cadence work no longer
        inflates step_ms / deflates MFU."""
        self._excluded += max(seconds, 0.0)

    def tick(self, tokens: int) -> Optional[dict]:
        """Returns {step_ms, tokens_per_sec_per_chip[, mfu]} once
        measuring (post-warmup), else None. ``mfu`` is present only when
        the device has a peak (``peak_flops``): never on CPU."""
        now = time.perf_counter()
        if self._last is None:
            self._last = now
            self._excluded = 0.0
            return None
        dt = max(now - self._last - self._excluded, 0.0)
        self._last, self._excluded = now, 0.0
        self._steps += 1
        if self._steps <= self.warmup:
            return None
        self._time += dt
        self._tokens += tokens
        per_chip = self._tokens / self._time / self.n_chips
        out = {
            "step_ms": 1000.0 * dt,
            "tokens_per_sec_per_chip": per_chip,
        }
        if self.peak is not None:
            out["mfu"] = per_chip * self.flops_per_tok / self.peak
        return out

