"""HBM gauges from ``device.memory_stats()``.

TPU PJRT devices expose allocator stats (bytes in use / peak / limit);
CPU devices usually return nothing, and this degrades to ``{}`` there —
callers can always splat the result into a metrics dict. The per-step
reading costs one local C++ call, so the train loop logs it on every
tracker flush and the serve loop on every snapshot; OOMs then come with
a trajectory, not just a death.
"""

from __future__ import annotations

from typing import Optional

from progen_tpu.telemetry.spans import live_jax


def live_devices() -> list:
    """``jax.devices()`` when a backend is ALREADY live, else ``[]`` —
    never initialises one (``spans.live_jax``): a jax-free process taking
    a crash dump must not pull the chip out from under a replica."""
    jax = live_jax()
    if jax is None:
        return []
    try:
        return list(jax.devices())
    except Exception:
        return []


def device_memory_stats(devices=None) -> list:
    """Per-device ``memory_stats()`` snapshot (numeric fields only) for
    ``devices`` (default: every device of the live backend); [] when no
    backend is live, ``{"device": id}`` alone where the backend reports
    nothing (CPU)."""
    out = []
    for d in live_devices() if devices is None else devices:
        try:
            stats = d.memory_stats() or {}
        except Exception:
            stats = {}
        out.append({"device": str(d.id), **{
            k: v for k, v in stats.items() if isinstance(v, (int, float))
        }})
    return out


def hbm_gauges(device=None, prefix: str = "hbm/") -> dict:
    """Flat gauge dict (GB, rounded) for ``device`` (default: first
    device of the live backend). Empty when no backend is live or it
    exposes no memory stats."""
    if device is None:
        devices = live_devices()
        if not devices:
            return {}
        device = devices[0]
    stats = getattr(device, "memory_stats", lambda: None)
    try:
        stats = stats() or {}
    except Exception:
        return {}
    out = {}

    def _gb(key: str) -> Optional[float]:
        v = stats.get(key)
        return round(v / 2**30, 3) if v is not None else None

    for src, dst in (
        ("bytes_in_use", "in_use_gb"),
        ("peak_bytes_in_use", "peak_gb"),
        ("bytes_limit", "limit_gb"),
        ("largest_alloc_size", "largest_alloc_gb"),
    ):
        v = _gb(src)
        if v is not None:
            out[f"{prefix}{dst}"] = v
    limit = stats.get("bytes_limit")
    if limit:
        out[f"{prefix}used_pct"] = round(
            100.0 * stats.get("bytes_in_use", 0) / limit, 2
        )
    return out
