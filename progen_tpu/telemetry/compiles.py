"""XLA compile and compile-cache counters, from ``jax.monitoring``.

The engine's own compile counts (``ServeEngine.decode_compile_count``,
``prefill_compile_count``) are jit-cache sizes of named programs; they
miss everything else the process compiles — an eager ``jnp.pad`` at a
new shape, a reference model, a lazily built embed twin — and say
nothing of what a compile cost or whether the persistent cache served
it. jax reports both through ``jax.monitoring``; ``install()`` listens
and ``snapshot()`` reads, up to a time if asked, so a benchmark can tell
the compiles of set-up from those inside its window.

Per event: a count, the sum of seconds (0 for the plain event) and the
last ``_KEEP`` occurrences as ``(perf_counter time, seconds)``.
``backend_compile`` is every trip through XLA's compile-or-load, so it
counts persistent-cache hits too and its seconds include their
retrieval; ``cache_misses`` is what jax calls a miss: an executable
compiled and written to the persistent cache.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Optional

# jax's event -> the short name snapshot() keys it by
EVENTS = {
    "/jax/core/compile/backend_compile_duration": "backend_compile",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}
_KEEP = 4096  # occurrences kept per event: a serving set-up makes ~200

_lock = threading.Lock()
_installed = False
_counts = {name: 0 for name in EVENTS.values()}
_seconds = {name: 0.0 for name in EVENTS.values()}
_recent = {name: deque(maxlen=_KEEP) for name in EVENTS.values()}


def _record(event: str, seconds: float) -> None:
    name = EVENTS.get(event)
    if name is None:
        return
    with _lock:
        _counts[name] += 1
        _seconds[name] += seconds
        _recent[name].append((time.perf_counter(), seconds))


def _on_duration(event: str, duration: float, **_) -> None:
    _record(event, duration)


def _on_event(event: str, **_) -> None:
    _record(event, 0.0)


def install() -> None:
    """Register the listeners, once a process however often it is
    called. Imports jax; registering initialises no backend."""
    global _installed
    with _lock:
        if _installed:
            return
        _installed = True
    import jax.monitoring

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def installed() -> bool:
    """Whether the counters listen: a process that never called
    ``install()`` has counts of 0 that mean nothing."""
    return _installed


def backend_compiles() -> int:
    """Trips through XLA's compile-or-load since ``install()``."""
    return _counts["backend_compile"]


def snapshot(until: Optional[float] = None) -> dict:
    """``{name: {"count", "seconds", "recent"}}`` for the two events.
    With ``until`` (``perf_counter`` clock) counts and seconds are those
    of the kept occurrences before it."""
    out = {}
    with _lock:
        for name in EVENTS.values():
            recent = list(_recent[name])
            if until is None:
                count, seconds = _counts[name], _seconds[name]
            else:
                recent = [(t, s) for t, s in recent if t < until]
                count, seconds = len(recent), sum(s for _, s in recent)
            out[name] = {"count": count, "seconds": seconds,
                         "recent": recent}
    return out
