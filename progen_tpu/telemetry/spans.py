"""Span API: context-manager tracing to events.jsonl.

A span is one timed region of host code (``span("ckpt/save")``). On
entry a ``B`` (begin) record goes to the sink; on exit an ``E`` (end)
record with the duration. Crash forensics fall out of the format: a
``B`` with no matching ``E`` in ``events.jsonl`` IS the phase the
process died in — no log-diving required (Dapper-style span trees,
sized for one process).

A span is a span of HOST time: like a stage (below) it shows on the
profiler's clock through ``jax.profiler.TraceAnnotation`` while a trace
is being taken. It names no device op: a jitted program does not take
the name stack it is called under, so the device's work is labelled by
the programs' own scopes (``telemetry/scopes.py``). jax is imported
lazily and its absence is tolerated (pure-host tools can use spans too).

The module-level ``span()``/``configure()`` pair operates a process
global ``Telemetry`` so deep callees (checkpoint.py, bench phases) can
open spans without threading a handle through every signature. With no
sink configured spans still maintain the in-memory recent/open ring
(what the stall watchdog reports) at ~zero cost.

``stage(name)`` is the span's hot-path sibling: no record, no sink, no
hook — two clock reads, a
``jax.profiler.TraceAnnotation`` (so the stage shows on the device's
clock in any profiler trace, and costs nothing while no trace is being
taken) and one tuple appended to an in-memory ring that a benchmark or
a debugger reads afterwards (``Telemetry.stages``). Spans land in the
same ring and the same trace, so a stage knows the span that caused it.
Use ``span`` where a crash must leave a ``B`` without its ``E`` or a
chaos rule must be able to fire; use ``stage`` wherever the call runs
once per token or per batch.
"""

from __future__ import annotations

import contextlib
import itertools
import json
import sys
import threading
import time
from collections import deque
from pathlib import Path
from typing import Callable, List, Optional, Tuple

_HOST_INDEX: Optional[int] = None


def live_jax():
    """The ``jax`` module when a backend is ALREADY live, else ``None``.

    Deliberately init-free: ``jax.devices()`` / ``jax.process_index()``
    on a cold process *initialise* the backend — which claims every
    visible chip — and telemetry must never do that (tests assert
    backends stay uninitialized at import; the router, the collector and
    the deploy controller emit telemetry and take flight dumps too, and
    a chip belongs to one process at a time). So jax is consulted only
    if it is already imported AND its backends are already up."""
    jax = sys.modules.get("jax")
    if jax is None:
        return None
    try:
        from jax._src import xla_bridge

        return jax if xla_bridge.backends_are_initialized() else None
    except Exception:
        return None


def host_index() -> int:
    """This process's index in a multi-process run (0 single-process).

    Asks jax only through ``live_jax`` and caches the answer from then
    on — before a backend is live every record is host 0, which is
    exactly right for the only process that can exist pre-init."""
    global _HOST_INDEX
    if _HOST_INDEX is not None:
        return _HOST_INDEX
    jax = live_jax()
    if jax is None:
        return 0
    try:
        _HOST_INDEX = int(jax.process_index())
    except Exception:
        return 0
    return _HOST_INDEX


class EventLog:
    """Crash-safe append-only JSONL sink: one record per line, flushed
    per line (same discipline as tracking.JsonlTracker — a SIGKILL at
    any instant loses at most the line being written, never the file)."""

    def __init__(self, path):
        self.path = Path(path)
        self.path.parent.mkdir(parents=True, exist_ok=True)
        self._f = self.path.open("a")
        self._lock = threading.Lock()

    def emit(self, record: dict) -> None:
        # the watchdog thread emits concurrently with the main loop; the
        # lock keeps lines whole (write+flush is one critical section)
        with self._lock:
            if self._f.closed:
                return
            self._f.write(json.dumps(record) + "\n")
            self._f.flush()

    def close(self) -> None:
        with self._lock:
            if not self._f.closed:
                self._f.close()


# span-entry hooks: called with the span name after the B record is
# emitted, INSIDE the span's try block — a hook that raises surfaces to
# the span's caller while the E record still closes the span. The chaos
# injector (resilience/chaos.py) registers here; empty list = no-op.
SPAN_ENTRY_HOOKS: list = []

# emit taps: called with every record that reaches Telemetry.emit —
# BEFORE the sink check, so a tap sees records even on a sink-less
# process (spans still maintain the ring with no events.jsonl). The
# flight recorder (telemetry/flight.py) registers here; a tap must
# never raise and never block (it runs on the training/serving hot
# path). Empty list = no-op.
EMIT_TAPS: list = []


# bound once: the stage's budget is under two microseconds
_perf_counter = time.perf_counter
_get_ident = threading.get_ident


# one completed stage or span in the ring:
# (seq, parent_seq, name, t0, dur, thread_id)
StageRecord = Tuple[int, Optional[int], str, float, float, int]

# completed stages and spans the ring holds, oldest dropped first: a
# 40 s serving window is ~1,400 steps of ~10 stages
MAX_STAGES = 65536


class _Stage:
    """The context manager ``Telemetry.stage`` returns. A class with
    slots, not a generator: the whole of enter + exit is budgeted at
    under two microseconds. ``dur`` is the stage's seconds once it has
    exited, for a caller that keeps a time ledger of its own."""

    __slots__ = ("_tel", "name", "dur", "_seq", "_parent", "_ann", "_t0")

    def __init__(self, tel: "Telemetry", name: str):
        self._tel = tel
        self.name = name

    def __enter__(self) -> "_Stage":
        tel = self._tel
        try:
            stack = tel._nesting.open
        except AttributeError:
            stack = tel._nesting.open = []
        self._parent = stack[-1] if stack else None
        self._seq = seq = next(tel._seq)
        stack.append(seq)
        # never import jax from here (see ``live_jax``); where it is
        # already imported the annotation needs no backend
        jax = sys.modules.get("jax")
        if jax is None:
            self._ann = None
        else:
            self._ann = ann = jax.profiler.TraceAnnotation(self.name)
            ann.__enter__()
        self._t0 = _perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t0 = self._t0
        self.dur = dur = _perf_counter() - t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        tel = self._tel
        tel._nesting.open.pop()
        tel._stages.append((self._seq, self._parent, self.name, t0, dur,
                            _get_ident()))
        return False


class Telemetry:
    """Span emitter + in-memory recent/open span state.

    ``sink`` is any ``callable(dict)`` — an ``EventLog.emit``, a
    ``JsonlTracker.log_event``, or None (records dropped, ring kept).
    """

    def __init__(self, sink: Optional[Callable[[dict], None]] = None,
                 max_recent: int = 64):
        self._sink = sink
        self._seq = itertools.count()
        self._lock = threading.Lock()
        self._open: dict[int, dict] = {}
        self._recent: deque = deque(maxlen=max_recent)
        self._stages: deque = deque(maxlen=MAX_STAGES)
        self._nesting = threading.local()  # .open: seqs, innermost last

    def set_sink(self, sink: Optional[Callable[[dict], None]]) -> None:
        self._sink = sink

    @property
    def recording(self) -> bool:
        """True when a record handed to ``emit`` would reach anyone: a
        sink or an emit tap. Hot paths check it before BUILDING a
        record."""
        return self._sink is not None or bool(EMIT_TAPS)

    def emit(self, record: dict) -> None:
        # every record carries its host: under multi-process training the
        # per-host event files merge into one trace, and pid is what the
        # trace/skew tooling groups on (MegaScale-style straggler
        # attribution needs the host on *every* retry/anomaly/stall line,
        # not just spans)
        record.setdefault("pid", host_index())
        for tap in EMIT_TAPS:
            tap(record)
        sink = self._sink
        if sink is None:
            return
        try:
            sink(record)
        except (OSError, ValueError):
            # a closed/broken sink must never take the training loop
            # down; drop the record and keep the in-memory state
            self._sink = None

    @contextlib.contextmanager
    def span(self, name: str, **attrs):
        # a span is also a stage: it joins the ring under the id its
        # records carry, shows in a profiler trace under its literal
        # name (attrs would make every call a name of its own there)
        # and is the parent of the stages opened inside it
        with _Stage(self, name) as st:
            sid = st._seq
            thread = threading.current_thread()
            begin = {
                "ev": "B", "span": name, "id": sid, "ts": time.time(),
                "tid": thread.ident, "thread": thread.name,
            }
            if attrs:
                begin.update(attrs)
            with self._lock:
                self._open[sid] = begin
            self.emit(begin)
            t0 = time.perf_counter()
            try:
                for hook in SPAN_ENTRY_HOOKS:
                    hook(name)
                yield
            finally:
                dur = time.perf_counter() - t0
                end = {
                    "ev": "E", "span": name, "id": sid,
                    "ts": time.time(), "dur_s": round(dur, 6),
                    "tid": thread.ident, "thread": thread.name,
                }
                if attrs:
                    end.update(attrs)
                with self._lock:
                    self._open.pop(sid, None)
                    self._recent.append(end)
                self.emit(end)

    # ----- stages: the hot-path ring ---------------------------------------

    def stage(self, name: str) -> _Stage:
        """Time a hot-path region: on exit one ``(seq, parent_seq, name,
        t0, dur, thread_id)`` tuple joins the ring (``t0`` on
        ``time.perf_counter``), and while a ``jax.profiler`` trace is
        being taken the region shows in it under ``name``. Nothing is
        written anywhere else."""
        return _Stage(self, name)

    def stages(self, since: Optional[float] = None,
               until: Optional[float] = None) -> List[StageRecord]:
        """The ring's tuples whose ``t0`` lies in [since, until) on the
        ``perf_counter`` clock, in order of completion (a child before
        its parent); spans are among them."""
        return [
            r for r in list(self._stages)
            if (since is None or r[3] >= since)
            and (until is None or r[3] < until)
        ]

    # ----- watchdog-facing state ------------------------------------------

    def open_spans(self) -> list:
        """Spans currently inside their body — where the process is NOW."""
        with self._lock:
            return sorted(self._open.values(), key=lambda r: r["id"])

    def recent_spans(self, n: int = 16) -> list:
        """The last ``n`` completed spans, oldest first."""
        with self._lock:
            return list(self._recent)[-n:]


_GLOBAL = Telemetry()


def get_telemetry() -> Telemetry:
    return _GLOBAL


def configure(sink: Optional[Callable[[dict], None]] = None,
              path=None) -> Telemetry:
    """Point the process-global telemetry at a sink. ``path`` is a
    convenience that opens an ``EventLog`` there; ``sink`` wins when both
    are given; ``configure()`` with neither detaches (spans keep timing,
    records drop)."""
    if sink is None and path is not None:
        sink = EventLog(path).emit
    _GLOBAL.set_sink(sink)
    return _GLOBAL


def span(name: str, **attrs):
    """Module-level span on the process-global Telemetry."""
    return _GLOBAL.span(name, **attrs)


def stage(name: str) -> _Stage:
    """Module-level stage on the process-global Telemetry."""
    return _Stage(_GLOBAL, name)


def step_print(step, msg: str) -> None:
    """Step-stamped console line, format-consistent with the tracker
    stream (the tracker carries ``_time``/``_step``; the console carries
    the same two, human-readable): ``[HH:MM:SS step N] msg``."""
    stamp = time.strftime("%H:%M:%S")
    print(f"[{stamp} step {step}] {msg}")
