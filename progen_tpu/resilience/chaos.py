"""Env-driven fault injection keyed on telemetry span names.

Recovery code that only runs during real incidents is recovery code
that has never run. This module turns the existing telemetry span
vocabulary (``ckpt/save``, ``ckpt/restore``, ``data/read``,
``train/eval``, ...) into injection points, so a test — or a brave
operator — can rehearse every failure mode the resilience layer claims
to survive:

    PROGEN_CHAOS="ckpt/save:0.3,data/read:kill"

Comma-separated ``target:spec`` rules; ``target`` is a span name or a
retry-site label (resilience/retry.py labels its attempts). Specs:

  * ``0.3``      — raise a transient ``ChaosError`` with probability
                   0.3 at each hit (seeded by ``PROGEN_CHAOS_SEED``);
  * ``fail@N``   — raise deterministically on the Nth hit (1-based);
  * ``kill``     — SIGKILL the process at the first hit;
  * ``kill@N``   — SIGKILL at the Nth hit (the kill-matrix harness
                   walks N across a run's span timeline);
  * ``spike@N``  — value perturbation: the first N calls to
                   ``perturb(target, x)`` return a huge loss (1e9).
                   Used by the anomaly-sentinel integration tests via
                   the ``train/loss`` site in cli/train.py;
  * ``nan@N``    — like ``spike@N`` but returns NaN.

Injection is wired in three places so no production code needs
test-only seams: the telemetry span entry hook (installed by
``install_from_env``), the per-attempt hook inside ``retry_call``, and
direct ``maybe_inject`` call sites on span-free hot paths. With
``PROGEN_CHAOS`` unset everything here is a dict-lookup no-op.

Serving targets (the serve kill-matrix, tests/test_serve_kill_matrix):

  * ``serve/prefill``        — span entry of ``ServeEngine.prefill``,
                               the one-call admission of direct
                               callers; the scheduler (so ``cli.serve``)
                               never passes it;
  * ``serve/prefill_chunk``  — span entry of each chunk of an admission
                               — every admission the scheduler makes,
                               the whole prime when unbudgeted
                               (``kill@N`` = die mid-chunk with the
                               slot acquired but never activated;
                               replay must re-run the whole prefill
                               exactly once);
  * ``serve/decode``         — called by the scheduler once per decode
                               step, before the engine advances
                               (``kill@N`` = die after N-1 full steps);
  * ``serve/reload``         — background checkpoint load of a hot
                               weight reload (kill = die mid-load,
                               current weights were still serving);
  * ``serve/reload_commit``  — the between-steps param swap (kill =
                               die at the commit point; the swap is a
                               host-side rebind, so it either fully
                               applied or never happened).

Router targets (the fleet kill-matrix, tests/test_router_kill_matrix):

  * ``router/connect``   — replica socket connect (``fail@N``/``prob``
                           = a refused/flaky replica; the circuit
                           breaker must absorb it);
  * ``router/dispatch``  — just before a request line is written to a
                           replica (transient ``fail@N`` = re-route on
                           the backoff schedule; ``kill@N`` = the
                           ROUTER dies mid-dispatch);
  * ``router/handoff``   — span entry of the journal-ownership handoff
                           after a replica death (a fault here must not
                           lose the dead replica's in-flight work —
                           the fold is idempotent and is retried).

Fleet targets (progen_tpu/fleet/ — TCP transport and autoscaler):

  * ``transport/accept``  — the framed TCP listener's accept path: the
                            dial is accepted then immediately dropped
                            (a flaky fronting LB); the client retries
                            or its breaker backs off;
  * ``transport/frame``   — per decoded frame: the frame is dropped
                            (``ev:"frame_drop"`` reason ``chaos``) and
                            the connection condemned, simulating a
                            corrupted/truncated frame on the wire —
                            the router must treat the link as down and
                            run the journal-ownership handoff;
  * ``autoscaler/decide`` — top of each autoscaler decide tick; a
                            transient fault must cost one tick, never
                            the fleet (the router CLI skips the tick),
                            and ``kill@N`` dies inside the decision.

Workload targets (progen_tpu/workloads/scoring.py):

  * ``score/batch``     — top of each batch-scoring step, after the
                          resume skip-scan (``kill@N`` = die mid-sweep:
                          the fsync'd shard journal must make the
                          resumed run re-score nothing and drop
                          nothing — the CI workloads smoke's contract).

Forensics targets (progen_tpu/telemetry/flight.py):

  * ``flight/dump``     — span entry of a flight-recorder dump
                          (``kill@N`` = die at the dump site: the
                          atomic tmp+fsync+rename discipline must
                          leave no file or a complete one, never a
                          torn flight-*.json);
  * ``profile/window``  — span entry of an on-demand profiler window
                          (a fault here costs the window — it is
                          rejected with a reason — never the serve
                          loop).

An unknown target (typo'd span name, renamed site) warns ONCE at
install instead of silently never firing — a chaos rehearsal whose
faults never land proves nothing.
"""

from __future__ import annotations

import os
import random
import signal
import warnings
from dataclasses import dataclass
from typing import Dict, Optional

from progen_tpu.resilience.retry import TransientError

# every injectable site: span names + retry-site labels + perturb sites
# + direct maybe_inject call sites. Kept in lockstep with the code (the
# unknown-target warning below is what notices drift).
KNOWN_TARGETS = frozenset({
    # spans
    "ckpt/finalize", "ckpt/restore", "ckpt/restore_params", "ckpt/save",
    "deploy/canary", "deploy/probe", "deploy/promote", "deploy/rollback",
    "flight/dump", "profile/window",
    "router/handoff",
    "serve/prefill", "serve/prefill_chunk", "serve/reload",
    "serve/reload_commit",
    "train/ckpt", "train/compile", "train/eval", "train/rollback",
    "train/sample",
    # retry-site labels (resilience/retry.py)
    "ckpt/io/meta_read", "ckpt/io/meta_write", "ckpt/io/restore",
    "ckpt/io/save", "data/glob", "data/read",
    # perturb sites
    "train/loss",
    # direct maybe_inject sites
    "autoscaler/decide", "router/connect", "router/dispatch",
    "score/batch", "serve/decode", "transport/accept",
    "transport/frame",
})

_WARNED_UNKNOWN: set = set()


class ChaosError(TransientError):
    """Injected transient fault (classified retryable by design)."""


@dataclass
class _Rule:
    kind: str  # "prob" | "fail" | "kill" | "spike" | "nan"
    arg: float  # probability, or hit index / count
    hits: int = 0


def _parse(spec: str) -> Dict[str, _Rule]:
    rules: Dict[str, _Rule] = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        target, _, s = part.rpartition(":")
        if not target:
            raise ValueError(f"chaos rule needs 'target:spec': {part!r}")
        if s == "kill":
            rules[target] = _Rule("kill", 1)
        elif s.startswith("kill@"):
            rules[target] = _Rule("kill", int(s[len("kill@"):]))
        elif s.startswith("fail@"):
            rules[target] = _Rule("fail", int(s[len("fail@"):]))
        elif s.startswith("spike@"):
            rules[target] = _Rule("spike", int(s[len("spike@"):]))
        elif s.startswith("nan@"):
            rules[target] = _Rule("nan", int(s[len("nan@"):]))
        else:
            p = float(s)
            if not 0.0 <= p <= 1.0:
                raise ValueError(f"chaos probability out of [0,1]: {part!r}")
            rules[target] = _Rule("prob", p)
    return rules


def _record_injection(name: str, rule: "_Rule") -> None:
    """Every injection leaves a ``chaos`` event + counter — a chaos run
    whose trace doesn't show where the faults landed can't distinguish
    'survived the fault' from 'fault never fired'. Lazy imports + broad
    except: the injector must work (and kill) even with telemetry torn
    down."""
    import time

    try:
        from progen_tpu import telemetry
        from progen_tpu.telemetry.registry import get_registry

        get_registry().inc("chaos_injections")
        telemetry.get_telemetry().emit({
            "ev": "chaos",
            "ts": time.time(),
            "site": name,
            "kind": rule.kind,
            "hit": rule.hits,
        })
    except Exception:
        pass


class ChaosInjector:
    def __init__(self, spec: str, seed: int = 0):
        self.rules = _parse(spec)
        self._rng = random.Random(seed)

    def on_site(self, name: str) -> None:
        """Called at a span entry / retry attempt named ``name``."""
        rule = self.rules.get(name)
        if rule is None or rule.kind in ("spike", "nan"):
            return
        rule.hits += 1
        if rule.kind == "prob":
            if self._rng.random() < rule.arg:
                _record_injection(name, rule)
                raise ChaosError(f"chaos: injected fault at {name!r}")
        elif rule.kind == "fail":
            if rule.hits == rule.arg:
                _record_injection(name, rule)
                raise ChaosError(
                    f"chaos: injected fault at {name!r} (hit {rule.hits})"
                )
        elif rule.kind == "kill":
            if rule.hits == rule.arg:
                # the event is written (and flushed, per-line) BEFORE the
                # kill — the post-mortem trace shows where the run died
                _record_injection(name, rule)
                # flush whatever the process has buffered — the whole
                # point is to die where a preemption would
                import sys

                for f in (sys.stdout, sys.stderr):
                    try:
                        f.flush()
                    except (OSError, ValueError):
                        pass
                os.kill(os.getpid(), signal.SIGKILL)

    def perturb(self, name: str, value: float) -> float:
        """Value-level injection (``spike@N`` / ``nan@N`` rules)."""
        rule = self.rules.get(name)
        if rule is None or rule.kind not in ("spike", "nan"):
            return value
        if rule.hits >= rule.arg:
            return value
        rule.hits += 1
        _record_injection(name, rule)
        return float("nan") if rule.kind == "nan" else 1e9


_INJECTOR: Optional[ChaosInjector] = None


def _warn_unknown_targets(rules: Dict[str, _Rule]) -> None:
    """Once per unknown target per process: a rule aimed at a
    nonexistent site never fires, and 'survived chaos' must not be
    claimable when the chaos never happened."""
    for target in rules:
        if target in KNOWN_TARGETS or target in _WARNED_UNKNOWN:
            continue
        _WARNED_UNKNOWN.add(target)
        warnings.warn(
            f"PROGEN_CHAOS target {target!r} matches no known injection "
            f"site (span name, retry label, or perturb site) — this "
            f"rule will never fire",
            stacklevel=3,
        )


def install(spec: str, seed: int = 0) -> ChaosInjector:
    """Install an injector and hook it into telemetry span entry."""
    global _INJECTOR
    _INJECTOR = ChaosInjector(spec, seed)
    _warn_unknown_targets(_INJECTOR.rules)
    from progen_tpu.telemetry import spans

    if maybe_inject not in spans.SPAN_ENTRY_HOOKS:
        spans.SPAN_ENTRY_HOOKS.append(maybe_inject)
    return _INJECTOR


def uninstall() -> None:
    global _INJECTOR
    _INJECTOR = None
    from progen_tpu.telemetry import spans

    if maybe_inject in spans.SPAN_ENTRY_HOOKS:
        spans.SPAN_ENTRY_HOOKS.remove(maybe_inject)


def install_from_env() -> Optional[ChaosInjector]:
    """Install from ``PROGEN_CHAOS`` (uninstall when unset/empty) —
    called at CLI entry points so a subprocess under test inherits its
    fault plan from the environment alone."""
    spec = os.environ.get("PROGEN_CHAOS", "").strip()
    if not spec:
        uninstall()
        return None
    return install(spec, seed=int(os.environ.get("PROGEN_CHAOS_SEED", "0")))


def maybe_inject(name: str) -> None:
    """The hook: no-op unless an injector is installed."""
    if _INJECTOR is not None:
        _INJECTOR.on_site(name)


def perturb(name: str, value: float) -> float:
    if _INJECTOR is None:
        return value
    return _INJECTOR.perturb(name, value)
