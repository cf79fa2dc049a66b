"""Experiment tracking: wandb-compatible interface, local-first backends.

Capability parity (/root/reference/train.py:24-28,135-150,193,211,222):
``init`` with resume-by-run-id, scalar logging (loss / valid_loss), config
attachment (num_params), HTML-rendered samples via a Jinja2 template, and a
disabled mode (``--wandb_off`` -> ``mode='disabled'``, train.py:143).

Backends:
  * ``WandbTracker`` — used when the wandb package exists (it is not in this
    image; the class stays import-guarded);
  * ``JsonlTracker`` — default: metrics appended as JSON lines under
    ``{dir}/{run_id}/metrics.jsonl``, HTML artifacts as files; greppable and
    sufficient for loss-curve comparison against the reference;
  * ``NoopTracker`` — the reference's disabled mode.

Only process 0 should construct a real tracker; `make_tracker` enforces
that itself through ``telemetry.spans.host_index``, which asks jax only
when a backend is already live — the router and the deploy controller
build trackers too, and a jax-free process must never claim a chip.
"""

from __future__ import annotations

import json
import threading
import time
import uuid
from pathlib import Path
from typing import Optional

from progen_tpu.telemetry.spans import host_index

try:  # template parity with train.py:28; fallback keeps jinja2 optional
    from jinja2 import Template

    _SAMPLE_TMPL = Template(
        "<i>{{prime_str}}</i><br/><br/>"
        '<div style="overflow-wrap: break-word;">{{sampled_str}}</div>'
    )

    def render_sample_html(prime_str: str, sampled_str: str) -> str:
        return _SAMPLE_TMPL.render(
            prime_str=prime_str, sampled_str=sampled_str
        )

except ImportError:  # pragma: no cover

    def render_sample_html(prime_str: str, sampled_str: str) -> str:
        return (
            f"<i>{prime_str}</i><br/><br/>"
            f'<div style="overflow-wrap: break-word;">{sampled_str}</div>'
        )


class NoopTracker:
    run_id: Optional[str] = None

    def log(self, metrics: dict, step: Optional[int] = None) -> None:
        pass

    def log_event(self, record: dict) -> None:
        pass

    def log_html(self, name: str, html: str, step: Optional[int] = None) -> None:
        pass

    def set_config(self, config: dict) -> None:
        pass

    def finish(self) -> None:
        pass


class JsonlTracker(NoopTracker):
    def __init__(self, project: str, run_id: Optional[str], dir: str):
        self.run_id = run_id or uuid.uuid4().hex[:8]
        self.path = Path(dir) / project / self.run_id
        self.path.mkdir(parents=True, exist_ok=True)
        self._metrics = (self.path / "metrics.jsonl").open("a")
        self._events = None  # opened on first span; most runs have none
        # the watchdog thread, async-checkpoint paths, and retry hooks
        # all emit through log_event concurrently with the train loop's
        # log(); the lock makes every write+flush one critical section
        # so JSONL lines can never tear or interleave. REENTRANT: the
        # serve CLI's second-signal handler logs through this same
        # tracker and a signal can land while the main thread holds the
        # lock mid-write — a plain Lock would deadlock the exit path. A
        # reentrant write can interleave into the interrupted line, but
        # iter_jsonl skips (and counts) torn lines by contract.
        self._lock = threading.RLock()

    def log(self, metrics: dict, step: Optional[int] = None) -> None:
        rec = {"_time": time.time(), **metrics}
        if step is not None:
            rec["_step"] = step
        with self._lock:
            if self._metrics.closed:
                raise ValueError("tracker is finished")
            self._metrics.write(json.dumps(rec) + "\n")
            self._metrics.flush()

    def log_event(self, record: dict) -> None:
        """Span/watchdog records -> events.jsonl beside metrics.jsonl,
        same crash-safety discipline (flush per line). Raises ValueError
        after ``finish()`` — telemetry sinks treat that as detach."""
        with self._lock:
            if self._events is None:
                if self._metrics.closed:
                    raise ValueError("tracker is finished")
                self._events = (self.path / "events.jsonl").open("a")
            self._events.write(json.dumps(record) + "\n")
            self._events.flush()

    def log_html(self, name: str, html: str, step: Optional[int] = None) -> None:
        suffix = f"_{step}" if step is not None else ""
        (self.path / f"{name}{suffix}.html").write_text(html)

    def set_config(self, config: dict) -> None:
        (self.path / "config.json").write_text(json.dumps(config, default=str))

    def finish(self) -> None:
        with self._lock:
            self._metrics.close()
            if self._events is not None:
                self._events.close()


class WandbTracker(NoopTracker):  # exercised via a mock module in-suite
    def __init__(self, project: str, run_id: Optional[str]):
        import wandb

        self._wandb = wandb
        self._run = wandb.init(
            project=project,
            id=run_id,
            resume="allow" if run_id else None,
        )
        self.run_id = self._run.id

    def log(self, metrics: dict, step: Optional[int] = None) -> None:
        self._wandb.log(metrics, step=step)

    def log_html(self, name: str, html: str, step: Optional[int] = None) -> None:
        self._wandb.log({name: self._wandb.Html(html)}, step=step)

    def set_config(self, config: dict) -> None:
        self._run.config.update(config, allow_val_change=True)

    def finish(self) -> None:
        self._run.finish()


def make_tracker(
    project: str,
    run_id: Optional[str] = None,
    *,
    disabled: bool = False,
    dir: str = "./runs",
) -> NoopTracker:
    """Tracker factory. Disabled, or on any process but 0 -> Noop
    (reference logs from its single process; multi-host must gate — call
    this after the backend is up there, as cli/train.py does)."""
    if disabled or host_index() != 0:
        return NoopTracker()
    try:
        import wandb  # noqa: F401

        return WandbTracker(project, run_id)
    except ImportError:
        return JsonlTracker(project, run_id, dir)
