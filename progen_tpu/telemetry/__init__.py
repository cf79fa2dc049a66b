"""Unified telemetry: spans, goodput ledger, stall watchdog, HBM gauges,
Prometheus exposition.

The reference has no observability beyond tqdm (SURVEY §5); this package
is the per-phase time accounting and span-level tracing that turns a
hung or slow run into a one-line diagnosis (MegaScale NSDI'24, Dapper
2010 — PAPERS.md "Observability"):

  * ``span("ckpt/save")`` — context-manager spans emitting begin/end
    records to a crash-safe ``events.jsonl`` (per-line flush), on the
    profiler's clock as host spans (``TraceAnnotation``);
  * ``scopes.CLASSES`` — the mechanism classes every program files its
    device ops under (``jax.named_scope``), which the benchmark's
    ``device_scopes`` reader sums per program from a trace;
  * ``GoodputLedger`` — classifies train-loop wall clock into
    compile/step/data/checkpoint/eval/sample/log buckets and reports
    ``goodput_pct`` next to MFU;
  * ``StallWatchdog`` — heartbeat thread that dumps all-thread stacks
    (faulthandler) plus a last-spans report when no step completes
    within a deadline;
  * ``hbm_gauges`` — per-device HBM occupancy from
    ``device.memory_stats()``;
  * ``prometheus_text`` / ``start_prometheus_server`` — text exposition
    of any ``structured()`` metrics source (serving AND the train-loop
    ``MetricsRegistry``) for scraping (file and HTTP);
  * ``MetricsRegistry`` / ``get_registry`` — the process-wide counter/
    gauge/timing store shared by train, serve, bench, and resilience;
  * ``trace.build_trace`` / ``export_trace`` — events.jsonl → Chrome
    Trace Event / Perfetto JSON (the ``telemetry export-trace`` CLI);
  * ``per_host_reports`` / ``goodput_skew`` / ``emit_per_host_goodput``
    — MegaScale-style per-host goodput + straggler skew table;
  * ``stitch_trace`` / ``clock_offsets`` / ``emit_clock_beacon`` —
    N hosts' event files → ONE fleet trace on a common corrected clock
    (the ``telemetry stitch`` CLI), beacon-anchored skew correction,
    plus per-request journey flows across router → replica → survivor;
  * ``slo`` — the fleet SLO watchtower: objectives from TOML,
    multi-window burn rates over metrics.jsonl / Prometheus textfiles,
    ``ev: "slo"`` transition records, and the slo-report CI gate.

Everything is CPU-testable; nothing here imports jax at module scope.
"""

from progen_tpu.telemetry.goodput import (
    BUCKETS,
    GoodputLedger,
    emit_per_host_goodput,
    goodput_skew,
    per_host_reports,
)
from progen_tpu.telemetry.hbm import hbm_gauges
from progen_tpu.telemetry.prometheus import (
    prometheus_text,
    start_prometheus_server,
    write_prometheus,
)
from progen_tpu.telemetry.registry import MetricsRegistry, get_registry
from progen_tpu.telemetry.slo import (
    SloConfig,
    SloWatch,
    evaluate as evaluate_slos,
    exit_code as slo_exit_code,
    load_objectives,
)
from progen_tpu.telemetry.spans import (
    EventLog,
    Telemetry,
    configure,
    get_telemetry,
    host_index,
    span,
    step_print,
)
from progen_tpu.telemetry.stitch import (
    clock_offsets,
    emit_clock_beacon,
    stitch_streams,
    stitch_trace,
)
from progen_tpu.telemetry.trace import build_trace, export_trace
from progen_tpu.telemetry.watchdog import StallWatchdog

__all__ = [
    "BUCKETS",
    "GoodputLedger",
    "per_host_reports",
    "goodput_skew",
    "emit_per_host_goodput",
    "EventLog",
    "Telemetry",
    "configure",
    "get_telemetry",
    "host_index",
    "span",
    "step_print",
    "StallWatchdog",
    "hbm_gauges",
    "prometheus_text",
    "write_prometheus",
    "start_prometheus_server",
    "MetricsRegistry",
    "get_registry",
    "build_trace",
    "export_trace",
    "clock_offsets",
    "emit_clock_beacon",
    "stitch_streams",
    "stitch_trace",
    "SloConfig",
    "SloWatch",
    "evaluate_slos",
    "slo_exit_code",
    "load_objectives",
]
