"""python -m benchmark.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Loads the cell from BENCHMARK.json, its configuration and traffic files,
the traffic's driver and each metric's reader by name; refuses to run
without a TPU; prints the contract's JSON object as the last line of
stdout. Everything else it writes goes under runs/benchmark/ in the
checkout. See PERF.md for what each metric means.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse
import contextlib
import importlib
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = Path(__file__).resolve().parent
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def load_cell(name: str) -> tuple:
    """(manifest, cell, config, traffic) for the cell called ``name``."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = json.loads((ROOT / entry["file"]).read_text())
    traffic = json.loads(
        (BENCH / "traffic" / f"{cell['traffic']}.json").read_text()
    )
    return manifest, cell, config, traffic


class Run:
    """What a driver and the readers share: the cell's data, a span clock,
    the window, and the counters and samples the metrics are read from."""

    def __init__(self, manifest, cell, config, traffic, seed, seconds,
                 trace, devices, out_dir):
        self.manifest, self.cell = manifest, cell
        self.config, self.traffic = config, traffic
        self.seed, self.seconds, self.tracing = int(seed), float(seconds), bool(trace)
        self.devices = list(devices)[: cell["chips"]]
        self.out_dir = Path(out_dir)
        self.out_dir.mkdir(parents=True, exist_ok=True)
        self.tmp = Path(tempfile.mkdtemp(prefix="bm-"))
        self.counters, self.samples, self.notes = {}, {}, {}
        self.span_total, self.span_count = {}, {}
        self.trace = None
        self.trace_window = None
        self.t_open = self.t_close = None
        self._compiles = 0
        self._annotate = None  # the profiler's annotation while it is on
        self._wrapped = []
        try:
            from benchmark import stats

            self.peak = stats.peaks(self.devices[0].device_kind)
        except KeyError:
            self.peak = None  # main() refuses this on a TPU
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == COMPILE_EVENT:
            self._compiles += 1

    # ----- spans: host clock always, profiler annotation while tracing ----

    @contextlib.contextmanager
    def span(self, name: str):
        ann = self._annotate(name) if self._annotate else None
        if ann is not None:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            if ann is not None:
                ann.__exit__(None, None, None)
            self.span_total[name] = self.span_total.get(name, 0.0) + dt
            self.span_count[name] = self.span_count.get(name, 0) + 1

    def wrap(self, obj, attr: str, name: str) -> None:
        """Put a span around obj.attr(...) — the benchmark's own span at a
        layer boundary, no change to the program. Traced runs only."""
        inner = getattr(obj, attr)

        def spanned(*a, **k):
            with self.span(name):
                return inner(*a, **k)

        self._wrapped.append((obj, attr, obj.__dict__.get(attr)))
        setattr(obj, attr, spanned)

    # ----- the window ------------------------------------------------------

    def open_window(self) -> float:
        """Set-up ends here. Returns the opening time (perf_counter)."""
        self.counters["setup_s"] = time.perf_counter() - T_START
        self._note_first_setup()
        self.span_total.clear()
        self.span_count.clear()
        self._compiles_at_open = self._compiles
        self.t_open = time.perf_counter()
        return self.t_open

    def _note_first_setup(self) -> None:
        """The first run of a cell against a compile cache leaves its
        set-up time beside the cache; later runs read it (env.compile_s)."""
        cache = os.environ.get("JAX_COMPILATION_CACHE_DIR")
        if not cache:
            return
        marker = Path(cache) / f"bm-first-setup-{self.cell['name']}.json"
        if marker.exists():
            self.counters["first_setup_s"] = json.loads(marker.read_text())["setup_s"]
        else:
            marker.parent.mkdir(parents=True, exist_ok=True)
            marker.write_text(json.dumps({"setup_s": self.counters["setup_s"]}))

    def due(self) -> bool:
        """True once ``seconds`` have passed; the driver then closes the
        window at the next boundary of its work. In a traced run the
        profiler is switched on for the window's last few seconds."""
        elapsed = time.perf_counter() - self.t_open
        if (self.tracing and not self._annotate and elapsed >=
                self.seconds - self.traffic.get("trace_seconds", 3)):
            self._start_profiler()
        return elapsed >= self.seconds

    def close_window(self) -> float:
        self.t_close = time.perf_counter()
        if self._annotate:
            self._stop_profiler()
        self.counters["window_s"] = self.t_close - self.t_open
        self.counters["xla_compiles_in_window"] = (
            self._compiles - self._compiles_at_open
        )
        self.counters["chips"] = len(self.devices)
        return self.t_close

    def _start_profiler(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        opts.enable_hlo_proto = False
        self._trace_dir = self.tmp / "trace"
        jax.profiler.start_trace(str(self._trace_dir), profiler_options=opts)
        self._annotate = jax.profiler.TraceAnnotation
        self._window_ann = self._annotate("bm.window")
        self._window_ann.__enter__()

    def _stop_profiler(self):
        import jax

        from benchmark import xplane

        self._window_ann.__exit__(None, None, None)
        self._annotate = None
        jax.profiler.stop_trace()
        path = xplane.find_xplane(str(self._trace_dir))
        if path is None:
            return
        names = set(self.span_total) | {"bm.window"}
        full = xplane.load(path, names)
        win = next((e for e in full["host"] if e[0] == "bm.window"), None)
        if win is None:
            return
        lo, hi = win[1], win[1] + win[2]
        self.trace = xplane.clip(full, lo, hi)
        self.trace["host"] = [e for e in self.trace["host"] if e[0] != "bm.window"]
        self.trace_window = (lo, hi)

    # ----- the result line -------------------------------------------------

    def read_metrics(self) -> dict:
        kind = "per_layer" if self.tracing else "end_to_end"
        out = {}
        for m in self.manifest[kind]:
            if self.cell["name"] not in m.get("workloads", [self.cell["name"]]):
                continue
            spec = json.loads((BENCH / "metrics" / f"{m['name']}.json").read_text())
            reader = importlib.import_module(f"benchmark.readers.{spec['reader']}")
            value = reader.read(self, spec)
            if value is not None:
                out[m["name"]] = {"value": float(value), "unit": m["unit"]}
        return out

    def device_block(self) -> dict:
        from benchmark import xplane

        # the runtime keeps a program's scratch apart from the live buffers
        # ("reserved" against "in use"); both count against the chip's limit
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                       + int(stats.get("peak_bytes_reserved", 0)))
        self.notes["memory_stats_device0"] = self.devices[0].memory_stats()
        self.counters["hbm_peak_bytes"] = peak
        block = {
            "platform": self.devices[0].platform,
            "kind": self.devices[0].device_kind,
            "count": len(self.devices),
            "memory_peak_bytes": peak,
        }
        if self.trace is not None:
            block["busy_s"] = xplane.busy_seconds(self.trace)
            block["window_s"] = (self.trace_window[1] - self.trace_window[0]) / 1e9
        return block

    def result_line(self, outcome: dict) -> dict:
        from benchmark import xplane

        device = self.device_block()  # fills hbm_peak_bytes for the readers
        line = {
            "correct": bool(outcome["correct"]),
            "attempted": int(outcome["attempted"]),
            "failed": int(outcome["failed"]),
            "metrics": self.read_metrics(),
            "device": device,
            "xla_compiles_in_window": self.counters.get("xla_compiles_in_window"),
        }
        if self.trace is not None:
            line["breakdown"] = {
                "device_ops": xplane.op_ranking(self.trace),
                "idle_gaps": xplane.idle_gaps(self.trace, *self.trace_window),
            }
        # what decided ``correct``, each number beside its limit: last
        line["compared"] = outcome.get("compared", {})
        return line

    def write_details(self, outcome: dict) -> None:
        """Histograms, span sums and the correctness check's numbers: too
        long for the result line, kept under the run's output directory."""
        detail = {
            "cell": self.cell["name"], "seed": self.seed,
            "counters": self.counters, "notes": self.notes,
            "span_total_s": self.span_total, "span_count": self.span_count,
            "check": outcome.get("check"),
        }
        (self.out_dir / "detail.json").write_text(json.dumps(detail, indent=1))
        if self.trace is not None and self.trace["devices"]:
            dev0 = self.trace["devices"][0]
            piece = {
                "devices": [{
                    "ops": [[n[:240], s, d] for n, s, d in dev0["ops"][:1500]],
                    "modules": dev0["modules"][:200],
                }],
                "host": self.trace["host"][:2000],
            }
            (self.out_dir / "trace_slice.json").write_text(json.dumps(piece))

    def cleanup(self):
        import jax.monitoring

        for obj, attr, was in reversed(self._wrapped):
            if was is None:
                delattr(obj, attr)  # an instance attribute over a method
            else:
                setattr(obj, attr, was)
        jax.monitoring.unregister_event_duration_listener(self._on_event)
        shutil.rmtree(self.tmp, ignore_errors=True)


def execute(manifest, cell, config, traffic, seed, seconds, trace, devices,
            out_dir) -> dict:
    """Run one cell on ``devices`` and return the result line as a dict.
    main() only lets a TPU get this far; the CPU tests call it directly at
    a tiny size to rehearse the control flow."""
    run = Run(manifest, cell, config, traffic, seed, seconds, trace, devices,
              out_dir)
    try:
        driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")
        outcome = driver.run(run)
        line = run.result_line(outcome)
        run.write_details(outcome)
        return line
    finally:
        run.cleanup()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest, cell, config, traffic = load_cell(args.workload)
    seconds = args.seconds or manifest["run_seconds"]

    # the program's own runtime environment, as every CLI loads it: libtpu
    # flags and the compile cache at <checkout>/runs/xla_cache unless
    # JAX_COMPILATION_CACHE_DIR is already set
    from progen_tpu.utils.env import load_env_file

    load_env_file()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / "runs" / "xla_cache"))
    import jax

    from benchmark import stats

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(
            f"refused: {cell['name']} needs {cell['chips']} TPU chip(s), jax "
            f"found {len(devices)} x {devices[0].platform}", file=sys.stderr,
        )
        return 3
    stats.peaks(devices[0].device_kind)  # unknown kind: an error, no default
    out_dir = (ROOT / "runs" / "benchmark" / cell["name"]
               / f"seed{args.seed}-trace{args.trace}")
    line = execute(manifest, cell, config, traffic, args.seed, seconds,
                   args.trace, devices, out_dir)
    for name, (value, limit) in line["compared"].items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
