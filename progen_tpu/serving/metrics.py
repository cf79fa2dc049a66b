"""Serving metrics: counters, gauges, and timing observations.

Deliberately dependency-free and single-threaded (the scheduler owns
the loop); the only integration point is ``log_to(tracker)``, which
flattens a snapshot into the wandb-compatible ``tracking.py`` interface
under a ``serve/`` prefix — so serving runs land in the same
metrics.jsonl / wandb stream as training runs.

Throughput is derived, not sampled: the scheduler accumulates exact
token counts and wall-clock time around the prefill/decode calls, and
``snapshot()`` divides. That makes decode_tokens_per_s a true
steady-state number (tokens that actually advanced / time the device
actually spent), not a gauge that depends on when you look.
``prefill_time_s`` is different: under chunked admission it is the HOST
time inside ``engine.advance_prefill`` — the chunk is dispatched and the
call returns before the device has run it — so it, and
``prefill_tokens_per_s`` with it, says what admission costs the serving
loop's thread, not how fast the device prefills (measured on a v5e,
PERF.md, PR 27: ≈ 0.5 ms a token of host time against 1.0 ms on the
device for a 16-token chunk, one block's pass over the weights).

Latency lands in three reservoir-quantile families the scheduler
observes: ``ttft_s`` (submit → first token), ``itl_s`` (inter-token
latency — the gap between consecutive tokens of ONE request; the
number a streaming client actually feels between characters), and
``latency_s`` (submit → done). All three render as Prometheus
summaries with p50/p95/p99.
"""

from __future__ import annotations

from typing import Dict, Optional

from progen_tpu.telemetry.registry import (  # noqa: F401 — re-exported
    _QUANTILES,
    _RESERVOIR_CAP,
    _Timing,
)


# `# HELP` text for the names whose meaning the name does not carry
HELP = {
    "prefill_time_s": (
        "Host seconds inside engine.advance_prefill: dispatch of the "
        "prefill programs, not the device's prefill work"
    ),
    "prefill_tokens_per_s": (
        "prefill_tokens over prefill_time_s: prime tokens per second of "
        "HOST time in admission, not the device's prefill rate"
    ),
    "decode_steps_ahead": (
        "Decode steps whose successor was already launched when the host "
        "fetched their tokens: over decode_steps, the share of steps the "
        "device did not wait for the host between"
    ),
    "prefill_blocks": (
        "Prefill blocks executed: passes over the weights made to feed "
        "prompts, each an aligned block of the engine's prefill_width "
        "positions (128 at most, the largest divisor of window_size) of "
        "which prefill_tokens / (prefill_blocks * width) were real"
    ),
    "ring_rows_read": (
        "Rows of the live slots' K/V rings a decode step's attention read, "
        "a layer, summed over decode steps: the ring blocks in which a "
        "slot's query sees a row where the decode-attention kernel runs "
        "(ops/pallas_decode_attention.py), whole rings where it does not"
    ),
    "ring_rows_held": (
        "Rows of the live slots' K/V rings (live slots x 2 x window_size), "
        "summed over decode steps: the divisor of ring_rows_read"
    ),
    "moe_expert_layer_steps": (
        "Expert layers run by decode steps (steps x expert layers): the "
        "divisor of moe_experts_touched, moe_max_load_rows and "
        "moe_assignments"
    ),
    "moe_experts_touched": (
        "Distinct routed experts whose weights a decode step read, summed "
        "over expert layers and steps"
    ),
    "moe_max_load_rows": (
        "Rows of the busiest expert, summed over expert layers and decode "
        "steps"
    ),
    "moe_assignments": (
        "Token-to-expert assignments the router made in decode steps (live "
        "slots x experts per token x expert layers); where a layer holds "
        "every expert, none is dropped"
    ),
    "moe_held_assignments": (
        "Of moe_assignments, those whose expert the layer holds and "
        "computes (a layer holding a share of the router's experts drops "
        "the others)"
    ),
    "moe_feed_assignments": (
        "Token-to-expert assignments the router made in prefill blocks' "
        "live rows, reported with the decode step that follows"
    ),
    "moe_feed_held_assignments": (
        "Of moe_feed_assignments, those whose expert the layer holds"
    ),
    "moe_feed_expert_layer_blocks": (
        "Expert layers run by prefill blocks (blocks x expert layers); "
        "moe_feed_experts_touched and moe_feed_max_load_rows are summed "
        "over them, reported with the decode step that follows"
    ),
    "latent_cache_bytes": (
        "Bytes of the slot pool's latent attention cache (rows of "
        "kv_lora_rank + qk_rope_head_dim values per layer and position)"
    ),
    "sparse_layer_steps": (
        "Block-sparse attention layers run by decode steps (steps x sparse "
        "layers): the divisor of sparse_rows_visible, sparse_rows_attended "
        "and sparse_blocks_selected per layer and step"
    ),
    "sparse_rows_visible": (
        "Cached rows the live slots' queries could see (position + 1), "
        "summed over sparse layers and decode steps"
    ),
    "sparse_rows_attended": (
        "Cached rows the live slots' queries attended, a key/value group: "
        "the rows up to the query of its chosen blocks (all it could see "
        "below dense_len), summed over sparse layers and decode steps"
    ),
    "sparse_blocks_selected": (
        "Blocks those attended rows lay in, a key/value group, summed over "
        "sparse layers and decode steps"
    ),
    "sparse_feed_layer_blocks": (
        "Sparse layers run by prefill blocks (blocks x sparse layers); "
        "sparse_feed_rows_visible, sparse_feed_rows_attended and "
        "sparse_feed_blocks_selected are summed over their live rows, "
        "reported with the decode step that follows"
    ),
    "kv_cache_bytes": (
        "Bytes of the slot pool's key and value rows that grow with the "
        "sequence (sparse or full attention layers: max_len rows a slot, "
        "layer and key/value head)"
    ),
    "window_cache_bytes": (
        "Bytes of the slot pool's key and value rings (sliding-window "
        "layers: sliding_window + one prefill block of rows a slot, layer "
        "and key/value head, whatever the context)"
    ),
    "index_cache_bytes": (
        "Bytes of the slot pool's pooled keys, the index the block "
        "selection scores (one a kernel_stride rows)"
    ),
    "linear_state_bytes": (
        "Bytes of the slot pool's recurrent states (linear-attention "
        "layers: heads x head_dim x head_dim float32 a slot and layer, "
        "whatever the context)"
    ),
    "raw_weight_bytes": (
        "Bytes of the parameter tree as handed to the engine, in the "
        "checkpoint's type (embeddings, int8 quantization and a reload's "
        "dtype check read it)"
    ),
    "served_weight_bytes": (
        "Bytes of the tree the decode step and the prefills take: every "
        "leaf they would convert to the compute type at each use held "
        "converted, every other leaf shared with the raw tree"
    ),
    "served_leaves_cast": (
        "Leaves of the served tree held converted (0: the served tree is "
        "the raw tree; else, while the engine holds a slot, the cast "
        "leaves' served bytes are resident on top of raw_weight_bytes)"
    ),
    "cache_write_leaves_kernel": (
        "Cache leaves whose new rows the decode step writes for all slots "
        "in one row-write kernel call (ops/pallas_row_write.py), counted "
        "when the step is traced"
    ),
    "cache_write_leaves_select": (
        "Cache leaves, too small to need the kernel or written along their "
        "lanes, that the decode step rewrites whole in one select for all "
        "slots' new rows, counted when the step is traced"
    ),
    "cache_write_leaves_loop": (
        "Cache leaves whose new rows the decode step writes as one update "
        "a slot (off the TPU, or a leaf neither other path takes), counted "
        "when the step is traced"
    ),
    "xla_compile_count": (
        "XLA compile-or-load events of the whole process (jax.monitoring), "
        "those the decode/prefill jit-cache counts miss among them"
    ),
}


class ServingMetrics:
    """Counters (monotonic), gauges (last value), timings (running
    stats), and time accumulators (for derived throughput)."""

    def __init__(self):
        self.counters: Dict[str, int] = {}
        self.gauges: Dict[str, float] = {}
        self._timings: Dict[str, _Timing] = {}
        self._times: Dict[str, float] = {}

    def inc(self, name: str, by: int = 1) -> None:
        self.counters[name] = self.counters.get(name, 0) + by

    def set_gauge(self, name: str, value: float) -> None:
        self.gauges[name] = float(value)

    def observe(self, name: str, seconds: float, trace_id=None) -> None:
        self._timings.setdefault(name, _Timing()).observe(
            seconds, trace_id=trace_id
        )

    def declare_timing(self, name: str) -> None:
        """Pre-register a timing family at zero observations so the
        Prometheus exposition carries it from process start (a scraper
        needs ``itl_seconds_count 0`` — an absent family looks like a
        broken exporter, not an idle server)."""
        self._timings.setdefault(name, _Timing())

    def add_time(self, name: str, seconds: float) -> None:
        self._times[name] = self._times.get(name, 0.0) + seconds

    def snapshot(self) -> Dict[str, float]:
        """Flat dict of everything, plus derived tokens/s rates. Keys are
        stable, so jsonl consumers can grep a run end-to-end."""
        out: Dict[str, float] = {}
        for k, v in self.counters.items():
            out[k] = float(v)
        out.update(self.gauges)
        for k, v in self._times.items():
            out[k] = v
        for name, t in self._timings.items():
            for stat, v in t.stats().items():
                out[f"{name}_{stat}"] = v
        decode_t = self._times.get("decode_time_s", 0.0)
        if decode_t > 0:
            out["decode_tokens_per_s"] = (
                self.counters.get("decode_tokens", 0) / decode_t
            )
        prefill_t = self._times.get("prefill_time_s", 0.0)
        if prefill_t > 0:
            out["prefill_tokens_per_s"] = (
                self.counters.get("prefill_tokens", 0) / prefill_t
            )
        return out

    def structured(self) -> dict:
        """Typed view for exposition formats that distinguish metric
        kinds (telemetry.prometheus): counters (monotonic, incl. the
        accumulated-time counters), gauges, derived rates, and timings
        with reservoir quantiles."""
        derived = {}
        decode_t = self._times.get("decode_time_s", 0.0)
        if decode_t > 0:
            derived["decode_tokens_per_s"] = (
                self.counters.get("decode_tokens", 0) / decode_t
            )
        prefill_t = self._times.get("prefill_time_s", 0.0)
        if prefill_t > 0:
            derived["prefill_tokens_per_s"] = (
                self.counters.get("prefill_tokens", 0) / prefill_t
            )
        return {
            "counters": {
                **{k: float(v) for k, v in self.counters.items()},
                **self._times,
            },
            "gauges": dict(self.gauges),
            "derived": derived,
            "help": HELP,
            "timings": {
                name: {
                    "sum": t.sum,
                    "count": t.count,
                    "quantiles": {
                        str(q): t.quantile(q) for q, _ in _QUANTILES
                    },
                    **(
                        {"exemplars": t.exemplars()}
                        if t._exemplars else {}
                    ),
                }
                for name, t in self._timings.items()
            },
        }

    def log_to(self, tracker, step: Optional[int] = None,
               prefix: str = "serve/") -> None:
        """Emit the snapshot through a tracking.py tracker (Jsonl/wandb/
        Noop all share the ``log(dict, step)`` shape). The router logs
        the same registry shape under ``router/``."""
        tracker.log(
            {f"{prefix}{k}": v for k, v in self.snapshot().items()}, step
        )
