"""Model FLOP/s utilization: the FLOPs the forward and backward passes need
per token (benchmark/flops.py, recomputation not counted) times tokens per
second per chip, over the chip's bf16 peak."""

from benchmark import flops


def read(run, spec):
    if run.peak is None or not run.counters.get("tokens"):
        return None
    rate = run.counters["tokens"] / run.counters["window_s"] / run.counters["chips"]
    return 100.0 * rate * flops.train_flops_per_token(run.config) / run.peak["bf16_flops"]
