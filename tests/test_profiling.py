"""Profiling helpers: FLOPs accounting, StepTimer, hardware-RNG switch."""

import time

import pytest

from progen_tpu import profiling
from progen_tpu.config import ProGenConfig


class TestFlops:
    def test_flops_per_token_scales_with_params(self):
        small = ProGenConfig(dim=256, depth=4, seq_len=512, window_size=128)
        big = ProGenConfig(dim=512, depth=8, seq_len=512, window_size=128)
        assert profiling.flops_per_token(big) > profiling.flops_per_token(
            small
        )
        # dominated by 6N
        assert profiling.flops_per_token(small) > 6 * small.num_params()

    def test_flops_long8k_sgu_not_params_convention(self):
        # At n=8192 the (n, n) spatial matrices must be charged by their
        # actual per-token work (6*n*d_half), not 6*params (6*n*n) — the
        # params convention overstates the SGU term n/d_half = 8x here.
        cfg = ProGenConfig(
            dim=512, depth=12, heads=8, dim_head=64,
            window_size=512, seq_len=8192, global_mlp_depth=2,
        )
        n, d_half = 8192, (4 * 512) // 2  # 1024
        dense = 6 * (cfg.num_params() - 2 * n * n)
        sgu = 2 * 6 * n * d_half
        attn = 12 * cfg.depth * cfg.heads * cfg.dim_head * (2 * 512)
        assert profiling.flops_per_token(cfg) == dense + sgu + attn
        # the old 6*num_params accounting was exactly 6*n*(n - d_half)
        # per gMLP layer too high
        old = 6 * cfg.num_params() + attn
        assert old - profiling.flops_per_token(cfg) == 2 * 6 * n * (n - d_half)

    def test_flops_default_config_coincides_with_params_convention(self):
        # default: n=1024 == d_half=1024, so the corrected formula equals
        # the plain 6*num_params convention — the tiny/default numbers in
        # prior BENCH records are unchanged by the fix
        cfg = ProGenConfig()
        attn = 12 * cfg.depth * cfg.heads * cfg.dim_head * (
            2 * cfg.window_size
        )
        assert profiling.flops_per_token(cfg) == 6 * cfg.num_params() + attn

    def test_unknown_tpu_kind_is_an_error(self):
        # a utilization against a guessed peak is worse than none
        class Dev:
            platform = "tpu"
            device_kind = "TPU v99 mystery"

        with pytest.raises(ValueError, match="TPU v99 mystery"):
            profiling.peak_flops(Dev())

    def test_cpu_has_no_peak(self):
        import jax

        assert profiling.peak_flops(jax.devices("cpu")[0]) is None

    @pytest.mark.parametrize(
        "kind, peak",
        [("TPU v4", 275e12), ("TPU v5 lite", 197e12), ("TPU v5p", 459e12)],
    )
    def test_peak_flops_by_kind(self, kind, peak):
        class Dev:
            platform = "tpu"
            device_kind = kind

        assert profiling.peak_flops(Dev()) == peak


class TestStepTimer:
    def test_warmup_skipped_then_metrics(self):
        t = profiling.StepTimer(
            n_chips=2, flops_per_tok=1000, peak=1e6, warmup=1
        )
        assert t.tick(100) is None  # establishes t0
        assert t.tick(100) is None  # warmup step discarded
        time.sleep(0.01)
        out = t.tick(100)
        assert out is not None
        assert out["tokens_per_sec_per_chip"] > 0
        assert 0 < out["mfu"] < 1e6
        assert out["step_ms"] >= 10.0

    def test_no_peak_means_no_mfu(self):
        # CPU: throughput is still reported, a utilization is not
        t = profiling.StepTimer(n_chips=1, flops_per_tok=10, peak=None,
                                warmup=0)
        t.tick(0)
        out = t.tick(50)
        assert "mfu" not in out
        assert out["tokens_per_sec_per_chip"] > 0

    def test_mfu_formula(self):
        t = profiling.StepTimer(n_chips=1, flops_per_tok=10, peak=1e3,
                                warmup=0)
        t.tick(0)
        time.sleep(0.005)
        out = t.tick(50)
        assert out["mfu"] == pytest.approx(
            out["tokens_per_sec_per_chip"] * 10 / 1e3
        )


class TestHardwareRng:
    def test_switch_and_restore(self):
        import jax

        from progen_tpu.utils.rng import use_default_rng, use_hardware_rng

        try:
            use_hardware_rng()
            key = jax.random.PRNGKey(0)
            # rbg keys are 4x uint32
            assert jax.random.uniform(key, (4,)).shape == (4,)
        finally:
            use_default_rng()


class TestStartupLine:
    def test_announce_startup_reports_the_device(self, capsys, monkeypatch):
        import json

        import jax

        from progen_tpu.parallel.partition import make_mesh

        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/cache")
        mesh = make_mesh(data=2, seq=1, model=2)
        rec = profiling.announce_startup("train", mesh, codec="python")
        line = capsys.readouterr().err.strip()
        assert line.startswith("startup: ")
        assert json.loads(line[len("startup: "):]) == rec
        dev = jax.devices()[0]
        assert rec["platform"] == dev.platform == "cpu"
        assert rec["device_kind"] == dev.device_kind
        assert rec["device_count"] == len(jax.devices())
        assert rec["mesh"] == {"data": 2, "seq": 1, "model": 2}
        assert rec["jax"] == jax.__version__
        assert rec["compile_cache_dir"] == "/some/cache"
        assert rec["codec"] == "python" and rec["role"] == "train"
