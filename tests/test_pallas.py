"""Pallas windowed-attention kernel vs the XLA golden (interpret mode on
CPU; the same kernel runs compiled on TPU — see bench.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.ops.attention import local_attention
from progen_tpu.ops.pallas_attention import pallas_local_attention

SHAPE = (2, 3, 64, 32)  # (b, h, n, d)


def _qkv(key, shape=SHAPE, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(key), 3)
    return (
        jax.random.normal(kq, shape, dtype),
        jax.random.normal(kk, shape, dtype),
        jax.random.normal(kv, shape, dtype),
    )


class TestPallasForward:
    @pytest.mark.parametrize("window", [8, 16, 32])
    def test_matches_xla_golden(self, window):
        q, k, v = _qkv(0)
        out = pallas_local_attention(q, k, v, window, None, True)
        ref = local_attention(q, k, v, window_size=window)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_window_zero_dilution_preserved(self):
        """First-window rows include the phantom zero keys in the softmax
        (upstream parity) — compare against the golden which models it."""
        q, k, v = _qkv(1, (1, 1, 16, 8))
        out = pallas_local_attention(q, k, v, 8, None, True)
        ref = local_attention(q, k, v, window_size=8)
        np.testing.assert_allclose(out[:, :, :8], ref[:, :, :8], atol=1e-5)

    def test_bf16_io_f32_softmax(self):
        q, k, v = _qkv(2, (1, 2, 32, 16), jnp.bfloat16)
        out = pallas_local_attention(q, k, v, 8, None, True)
        assert out.dtype == jnp.bfloat16
        ref = local_attention(q, k, v, window_size=8)
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), atol=3e-2,
            rtol=3e-2,
        )


class TestPallasBackward:
    @pytest.mark.parametrize("window", [8, 16])
    @pytest.mark.parametrize("bwd_impl", ["kv", "halo", "xla"])
    def test_grads_match_xla_golden(self, window, bwd_impl):
        q, k, v = _qkv(3)

        def loss_pallas(q, k, v):
            out = pallas_local_attention(q, k, v, window, None, True,
                                         bwd_impl)
            return (out * jnp.arange(out.size).reshape(out.shape)).sum()

        def loss_ref(q, k, v):
            out = local_attention(q, k, v, window_size=window)
            return (out * jnp.arange(out.size).reshape(out.shape)).sum()

        gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gp, gr, "qkv"):
            np.testing.assert_allclose(
                a, b, atol=2e-3, rtol=2e-3, err_msg=f"d{name} mismatch"
            )

    def test_bwd_impls_agree(self):
        """The kv-centric and halo backwards are the same math reassociated
        differently — grads must agree to f32 reassociation tolerance."""
        q, k, v = _qkv(5, (2, 2, 64, 16))

        def grads(impl):
            return jax.grad(
                lambda q, k, v: pallas_local_attention(
                    q, k, v, 16, None, True, impl
                ).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

        for a, b, name in zip(grads("kv"), grads("halo"), "qkv"):
            np.testing.assert_allclose(
                a, b, atol=1e-5, rtol=1e-5, err_msg=f"d{name} mismatch"
            )

    @pytest.mark.parametrize("g", [2, 4])
    def test_kv_batched_matches_g1(self, g):
        """kv_g<N>: identical math to kv, g batch-heads per program."""
        q, k, v = _qkv(10, (2, 2, 64, 16))  # bh = 4

        def grads(impl):
            return jax.grad(
                lambda q, k, v: pallas_local_attention(
                    q, k, v, 16, None, True, impl
                ).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

        for a, b, name in zip(grads("kv"), grads(f"kv_g{g}"), "qkv"):
            np.testing.assert_allclose(
                a, b, atol=1e-6, rtol=1e-6, err_msg=f"d{name} mismatch"
            )

    def test_kv_batched_non_dividing_falls_back(self):
        q, k, v = _qkv(11, (2, 3, 32, 8))  # bh = 6: g=4 -> largest is 3

        ga = jax.grad(lambda q: pallas_local_attention(
            q, k, v, 8, None, True, "kv_g4").sum())(q)
        gb = jax.grad(lambda q: pallas_local_attention(
            q, k, v, 8, None, True, "kv").sum())(q)
        np.testing.assert_allclose(ga, gb, atol=1e-6, rtol=1e-6)

    @pytest.mark.parametrize("bwd_impl", ["kv", "halo"])
    def test_last_window_keys_get_gradient(self, bwd_impl):
        """Neither backward may drop the final window's k/v gradient."""
        q, k, v = _qkv(4, (1, 1, 32, 8))

        def f(k):
            return pallas_local_attention(
                q, k, v, 8, None, True, bwd_impl
            ).sum()

        gk = jax.grad(f)(k)
        assert float(jnp.abs(gk[:, :, -8:]).sum()) > 0

    def test_unknown_bwd_impl_raises(self):
        q, k, v = _qkv(6, (1, 1, 16, 8))
        with pytest.raises(ValueError, match="bwd_impl"):
            jax.grad(
                lambda q: pallas_local_attention(
                    q, k, v, 8, None, True, "nope"
                ).sum()
            )(q)


class TestHaloVariant:
    """pallas_local_attention_halo: window 0's previous window supplied by
    a ring neighbor (parallel/ring_attention.py) instead of the phantom
    zeros — the sequence-parallel composition. Golden: local_attention
    with first_prev_k/v."""

    def _args(self, key, shape=(2, 2, 32, 8), w=8):
        b, h, n, d = shape
        ks = jax.random.split(jax.random.PRNGKey(key), 5)
        q, k, v = (jax.random.normal(kk, shape) for kk in ks[:3])
        hk = jax.random.normal(ks[3], (b, h, w, d))
        hv = jax.random.normal(ks[4], (b, h, w, d))
        return q, k, v, hk, hv

    @pytest.mark.parametrize("fwd_impl", ["pallas", "xla"])
    def test_forward_matches_golden(self, fwd_impl):
        from progen_tpu.ops.pallas_attention import (
            pallas_local_attention_halo,
        )

        q, k, v, hk, hv = self._args(20)
        out = pallas_local_attention_halo(
            q, k, v, hk, hv, 8, None, True, "kv", 1, fwd_impl
        )
        ref = local_attention(
            q, k, v, window_size=8, first_prev_k=hk, first_prev_v=hv
        )
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_zero_halo_equals_plain(self):
        from progen_tpu.ops.pallas_attention import (
            pallas_local_attention_halo,
        )

        q, k, v, hk, hv = self._args(21)
        out = pallas_local_attention_halo(
            q, k, v, jnp.zeros_like(hk), jnp.zeros_like(hv), 8, None, True
        )
        plain = pallas_local_attention(q, k, v, 8, None, True)
        np.testing.assert_allclose(out, plain, atol=1e-6, rtol=1e-6)

    @pytest.mark.parametrize("bwd_impl", ["kv", "kv_g2", "halo", "xla"])
    def test_all_grads_match_golden(self, bwd_impl):
        """dq, dk, dv AND d_halo_k/d_halo_v vs XLA autodiff of the golden
        — the halo grad is what the ring backward ppermutes back to the
        left neighbor, so it must be exact, not just plausible."""
        from progen_tpu.ops.pallas_attention import (
            pallas_local_attention_halo,
        )

        q, k, v, hk, hv = self._args(22)

        def loss(fn):
            return lambda *a: (
                fn(*a) * jnp.arange(q.size).reshape(q.shape)
            ).sum()

        gp = jax.grad(
            loss(lambda q_, k_, v_, hk_, hv_: pallas_local_attention_halo(
                q_, k_, v_, hk_, hv_, 8, None, True, bwd_impl)),
            argnums=(0, 1, 2, 3, 4),
        )(q, k, v, hk, hv)
        gr = jax.grad(
            loss(lambda q_, k_, v_, hk_, hv_: local_attention(
                q_, k_, v_, window_size=8,
                first_prev_k=hk_, first_prev_v=hv_)),
            argnums=(0, 1, 2, 3, 4),
        )(q, k, v, hk, hv)
        for a, b, name in zip(gp, gr, ["dq", "dk", "dv", "dhk", "dhv"]):
            np.testing.assert_allclose(
                a, b, atol=2e-3, rtol=2e-3, err_msg=f"{name} mismatch"
            )

    def test_halo_receives_gradient(self):
        from progen_tpu.ops.pallas_attention import (
            pallas_local_attention_halo,
        )

        q, k, v, hk, hv = self._args(23)
        ghk = jax.grad(
            lambda hk_: pallas_local_attention_halo(
                q, k, v, hk_, hv, 8, None, True
            ).sum()
        )(hk)
        assert float(jnp.abs(ghk).sum()) > 0


class TestMixedImpl:
    """fwd_impl="xla" + Pallas backward: the per-direction measured-winner
    combo (the policy table's rows: XLA fwd wins at w=256, Pallas bwd wins
    at both windows). Primal must equal the XLA golden exactly; grads must
    match XLA autodiff to the same tolerance as the pure-Pallas path."""

    def test_forward_is_xla_golden(self):
        q, k, v = _qkv(7)
        out = pallas_local_attention(
            q, k, v, 16, None, True, "halo", 1, "xla"
        )
        ref = local_attention(q, k, v, window_size=16)
        np.testing.assert_allclose(out, ref, atol=0, rtol=0)

    @pytest.mark.parametrize("bwd_impl", ["kv", "halo", "xla"])
    def test_grads_match_xla_autodiff(self, bwd_impl):
        q, k, v = _qkv(8)

        def loss(fn):
            return lambda q, k, v: (
                fn(q, k, v) * jnp.arange(q.size).reshape(q.shape)
            ).sum()

        gm = jax.grad(
            loss(lambda q, k, v: pallas_local_attention(
                q, k, v, 16, None, True, bwd_impl, 1, "xla")),
            argnums=(0, 1, 2),
        )(q, k, v)
        gr = jax.grad(
            loss(lambda q, k, v: local_attention(q, k, v, window_size=16)),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b, name in zip(gm, gr, "qkv"):
            np.testing.assert_allclose(
                a, b, atol=2e-3, rtol=2e-3, err_msg=f"d{name} mismatch"
            )

    def test_unknown_fwd_impl_raises(self):
        q, k, v = _qkv(9, (1, 1, 16, 8))
        with pytest.raises(ValueError, match="fwd_impl"):
            pallas_local_attention(q, k, v, 8, None, True, "kv", 1, "cuda")

    def test_measured_policy_table(self, monkeypatch, tmp_path):
        from progen_tpu.ops import pallas_attention as pa

        # pin the built-in fallback table: the live pallas_policy.json is a
        # bench-rewritten artifact whose winners legitimately change with
        # new on-chip measurements — lookup MECHANICS are what's under test
        monkeypatch.setattr(pa, "_POLICY_PATH", tmp_path / "absent.json")
        assert pa.measured_impls(256) == ("xla", "halo", 1)
        assert pa.measured_impls(512) == ("pallas", "kv", 4)
        # unmeasured window: nearest measured window's winners apply
        # (w=1024 is closer to 512 in log-space than to 256)
        assert pa.measured_impls(1024) == ("pallas", "kv", 4)
        assert pa.measured_impls(128) == ("xla", "halo", 1)

    def test_policy_decision_annotates_extrapolation(self):
        from progen_tpu.ops.pallas_attention import policy_decision

        exact = policy_decision(512, n=1024, bh=128)
        assert exact["exact_shape_match"]
        extrap = policy_decision(512, n=8192, bh=16)  # long8k shapes
        assert not extrap["exact_shape_match"]
        assert extrap["requested"] == {"window": 512, "n": 8192, "bh": 16}

    def test_policy_record_and_shape_aware_lookup(self, tmp_path):
        from progen_tpu.ops import pallas_attention as pa

        path = tmp_path / "policy.json"
        pa.record_policy_entry(
            {"window": 512, "n": 1024, "bh": 128,
             "fwd": "pallas", "bwd": "kv", "bh_block": 4}, path)
        pa.record_policy_entry(
            {"window": 512, "n": 8192, "bh": 16,
             "fwd": "pallas", "bwd": "kv_g4", "bh_block": 1}, path)
        # shape-aware: same window, different n picks its own entry
        assert pa.policy_decision(512, n=8192, bh=16, path=path)[
            "bwd"] == "kv_g4"
        assert pa.policy_decision(512, n=1024, bh=128, path=path)[
            "bwd"] == "kv"
        # re-recording a key replaces, never duplicates
        pa.record_policy_entry(
            {"window": 512, "n": 8192, "bh": 16,
             "fwd": "xla", "bwd": "halo", "bh_block": 1}, path)
        import json

        entries = json.loads(path.read_text())["entries"]
        assert len(entries) == 2
        assert pa.policy_decision(512, n=8192, path=path)["fwd"] == "xla"

    def test_policy_missing_file_falls_back(self, tmp_path):
        from progen_tpu.ops import pallas_attention as pa

        # unreadable/absent table -> the built-in rows, never a crash
        decision = pa.policy_decision(512, path=tmp_path / "nope.json")
        assert (decision["fwd"], decision["bwd"]) == ("pallas", "kv")
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        assert pa.policy_decision(256, path=bad)["bwd"] == "halo"

    def test_policy_skips_insane_values(self, tmp_path):
        import json

        from progen_tpu.ops import pallas_attention as pa

        # window=0 would ZeroDivisionError in the log-distance; such rows
        # must be filtered on read, falling back if nothing valid remains
        p = tmp_path / "p.json"
        p.write_text(json.dumps({"entries": [
            {"window": 0, "n": 1024, "bh": 128,
             "fwd": "xla", "bwd": "halo", "bh_block": 1},
            {"window": "big", "n": 1024, "bh": 128,
             "fwd": "xla", "bwd": "halo", "bh_block": 1},
        ]}))
        assert pa.policy_decision(512, path=p)["fwd"] == "pallas"  # fallback

    def test_policy_record_tolerates_legacy_rows(self, tmp_path):
        import json

        from progen_tpu.ops import pallas_attention as pa

        # a partial/hand-edited row must be dropped, not KeyError the
        # kernel phase after its chip time is already spent
        p = tmp_path / "p.json"
        p.write_text(json.dumps({"entries": [{"window": 512}]}))
        pa.record_policy_entry(
            {"window": 512, "n": 1024, "bh": 128,
             "fwd": "pallas", "bwd": "kv", "bh_block": 4}, p)
        entries = json.loads(p.read_text())["entries"]
        assert len(entries) == 1 and entries[0]["bwd"] == "kv"

    def test_policy_rejects_malformed_entry(self, tmp_path):
        from progen_tpu.ops import pallas_attention as pa

        with pytest.raises(ValueError, match="missing keys"):
            pa.record_policy_entry({"window": 512}, tmp_path / "p.json")


class TestModelIntegration:
    def test_use_pallas_attn_flag(self):
        """config.use_pallas_attn must trace end-to-end (VERDICT weak #2:
        the flag used to ImportError). The model dispatch auto-selects
        interpret mode off-TPU, so no monkeypatching is needed."""
        from progen_tpu.config import ProGenConfig
        from progen_tpu.models.progen import ProGen

        cfg = ProGenConfig(
            num_tokens=32, dim=32, seq_len=32, depth=2, window_size=8,
            global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2,
            dtype="float32", use_pallas_attn=True,
        )
        model = ProGen(cfg)
        tokens = jnp.zeros((1, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        out = model.apply({"params": params}, tokens)
        assert out.shape == (1, 32, 32)

        cfg_ref = ProGenConfig(**{**cfg.to_dict(), "use_pallas_attn": False})
        ref = ProGen(cfg_ref).apply({"params": params}, tokens)
        np.testing.assert_allclose(out, ref, atol=1e-4, rtol=1e-4)


class TestLayerPolicyDispatch:
    """The attention layer must hand pallas_local_attention the
    measured-winner impls for its window (and honor the config's explicit
    bh_block override)."""

    def _recorded_call(self, monkeypatch, window, seq, bh_block=0,
                       tmp_path=None):
        import progen_tpu.ops.pallas_attention as pa

        if tmp_path is not None:
            # pin the built-in fallback winners: dispatch mechanics, not
            # the live (bench-rewritten) policy file, are under test
            monkeypatch.setattr(pa, "_POLICY_PATH", tmp_path / "absent.json")
        from progen_tpu.config import ProGenConfig
        from progen_tpu.models.progen import ProGen

        calls = []
        real = pa.pallas_local_attention

        def recorder(q, k, v, w, scale, interpret, bwd_impl, g, fwd_impl):
            calls.append((w, bwd_impl, g, fwd_impl))
            # always run the cheap XLA path: this test pins dispatch, not
            # kernel numerics (covered elsewhere)
            return real(q, k, v, w, scale, True, bwd_impl, 1, "xla")

        monkeypatch.setattr(pa, "pallas_local_attention", recorder)
        cfg = ProGenConfig(
            num_tokens=32, dim=32, seq_len=seq, depth=1,
            window_size=window, global_mlp_depth=0, heads=2, dim_head=16,
            ff_mult=2, dtype="float32", use_pallas_attn=True,
            pallas_bh_block=bh_block,
        )
        model = ProGen(cfg)
        tokens = jnp.zeros((1, seq), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens)["params"]
        model.apply({"params": params}, tokens)
        return calls

    def test_small_window_gets_mixed_impls(self, monkeypatch, tmp_path):
        calls = self._recorded_call(monkeypatch, window=8, seq=32,
                                    tmp_path=tmp_path)
        assert calls and calls[-1] == (8, "halo", 1, "xla")

    def test_large_window_gets_pallas_impls(self, monkeypatch, tmp_path):
        calls = self._recorded_call(monkeypatch, window=512, seq=1024,
                                    tmp_path=tmp_path)
        assert calls and calls[-1] == (512, "kv", 4, "pallas")

    def test_config_bh_block_overrides_policy(self, monkeypatch, tmp_path):
        calls = self._recorded_call(monkeypatch, window=512, seq=1024,
                                    bh_block=2, tmp_path=tmp_path)
        assert calls and calls[-1][2] == 2

    def test_config_bh_block_one_forces_unbatched(self, monkeypatch,
                                                  tmp_path):
        # ADVICE r3: an explicit 1 must be distinguishable from unset —
        # it forces one-window-per-program even where the policy picks g=4
        calls = self._recorded_call(monkeypatch, window=512, seq=1024,
                                    bh_block=1, tmp_path=tmp_path)
        assert calls and calls[-1][2] == 1

    def test_xla_xla_policy_takes_plain_path(self, monkeypatch, tmp_path):
        # a shape whose measured winners are xla/xla must dispatch to the
        # plain autodiff path (no custom-VJP forward recompute): the
        # recorder must never be called
        import json

        import progen_tpu.ops.pallas_attention as pa

        table = tmp_path / "policy.json"
        table.write_text(json.dumps({"entries": [
            {"window": 8, "n": 32, "bh": 2,
             "fwd": "xla", "bwd": "xla", "bh_block": 1},
        ]}))
        monkeypatch.setattr(pa, "_POLICY_PATH", table)
        calls = self._recorded_call(monkeypatch, window=8, seq=32)
        assert calls == []


class TestBhBlock:
    """bh_block > 1: g batch-heads' windows per forward program — must be
    numerically identical to g=1 (same math, fatter blocks), with graceful
    fallback when g doesn't divide bh or would blow the VMEM budget."""

    @pytest.mark.parametrize("g", [2, 3, 6])
    def test_matches_g1(self, g):
        q, k, v = _qkv(4)  # bh = 6
        base = pallas_local_attention(q, k, v, 16, None, True)
        out = pallas_local_attention(q, k, v, 16, None, True, "kv", g)
        np.testing.assert_allclose(out, base, atol=1e-6, rtol=1e-6)

    def test_non_dividing_g_falls_back(self):
        q, k, v = _qkv(5)  # bh = 6; g=4 -> largest divisor <= 4 is 3
        out = pallas_local_attention(q, k, v, 16, None, True, "kv", 4)
        ref = local_attention(q, k, v, window_size=16)
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_vmem_budget_caps_g(self):
        from progen_tpu.ops.pallas_attention import _safe_bh_block

        # w=512: (g, 512, 1024) f32 probs -> 2 MB per g; 8 MB budget -> 4
        assert _safe_bh_block(8, 128, 512) == 4
        # w=256: 0.5 MB per g -> cap 16, bounded by requested 8
        assert _safe_bh_block(8, 128, 256) == 8
        # never 0, always divides
        assert _safe_bh_block(8, 6, 16) == 6
        assert _safe_bh_block(1, 7, 512) == 1

    def test_gradients_unaffected_by_bh_block(self):
        # bh_block only changes the forward schedule; the VJP ignores it
        q, k, v = _qkv(6)

        def loss(fn_g):
            return lambda q, k, v: fn_g(q, k, v).astype(jnp.float32).sum()

        g1 = jax.grad(loss(lambda q, k, v: pallas_local_attention(
            q, k, v, 16, None, True, "kv", 1)), argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(loss(lambda q, k, v: pallas_local_attention(
            q, k, v, 16, None, True, "kv", 2)), argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g1, g2):
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
