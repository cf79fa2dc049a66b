"""Fused Pallas layer kernels (ops/pallas_layers.py) vs their unfused
XLA references, in interpret mode on CPU — the same kernels Mosaic
compiles on TPU (bench.py kernel-fused-w*). Covers values and grads for
both kernels, the policy table (dispatch, nearest-shape lookup, the
record round-trip that must preserve the attention table), and the
model-level flag (identical param tree, matching outputs/grads). The
Mosaic side of the same kernels is in tests/test_tpu_lowering.py.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.ops.pallas_layers import (
    fused_norm_shift,
    fused_sgu_mix_gate,
    layer_policy_decision,
    norm_shift,
    norm_shift_reference,
    record_layer_policy_entry,
    safe_layer_block,
    sgu_mix_gate,
    sgu_mix_gate_reference,
)

B, N, D = 2, 64, 32
EPS = 1e-5


def _inputs(seed, d=D, dtype=jnp.float32):
    kx, kg, kw = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(kx, (B, N, d), dtype)
    gate = jax.random.normal(kg, (B, N, d), dtype)
    w = jax.random.normal(kw, (N, N), jnp.float32) / N
    bias = jnp.ones((N, 1), jnp.float32)
    scale = jnp.linspace(0.5, 1.5, d).astype(jnp.float32)
    return x, gate, w, bias, scale


class TestFusedNormShift:
    @pytest.mark.parametrize("block", [16, 32, 64])
    def test_matches_reference_f32(self, block):
        x, _, _, _, scale = _inputs(0)
        out = fused_norm_shift(x, scale, EPS, block, True, "float32")
        ref = norm_shift_reference(x, scale, EPS, "float32")
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_matches_reference_bf16(self):
        x, _, _, _, scale = _inputs(1, dtype=jnp.bfloat16)
        out = fused_norm_shift(x, scale, EPS, 16, True, "bfloat16")
        ref = norm_shift_reference(x, scale, EPS, "bfloat16")
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), atol=3e-2,
            rtol=3e-2,
        )

    def test_odd_features_split_matches_reference(self):
        # d=30: the shifted/passthrough split is d - d//2 = 15
        x, _, _, _, scale = _inputs(2, d=30)
        out = fused_norm_shift(x, scale, EPS, 16, True, "float32")
        ref = norm_shift_reference(x, scale, EPS, "float32")
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_first_row_shifts_in_zeros(self):
        # row 0's shifted half must be zero (no previous token), not a
        # halo read of row -1
        x, _, _, _, scale = _inputs(3)
        out = fused_norm_shift(x, scale, EPS, 16, True, "float32")
        split = D - D // 2
        np.testing.assert_allclose(out[:, 0, :split], 0.0, atol=1e-7)

    def test_grads_match_reference(self):
        x, _, _, _, scale = _inputs(4)

        def loss_fused(x, s):
            return fused_norm_shift(
                x, s, EPS, 16, True, "float32"
            ).sum()

        def loss_ref(x, s):
            return norm_shift_reference(x, s, EPS, "float32").sum()

        gx, gs = jax.grad(loss_fused, argnums=(0, 1))(x, scale)
        rx, rs = jax.grad(loss_ref, argnums=(0, 1))(x, scale)
        np.testing.assert_allclose(gx, rx, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(gs, rs, atol=1e-4, rtol=1e-4)


class TestFusedSguMixGate:
    @pytest.mark.parametrize("block", [16, 32])
    def test_matches_reference_f32(self, block):
        x, gate, w, bias, scale = _inputs(5)
        out = fused_sgu_mix_gate(
            x, gate, w, bias, scale, EPS, block, True, "float32"
        )
        ref = sgu_mix_gate_reference(
            x, gate, w, bias, scale, EPS, "float32"
        )
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)

    def test_matches_reference_bf16(self):
        x, gate, w, bias, scale = _inputs(6, dtype=jnp.bfloat16)
        out = fused_sgu_mix_gate(
            x, gate, w, bias, scale, EPS, 16, True, "bfloat16"
        )
        ref = sgu_mix_gate_reference(
            x, gate, w, bias, scale, EPS, "bfloat16"
        )
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            out.astype(np.float32), ref.astype(np.float32), atol=3e-2,
            rtol=3e-2,
        )

    def test_causality(self):
        # output at row t must not change when later gate rows change:
        # the in-kernel tril mask + skipped upper-triangle blocks
        x, gate, w, bias, scale = _inputs(7)
        out = fused_sgu_mix_gate(
            x, gate, w, bias, scale, EPS, 16, True, "float32"
        )
        bumped = gate.at[:, N // 2:, :].add(10.0)
        out2 = fused_sgu_mix_gate(
            x, bumped, w, bias, scale, EPS, 16, True, "float32"
        )
        np.testing.assert_allclose(
            out[:, : N // 2], out2[:, : N // 2], atol=1e-5
        )

    def test_grads_match_reference(self):
        x, gate, w, bias, scale = _inputs(8)

        def loss_fused(x, g, w, b, s):
            return fused_sgu_mix_gate(
                x, g, w, b, s, EPS, 16, True, "float32"
            ).sum()

        def loss_ref(x, g, w, b, s):
            return sgu_mix_gate_reference(
                x, g, w, b, s, EPS, "float32"
            ).sum()

        grads = jax.grad(loss_fused, argnums=(0, 1, 2, 3, 4))(
            x, gate, w, bias, scale
        )
        refs = jax.grad(loss_ref, argnums=(0, 1, 2, 3, 4))(
            x, gate, w, bias, scale
        )
        for g, r in zip(grads, refs):
            np.testing.assert_allclose(g, r, atol=1e-4, rtol=1e-4)


class TestLayerPolicy:
    def test_decision_prefers_nearest_shape(self, tmp_path):
        path = tmp_path / "policy.json"
        path.write_text(json.dumps({"layer_entries": [
            {"kind": "sgu_mix", "n": 1024, "d": 512, "impl": "pallas",
             "block": 256},
            {"kind": "sgu_mix", "n": 8192, "d": 512, "impl": "xla",
             "block": 512},
        ]}))
        near_small = layer_policy_decision("sgu_mix", 2048, 512, path)
        near_large = layer_policy_decision("sgu_mix", 8192, 1024, path)
        assert near_small["n"] == 1024
        assert near_large["impl"] == "xla"
        assert not near_large["exact_shape_match"]

    def test_unknown_kind_raises(self):
        with pytest.raises(ValueError):
            layer_policy_decision("attention", 1024, 512)

    def test_record_preserves_attention_entries(self, tmp_path):
        path = tmp_path / "policy.json"
        path.write_text(json.dumps({
            "schema": "pallas-policy-v1",
            "entries": [{"window": 256, "n": 1024, "fwd": "xla"}],
            "layer_entries": [
                {"kind": "sgu_mix", "n": 1024, "d": 512,
                 "impl": "pallas", "block": 256},
            ],
        }))
        record_layer_policy_entry(
            {"kind": "sgu_mix", "n": 1024, "d": 512, "impl": "xla",
             "block": 128},
            path,
        )
        doc = json.loads(path.read_text())
        # the attention table must survive the layer-table write
        assert doc["entries"] == [
            {"window": 256, "n": 1024, "fwd": "xla"}
        ]
        # same (kind, n, d) replaced, not duplicated
        assert len(doc["layer_entries"]) == 1
        assert doc["layer_entries"][0]["impl"] == "xla"

    def test_record_rejects_incomplete_entry(self, tmp_path):
        with pytest.raises(ValueError):
            record_layer_policy_entry(
                {"kind": "sgu_mix", "n": 1024},
                tmp_path / "policy.json",
            )

    def test_safe_layer_block_divides_and_caps(self):
        pick = lambda *a: safe_layer_block("norm_shift", *a)
        assert pick(256, 64, 32) == 64  # capped at n
        assert pick(48, 64, 32) == 32   # walks to a divisor
        assert pick(4, 64, 32) is None  # below sublane tile
        # bf16 rows come in 16-row sublane tiles: a legal f32 tile of 8
        # or 24 rows is not one
        assert pick(24, 96, 32, jnp.bfloat16) == 16
        assert pick(8, 64, 32, jnp.bfloat16) is None
        with pytest.raises(ValueError):
            safe_layer_block("attention", 256, 64, 32)

    def test_sgu_tile_is_lane_aligned_or_whole(self):
        # the SGU's row tile is the lane dim of its (bn, bn) weight
        # tile: Mosaic wants a multiple of 128 there, or the whole axis
        pick = lambda *a: safe_layer_block("sgu_mix", *a)
        assert pick(256, 64, 32) == 64      # the whole sequence
        assert pick(48, 64, 32) is None     # a 32-row weight tile: no
        assert pick(200, 256, 32) == 128
        assert pick(256, 1024, 1024) == 256

    def test_safe_layer_block_fits_scoped_vmem_at_large_width(self):
        # measured on a v5e chip (PR 21): at ProGen-large's gate width
        # Mosaic refuses the 256-row tile ("Scoped allocation with size
        # 17.82M and limit 16.00M") and compiles the 128-row one; tiny's
        # width keeps 256
        bf16 = jnp.bfloat16
        assert safe_layer_block("sgu_mix", 256, 1024, 3584, bf16) == 128
        assert safe_layer_block("sgu_mix", 256, 1024, 1024, bf16) == 256
        assert safe_layer_block("norm_shift", 256, 1024, 1792, bf16) == 256

    def test_no_legal_tile_is_an_error_not_a_fallback(self):
        # the config asked for the kernel: it gets the kernel or an error
        x = jnp.zeros((1, 12, 32), jnp.float32)
        with pytest.raises(ValueError, match="no legal row tile"):
            norm_shift(x, jnp.ones((32,)), EPS, "float32",
                       block_override=4, interpret=True)

    def test_dispatch_override_matches_reference(self):
        # two 128-row tiles: the smallest multi-tile SGU shape Mosaic
        # would accept, so the dispatcher's tile picker lets it through
        n, d = 256, 32
        kx, kg, kw = jax.random.split(jax.random.PRNGKey(9), 3)
        x = jax.random.normal(kx, (B, n, d))
        gate = jax.random.normal(kg, (B, n, d))
        w = jax.random.normal(kw, (n, n)) / n
        bias = jnp.ones((n, 1))
        scale = jnp.full((d,), 1.1)
        out = sgu_mix_gate(
            x, gate, w, bias, scale, EPS, "float32",
            block_override=128, interpret=True,
        )
        ref = sgu_mix_gate_reference(
            x, gate, w, bias, scale, EPS, "float32"
        )
        np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)
        out = norm_shift(
            x, scale, EPS, "float32", block_override=16, interpret=True
        )
        np.testing.assert_allclose(
            out, norm_shift_reference(x, scale, EPS, "float32"),
            atol=1e-5, rtol=1e-5,
        )


class TestModelFlag:
    CFG = dict(
        num_tokens=32, dim=32, seq_len=256, depth=2, window_size=64,
        global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2,
        dtype="float32", pallas_layer_block=128,  # two tiles a sequence
    )

    def _init_and_apply(self, fused):
        from progen_tpu.config import ProGenConfig
        from progen_tpu.models.progen import ProGen

        cfg = ProGenConfig(use_fused_layer_kernels=fused, **self.CFG)
        model = ProGen(cfg)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, cfg.seq_len), 0, cfg.num_tokens
        )
        variables = model.init(jax.random.PRNGKey(0), tokens)
        return model, variables, tokens

    def test_param_tree_identical_across_flag(self):
        _, v_off, _ = self._init_and_apply(False)
        _, v_on, _ = self._init_and_apply(True)
        td_off = jax.tree_util.tree_structure(v_off)
        td_on = jax.tree_util.tree_structure(v_on)
        assert td_off == td_on  # checkpoints interchangeable
        for a, b in zip(
            jax.tree_util.tree_leaves(v_off),
            jax.tree_util.tree_leaves(v_on),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_outputs_match_across_flag(self):
        model_off, variables, tokens = self._init_and_apply(False)
        model_on, _, _ = self._init_and_apply(True)
        out_off = model_off.apply(variables, tokens)
        out_on = model_on.apply(variables, tokens)
        np.testing.assert_allclose(
            np.asarray(out_off), np.asarray(out_on), atol=1e-4,
            rtol=1e-4,
        )

    def test_grads_match_across_flag(self):
        model_off, variables, tokens = self._init_and_apply(False)
        model_on, _, _ = self._init_and_apply(True)

        def loss(model, params):
            return model.apply(
                {"params": params}, tokens
            ).astype(jnp.float32).sum()

        g_off = jax.grad(lambda p: loss(model_off, p))(
            variables["params"]
        )
        g_on = jax.grad(lambda p: loss(model_on, p))(
            variables["params"]
        )
        flat_off = jax.tree_util.tree_leaves(g_off)
        flat_on = jax.tree_util.tree_leaves(g_on)
        for a, b in zip(flat_off, flat_on):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=5e-3, rtol=5e-3
            )
