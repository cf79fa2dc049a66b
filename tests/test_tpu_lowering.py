"""Cross-platform TPU lowering of the Pallas kernel — no TPU needed.

``jax.export`` with ``platforms=["tpu"]`` runs the full Pallas→Mosaic MLIR
lowering (where BlockSpec/rank/layout errors surface) at trace time on any
host; only the final Mosaic→LLO step happens on a real chip. This is the
regression net for VERDICT weak #2: the kernel's TPU lowering is validated
on every CPU suite run instead of only on first real-chip contact.
"""

import functools

import jax
import jax.numpy as jnp
import pytest

from progen_tpu.ops.pallas_attention import pallas_local_attention


def _export_for_tpu(fn, *args):
    return jax.export.export(jax.jit(fn), platforms=["tpu"])(*args)


class TestTpuLowering:
    @pytest.mark.parametrize("window", [256, 512])
    def test_forward_lowers_for_tpu(self, window):
        q = jnp.zeros((2, 8, 1024, 64), jnp.bfloat16)
        exp = _export_for_tpu(
            functools.partial(pallas_local_attention, window_size=window),
            q, q, q,
        )
        mlir = exp.mlir_module()
        assert "tpu_custom_call" in mlir  # the Mosaic kernel made it in

    @pytest.mark.parametrize("bwd_impl", ["kv", "halo", "kv_g4", "kv_g8"])
    def test_backward_lowers_for_tpu(self, bwd_impl):
        q = jnp.zeros((2, 8, 1024, 64), jnp.bfloat16)

        def loss(q, k, v):
            return pallas_local_attention(
                q, k, v, 256, None, False, bwd_impl
            ).astype(jnp.float32).sum()

        exp = _export_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
        assert "tpu_custom_call" in exp.mlir_module()

    @pytest.mark.parametrize("bwd_impl", ["kv", "halo", "kv_g4"])
    def test_backward_lowers_for_tpu_w512(self, bwd_impl):
        # the long8k shapes: w=512 is where VMEM pressure peaks
        q = jnp.zeros((1, 8, 2048, 64), jnp.bfloat16)

        def loss(q, k, v):
            return pallas_local_attention(
                q, k, v, 512, None, False, bwd_impl
            ).astype(jnp.float32).sum()

        exp = _export_for_tpu(jax.grad(loss, argnums=(0, 1, 2)), q, q, q)
        assert "tpu_custom_call" in exp.mlir_module()

    def test_forward_lowers_f32(self):
        q = jnp.zeros((1, 2, 512, 64), jnp.float32)
        exp = _export_for_tpu(
            functools.partial(pallas_local_attention, window_size=128),
            q, q, q,
        )
        assert "tpu_custom_call" in exp.mlir_module()

    @pytest.mark.parametrize(
        "window,seq,structure",
        [
            # tiny-pallas phase structure: scan, no remat
            (256, 512, {"scan_layers": True}),
            # long8k.toml structure: scan + remat + blocked SGU
            (512, 1024, {"scan_layers": True, "remat": True,
                         "sgu_block_size": 512}),
        ],
    )
    def test_full_model_grad_lowers_for_tpu(self, window, seq, structure,
                                            monkeypatch):
        """The whole model fwd+bwd with use_pallas_attn — the program the
        train-*-pallas bench phases Mosaic-compile on-chip. Standalone
        kernel lowering (above) passed in round 3 while the full train
        step still timed out on hardware, so the integrated graph (layer
        stack + custom VJP + the measured_impls mixed path) gets its own
        offline lowering net. d=64 matches the bench head dim; w picks
        the policy branch (256 -> xla fwd + halo bwd, 512 -> pallas g4
        fwd + kv bwd)."""
        import flax.linen as nn

        from progen_tpu.config import ProGenConfig
        from progen_tpu.models.progen import ProGen
        from progen_tpu.training.loss import cross_entropy

        cfg = ProGenConfig(
            num_tokens=64, dim=128, depth=2, heads=2, dim_head=64,
            window_size=window, seq_len=seq, global_mlp_depth=1,
            ff_mult=2, dtype="bfloat16", use_pallas_attn=True,
            **structure,
        )
        model = ProGen(cfg)
        tokens = jnp.zeros((2, seq + 1), jnp.int32)
        params = nn.meta.unbox(
            model.init(jax.random.PRNGKey(0), tokens[:, :-1])["params"]
        )

        def loss_fn(params, tokens):
            logits = model.apply({"params": params}, tokens[:, :-1])
            return cross_entropy(logits, tokens[:, 1:]).mean()

        # the layer picks interpret mode off jax.default_backend() (CPU on
        # this host); exporting FOR tpu must trace the compiled path the
        # chip will run
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        exp = _export_for_tpu(jax.grad(loss_fn), params, tokens)
        mlir = exp.mlir_module()
        # w=256 takes the mixed path: Pallas backward only; w=512 is
        # Pallas in both directions — either way the custom call must
        # survive into the TPU module
        assert "tpu_custom_call" in mlir

    @pytest.mark.parametrize("g", [4, 8])
    def test_forward_lowers_for_tpu_bh_block(self, g):
        """The batched (g, w, d) forward blocks must survive the Mosaic
        MLIR pipeline at bench shapes (bh=16, both windows)."""
        q = jnp.zeros((2, 8, 1024, 64), jnp.bfloat16)
        for window in (256, 512):
            exp = _export_for_tpu(
                functools.partial(
                    pallas_local_attention,
                    window_size=window,
                    bh_block=g,
                ),
                q, q, q,
            )
            assert "tpu_custom_call" in exp.mlir_module()


class TestFusedLayerKernelsLowerForTpu:
    """The fused layer kernels (ops/pallas_layers.py) through the same
    Pallas -> Mosaic lowering, forward and backward, at ProGen-tiny's and
    ProGen-large's widths and at the dtype the train step feeds them
    (bf16 activations). The first on-chip run refused fused_norm_shift's
    one-row halo block at exactly this stage (block shapes must be
    (8, 128)-aligned), which this net now catches on the CPU."""

    # (d, gate width = ff_mult * d / 2): tiny, large
    WIDTHS = [(512, 1024), (1792, 3584)]
    N, BLOCK, EPS = 1024, 256, 1e-5

    @pytest.mark.parametrize("d", [w[0] for w in WIDTHS])
    @pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
    def test_norm_shift_lowers_fwd_and_bwd(self, d, dtype):
        from progen_tpu.ops.pallas_layers import fused_norm_shift

        x = jnp.zeros((2, self.N, d), dtype)
        scale = jnp.ones((d,), jnp.float32)

        def fwd(x, s):
            return fused_norm_shift(x, s, self.EPS, self.BLOCK, False,
                                    "bfloat16")

        assert "tpu_custom_call" in _export_for_tpu(
            fwd, x, scale
        ).mlir_module()
        grad = jax.grad(
            lambda x, s: fwd(x, s).astype(jnp.float32).sum(),
            argnums=(0, 1),
        )
        # the VJP differentiates the XLA reference: it must lower too
        _export_for_tpu(grad, x, scale)

    @pytest.mark.parametrize("d_half", [w[1] for w in WIDTHS])
    def test_sgu_mix_gate_lowers_fwd_and_bwd(self, d_half):
        from progen_tpu.ops.pallas_layers import (
            fused_sgu_mix_gate,
            safe_layer_block,
        )

        n = self.N
        block = safe_layer_block("sgu_mix", self.BLOCK, n, d_half,
                                 jnp.bfloat16)
        x = jnp.zeros((2, n, d_half), jnp.bfloat16)
        w = jnp.zeros((n, n), jnp.float32)
        b = jnp.ones((n, 1), jnp.float32)
        s = jnp.ones((d_half,), jnp.float32)

        def fwd(x, g, w, b, s):
            return fused_sgu_mix_gate(x, g, w, b, s, self.EPS, block,
                                      False, "bfloat16")

        assert "tpu_custom_call" in _export_for_tpu(
            fwd, x, x, w, b, s
        ).mlir_module()
        grad = jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4),
        )
        _export_for_tpu(grad, x, x, w, b, s)

    def test_fused_model_grad_lowers_for_tpu(self, monkeypatch):
        """The whole model fwd+bwd with use_fused_layer_kernels at tiny's
        width — the program chip_smoke.py's fused leg compiles."""
        import flax.linen as nn

        from progen_tpu.config import ProGenConfig
        from progen_tpu.models.progen import ProGen
        from progen_tpu.training.loss import cross_entropy

        cfg = ProGenConfig(
            num_tokens=256, dim=512, depth=2, heads=8, dim_head=64,
            window_size=256, seq_len=1024, global_mlp_depth=1,
            dtype="bfloat16", use_fused_layer_kernels=True,
        )
        model = ProGen(cfg)
        tokens = jnp.zeros((2, cfg.seq_len + 1), jnp.int32)
        params = nn.meta.unbox(jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens[:, :-1])
        )["params"])

        def loss_fn(params, tokens):
            logits = model.apply({"params": params}, tokens[:, :-1])
            return cross_entropy(logits, tokens[:, 1:]).mean()

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        mlir = _export_for_tpu(jax.grad(loss_fn), params, tokens).mlir_module()
        assert mlir.count("tpu_custom_call") >= 2  # norm-shift + SGU


class TestDecodeAttentionLowering:
    """The decode step's attention kernel over the slot pool
    (ops/pallas_decode_attention.py) at the shapes it is served at."""

    @pytest.mark.parametrize(
        "slots,heads,dh,window,dtype",
        [
            (32, 14, 128, 512, jnp.bfloat16),  # ProGen-large, the gen cell
            (8, 8, 64, 512, jnp.bfloat16),  # long8k served
            (4, 2, 64, 128, jnp.float32),  # the engine test's model
        ],
    )
    def test_pooled_kernel_lowers_for_tpu(self, slots, heads, dh, window,
                                          dtype):
        from progen_tpu.ops.pallas_decode_attention import (
            pooled_decode_attention,
        )

        ring = 2 * window
        kv = jnp.zeros((slots, 1, heads, ring, dh), dtype)
        exp = _export_for_tpu(
            functools.partial(pooled_decode_attention, window=window),
            jnp.zeros((slots, heads, dh), dtype), kv, kv,
            jnp.zeros((slots, ring), jnp.int32),
            jnp.zeros((slots,), jnp.int32),
        )
        assert "tpu_custom_call" in exp.mlir_module()


class TestRowWriteLowering:
    """A decode step's cache writes (``models/layers.py::_update_at``'s
    batching rule over the slots) at the leaf shapes the served cells
    write, 32 slots: the row-write kernel (``ops/pallas_row_write.py``)
    with its output aliased to the leaf, or, for a leaf it does not fit,
    the one select that rewrites it."""

    @pytest.mark.parametrize(
        "leaf,dtype,axis,kernel",
        [
            ((1, 14, 1024, 128), jnp.bfloat16, 2, True),  # ProGen-large K, V
            ((1, 1024, 3584), jnp.float32, 1, True),  # its gate history
            ((1024,), jnp.int32, 0, False),  # its slot_pos: along lanes
            ((1536, 512), jnp.bfloat16, 0, True),  # kanana2-30b-a3b latent
            ((1536, 64), jnp.bfloat16, 0, False),  # its rope key: 64 lanes
            ((8, 4608, 128), jnp.bfloat16, 1, True),  # trinity's K, V rings
            ((8, 11264, 128), jnp.bfloat16, 1, True),  # its full layer's rows
        ],
    )
    def test_the_batched_rule_lowers_for_tpu(self, monkeypatch, leaf, dtype,
                                             axis, kernel):
        import re

        from progen_tpu.models.layers import _update_at
        from progen_tpu.ops import pallas_decode_attention

        # the rule as a TPU process traces it: its backend test and the
        # kernel's interpret switch both read the backend
        monkeypatch.setattr(pallas_decode_attention, "on_tpu", lambda: True)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        jax.clear_caches()
        row = list(leaf)
        row[axis] = 1
        exp = _export_for_tpu(
            jax.vmap(_update_at(axis)),
            jnp.zeros((32,) + leaf, dtype), jnp.zeros((32, *row), dtype),
            jnp.zeros((32,), jnp.int32),
        )
        jax.clear_caches()
        mlir = exp.mlir_module()
        calls = [ln for ln in mlir.splitlines()
                 if "stablehlo.custom_call @tpu_custom_call" in ln]
        assert len(calls) == int(kernel)
        if kernel:
            # the leaf, the kernel body's first argument, is the operand
            # its output aliases: written in place
            operands = re.search(r"tpu_custom_call\(([^)]*)\)",
                                 calls[0]).group(1).split(", ")
            alias = re.search(r"output_operand_alias<output_tuple_indices = "
                              r"\[\], operand_index = (\d+)", calls[0])
            assert operands[int(alias.group(1))] == "%arg0"
        else:
            assert "stablehlo.select" in mlir
            assert "dynamic_update_slice" not in mlir
