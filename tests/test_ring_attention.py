"""Ring halo-exchange sequence-parallel attention vs the single-device op."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.ops.attention import local_attention
from progen_tpu.parallel.partition import make_mesh
from progen_tpu.parallel.ring_attention import ring_local_attention


def _qkv(key, shape):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(key), 3)
    return (
        jax.random.normal(kq, shape),
        jax.random.normal(kk, shape),
        jax.random.normal(kv, shape),
    )


class TestRingAttention:
    @pytest.mark.parametrize("seq_shards", [2, 4, 8])
    def test_matches_local_attention(self, seq_shards):
        mesh = make_mesh(data=1, seq=seq_shards, model=1)
        q, k, v = _qkv(0, (2, 2, 64, 16))
        ref = local_attention(q, k, v, window_size=8)
        out = ring_local_attention(
            q, k, v, window_size=8, mesh=mesh, batch_axis=None
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_with_data_axis_too(self):
        mesh = make_mesh(data=2, seq=4, model=1)
        q, k, v = _qkv(1, (4, 2, 32, 8))
        ref = local_attention(q, k, v, window_size=8)
        out = ring_local_attention(q, k, v, window_size=8, mesh=mesh)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)

    def test_window_zero_dilution_preserved(self):
        """Shard 0 must zero its received halo (it wraps around the ring
        from the LAST shard) — keeping the reference's window-0 softmax
        dilution instead of attending to the sequence end."""
        mesh = make_mesh(data=1, seq=4, model=1)
        q, k, v = _qkv(2, (1, 1, 32, 8))
        ref = local_attention(q, k, v, window_size=8)
        out = ring_local_attention(
            q, k, v, window_size=8, mesh=mesh, batch_axis=None
        )
        np.testing.assert_allclose(
            np.asarray(out)[:, :, :8], np.asarray(ref)[:, :, :8], atol=1e-5
        )

    def test_gradients_flow_across_shards(self):
        """d(loss)/dk at a shard boundary must include the halo
        contribution from the neighboring shard's first window."""
        mesh = make_mesh(data=1, seq=4, model=1)
        q, k, v = _qkv(3, (1, 1, 32, 8))

        def ring_loss(k):
            return ring_local_attention(
                q, k, v, window_size=8, mesh=mesh, batch_axis=None
            ).sum()

        def ref_loss(k):
            return local_attention(q, k, v, window_size=8).sum()

        g_ring = jax.grad(ring_loss)(k)
        g_ref = jax.grad(ref_loss)(k)
        np.testing.assert_allclose(
            np.asarray(g_ring), np.asarray(g_ref), atol=1e-5
        )

    def test_misaligned_shards_raise(self):
        mesh = make_mesh(data=1, seq=8, model=1)
        q, k, v = _qkv(4, (1, 1, 32, 8))  # 32/(8 shards) = 4 < window 8
        with pytest.raises(ValueError):
            ring_local_attention(
                q, k, v, window_size=8, mesh=mesh, batch_axis=None
            )


class TestRingWithPallas:
    """use_pallas=True: each shard runs the halo-aware measured kernel
    (pallas_local_attention_halo) instead of the XLA dense path — the
    long-context multi-chip composition of the two flagship features."""

    def _policy(self, monkeypatch, tmp_path, fwd="pallas", bwd="kv"):
        import json

        import progen_tpu.ops.pallas_attention as pa

        # pin a policy whose winners exercise the Pallas path at the tiny
        # per-shard shapes the 8-device CPU mesh produces
        table = tmp_path / "policy.json"
        table.write_text(json.dumps({"entries": [
            {"window": 8, "n": 16, "bh": 4,
             "fwd": fwd, "bwd": bwd, "bh_block": 1},
        ]}))
        monkeypatch.setattr(pa, "_POLICY_PATH", table)

    @pytest.mark.parametrize("seq_shards", [2, 4])
    def test_forward_matches_gathered(self, seq_shards, monkeypatch,
                                      tmp_path):
        self._policy(monkeypatch, tmp_path)
        mesh = make_mesh(data=1, seq=seq_shards, model=1)
        q, k, v = _qkv(10, (2, 2, 64, 16))
        ref = local_attention(q, k, v, window_size=8)
        out = ring_local_attention(
            q, k, v, window_size=8, mesh=mesh, batch_axis=None,
            use_pallas=True,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4)

    def test_gradients_cross_shards(self, monkeypatch, tmp_path):
        """The halo grad (d_halo ppermuted back to the left neighbor by
        shard_map's transpose) must reproduce the gathered-op boundary
        gradients exactly."""
        self._policy(monkeypatch, tmp_path)
        mesh = make_mesh(data=1, seq=4, model=1)
        q, k, v = _qkv(11, (1, 1, 32, 8))

        g_ring = jax.grad(lambda k_: ring_local_attention(
            q, k_, v, window_size=8, mesh=mesh, batch_axis=None,
            use_pallas=True).sum())(k)
        g_ref = jax.grad(lambda k_: local_attention(
            q, k_, v, window_size=8).sum())(k)
        np.testing.assert_allclose(
            np.asarray(g_ring), np.asarray(g_ref), atol=1e-4
        )

    def test_xla_xla_policy_skips_kernel(self, monkeypatch, tmp_path):
        """A shape whose measured winners are xla/xla must use the plain
        dense path (no custom-VJP recompute) — and still be exact."""
        self._policy(monkeypatch, tmp_path, fwd="xla", bwd="xla")
        mesh = make_mesh(data=1, seq=2, model=1)
        q, k, v = _qkv(12, (1, 1, 32, 8))
        ref = local_attention(q, k, v, window_size=8)
        out = ring_local_attention(
            q, k, v, window_size=8, mesh=mesh, batch_axis=None,
            use_pallas=True,
        )
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)


class TestModelIntegration:
    """`config.use_ring_attn` + `ProGen(config, mesh=...)`: the explicit
    ring-collective attention as a path the real model (and therefore the
    train step) can invoke — full-model fwd/bwd parity vs the plain path."""

    def _setup(self, seq_shards, scan_layers=False, remat=False):
        import dataclasses

        from flax import linen as nn

        from progen_tpu.config import ProGenConfig
        from progen_tpu.models.progen import ProGen

        cfg = ProGenConfig(
            num_tokens=32, dim=32, seq_len=64, depth=3, window_size=8,
            global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2,
            dtype="float32", scan_layers=scan_layers, remat=remat,
        )
        mesh = make_mesh(data=2, seq=seq_shards, model=1)
        plain = ProGen(cfg)
        ring = ProGen(
            dataclasses.replace(cfg, use_ring_attn=True), mesh=mesh
        )
        tokens = jax.random.randint(
            jax.random.PRNGKey(7), (4, cfg.seq_len), 1, cfg.num_tokens
        )
        params = nn.meta.unbox(
            plain.init(jax.random.PRNGKey(0), tokens)["params"]
        )
        return plain, ring, params, tokens

    @pytest.mark.parametrize("seq_shards", [2, 4])
    def test_forward_parity(self, seq_shards):
        plain, ring, params, tokens = self._setup(seq_shards)
        ref = plain.apply({"params": params}, tokens)
        out = ring.apply({"params": params}, tokens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_same_param_tree(self):
        # init with ring enabled must yield the identical tree (the op is
        # parameter-free; init falls back to the local path) — checkpoints
        # are interchangeable across topologies
        from flax import linen as nn

        plain, ring, params, tokens = self._setup(2)
        ring_params = nn.meta.unbox(
            ring.init(jax.random.PRNGKey(0), tokens)["params"]
        )
        assert jax.tree.structure(params) == jax.tree.structure(ring_params)

    # remat=True: long8k ships remat; jax.checkpoint over the shard_map
    # ring must give the same grads as the plain path
    @pytest.mark.parametrize("remat", [False, True])
    def test_gradient_parity(self, remat):
        plain, ring, params, tokens = self._setup(2, remat=remat)

        def loss(model, p):
            return model.apply({"params": p}, tokens).astype(jnp.float32).sum()

        g_ref = jax.jit(jax.grad(lambda p: loss(plain, p)))(params)
        g_ring = jax.jit(jax.grad(lambda p: loss(ring, p)))(params)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=3e-3, rtol=2e-5
            ),
            g_ref,
            g_ring,
        )

    def test_scan_layers_forward_parity(self):
        plain, ring, params, tokens = self._setup(2, scan_layers=True)
        ref = plain.apply({"params": params}, tokens)
        out = ring.apply({"params": params}, tokens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=2e-5
        )

    def test_jitted_train_step_with_ring(self):
        """The production donated train step compiles and runs with the
        ring-attention model over a (data=2, seq=2) mesh."""
        from progen_tpu.parallel.partition import put_batch
        from progen_tpu.training.optimizer import make_optimizer
        from progen_tpu.training.step import (
            compile_train_step,
            init_train_state,
        )

        _, ring, _, _ = self._setup(2)
        optimizer = make_optimizer(1e-3)
        mesh = ring.mesh
        state, shardings = init_train_state(
            ring, optimizer, jax.random.PRNGKey(0),
            ring.config.seq_len, mesh=mesh,
        )
        step = compile_train_step(ring, optimizer, state, shardings, mesh)
        batch = np.random.default_rng(0).integers(
            1, 32, size=(2, 4, ring.config.seq_len + 1)
        ).astype(np.int32)
        with mesh:
            state, metrics = step(
                state, put_batch(batch, mesh, accum_axis=True)
            )
        assert np.isfinite(float(metrics["loss"]))
