"""scan_layers: lax.scan over the uniform blocks must be a pure layout
change — same math, same training, same sampling."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen, stack_params, unstack_params

TINY = ProGenConfig(
    num_tokens=32,
    dim=32,
    seq_len=32,
    depth=4,
    window_size=8,
    global_mlp_depth=1,
    heads=2,
    dim_head=16,
    ff_mult=2,
    dtype="float32",
)
TINY_SCAN = dataclasses.replace(TINY, scan_layers=True)


@pytest.fixture(scope="module")
def unrolled():
    model = ProGen(TINY)
    tokens = jnp.zeros((1, TINY.seq_len), jnp.int32)
    params = meta.unbox(model.init(jax.random.PRNGKey(0), tokens))["params"]
    return model, params


class TestScanLayers:
    def test_param_layout(self):
        model = ProGen(TINY_SCAN)
        tokens = jnp.zeros((1, TINY.seq_len), jnp.int32)
        params = meta.unbox(
            model.init(jax.random.PRNGKey(0), tokens)
        )["params"]
        assert "layers" in params and "attn0" not in params
        # stacked leading axis = n_uniform = depth - global_mlp_depth = 3
        assert params["layers"]["attn"]["to_qkv"]["kernel"].shape[0] == 3
        assert "ff3" in params  # trailing gMLP block stays unrolled

    def test_logits_match_unrolled(self, unrolled):
        model, params = unrolled
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (2, TINY.seq_len), 0, TINY.num_tokens
        )
        ref = model.apply({"params": params}, tokens)

        scan_model = ProGen(TINY_SCAN)
        scan_params = stack_params(params, TINY_SCAN)
        out = scan_model.apply({"params": scan_params}, tokens)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5
        )

    def test_stack_unstack_round_trip(self, unrolled):
        _, params = unrolled
        stacked = stack_params(params, TINY_SCAN)
        back = unstack_params(stacked, TINY_SCAN)
        for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(params)[0],
            jax.tree_util.tree_flatten_with_path(back)[0],
        ):
            assert jax.tree_util.keystr(ka) == jax.tree_util.keystr(kb)
            np.testing.assert_array_equal(a, b)

    def test_training_step_matches_unrolled(self, unrolled):
        """One optimizer step in both layouts lands on the same weights."""
        from progen_tpu.training.optimizer import make_optimizer
        from progen_tpu.training.state import TrainState
        from progen_tpu.training.step import make_train_step

        model, params = unrolled
        optimizer = make_optimizer(1e-3)
        batch = jax.random.randint(
            jax.random.PRNGKey(2), (1, 2, TINY.seq_len + 1), 0, 32
        )

        s_unrolled = TrainState.create(params, optimizer)
        s_unrolled, m_unrolled = jax.jit(make_train_step(model, optimizer))(
            s_unrolled, batch
        )

        scan_model = ProGen(TINY_SCAN)
        s_scan = TrainState.create(stack_params(params, TINY_SCAN), optimizer)
        s_scan, m_scan = jax.jit(make_train_step(scan_model, optimizer))(
            s_scan, batch
        )
        np.testing.assert_allclose(
            float(m_scan["loss"]), float(m_unrolled["loss"]), rtol=1e-6
        )
        got = unstack_params(s_scan.params, TINY_SCAN)
        atol = 1e-6
        for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_flatten_with_path(s_unrolled.params)[0],
            jax.tree_util.tree_flatten_with_path(got)[0],
        ):
            np.testing.assert_allclose(
                a, b, atol=atol, err_msg=jax.tree_util.keystr(ka)
            )

    def test_sharding_resolves_for_scan_layout(self):
        from progen_tpu.parallel.partition import make_mesh, state_shardings
        from jax.sharding import PartitionSpec as P

        mesh = make_mesh(data=2, seq=1, model=4)
        model = ProGen(TINY_SCAN)
        abstract = jax.eval_shape(
            model.init,
            jax.random.PRNGKey(0),
            jax.ShapeDtypeStruct((1, TINY.seq_len), jnp.int32),
        )
        sh = state_shardings(abstract, mesh)["params"]
        # stacked layer axis replicated, output dim still model-sharded
        assert sh["layers"]["attn"]["to_qkv"]["kernel"].spec == P(
            None, None, "model"
        )

    def test_sample_fast_with_scan_params(self, unrolled):
        from progen_tpu.sampling import sample, sample_fast

        model, params = unrolled
        scan_model = ProGen(TINY_SCAN)
        scan_params = stack_params(params, TINY_SCAN)
        prime = jnp.array([5, 9, 11], jnp.int32)
        naive = np.asarray(
            sample(
                jax.random.PRNGKey(4), scan_model, scan_params, prime,
                TINY.seq_len, top_k=10, add_bos=True,
            )
        )
        fast = np.asarray(
            sample_fast(
                jax.random.PRNGKey(4), scan_model, scan_params, prime,
                TINY.seq_len, top_k=10, add_bos=True,
            )
        )
        np.testing.assert_array_equal(naive, fast)
