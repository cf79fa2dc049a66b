"""benchmark/reference/progen_ref.py against the model at a tiny size on
the CPU, so that the on-chip ``correct`` check is itself tested."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.reference import progen_ref
from progen_tpu.config import ProGenConfig
from progen_tpu.models.progen import ProGen, decode_model
from progen_tpu.training.loss import cross_entropy

TINY = dict(num_tokens=256, dim=64, depth=4, heads=2, dim_head=32,
            window_size=16, seq_len=64, global_mlp_depth=2, dtype="float32")


def build(scan_layers, **over):
    config = ProGenConfig(**{**TINY, **over}, scan_layers=scan_layers)
    model = ProGen(config)
    params = meta.unbox(model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, config.seq_len), jnp.int32)
    ))["params"]
    # move every leaf off its initial value, norms and biases included
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
        params,
    )
    return config, model, params


@pytest.mark.parametrize("scan_layers", [True, False])
def test_full_forward_matches_in_both_parameter_layouts(scan_layers):
    config, model, params = build(scan_layers)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (64,), 1, 256)
    want = model.apply({"params": params}, tokens[None])[0]
    got = progen_ref.forward(params, tokens, config.to_dict(), q_block=24)
    assert float(jnp.abs(got - want).max()) < 5e-5
    assert float(jnp.abs(want).max()) > 1.0  # the comparison is not of zeros


def test_a_row_shorter_than_seq_len_uses_the_causal_corner():
    config, model, params = build(True)
    tokens = jax.random.randint(jax.random.PRNGKey(3), (64,), 1, 256)
    want = model.apply({"params": params}, tokens[None])[0][:40]
    got = progen_ref.forward(params, tokens[:40], config.to_dict())
    assert float(jnp.abs(got - want).max()) < 5e-5


def test_prefill_then_decode_through_the_cache_matches_the_reference():
    config, model, params = build(False)
    dec = decode_model(model)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(4), (48,), 1, 256))
    cache = dec.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["cache"]
    got = []
    for tok in tokens:  # the first 20 stand for the prefill, the rest decode
        logits, mut = dec.apply({"params": params, "cache": cache},
                                jnp.asarray([[tok]]), mutable=["cache"])
        cache = mut["cache"]
        got.append(logits[0, 0])
    want = progen_ref.forward(params, jnp.asarray(tokens), config.to_dict())
    assert float(jnp.abs(jnp.stack(got) - want).max()) < 5e-5


def test_row_loss_is_the_training_loss_with_the_eos_mask():
    config, model, params = build(True)
    row = np.array(jax.random.randint(jax.random.PRNGKey(5), (65,), 1, 256))
    row[50:] = 0  # padding: the first pad trains (it is the EOS), the rest do not
    logits = model.apply({"params": params}, jnp.asarray(row[None, :-1]))
    want = float(cross_entropy(logits, jnp.asarray(row[None, 1:]))[0])
    got = float(progen_ref.row_loss(params, jnp.asarray(row), config.to_dict()))
    assert abs(got - want) < 1e-5


def test_bf16_compute_stays_inside_the_tolerances_and_a_fault_does_not():
    from benchmark.drivers import gen

    config, model, params = build(True, dtype="bfloat16")
    tokens = jax.random.randint(jax.random.PRNGKey(6), (64,), 1, 256)
    want = progen_ref.forward(params, tokens, config.to_dict())
    std = float(want.std())
    got = model.apply({"params": params}, tokens[None])[0]
    err = np.asarray(got - want)
    assert np.sqrt((err ** 2).mean()) / std < gen.RMS_TOLERANCE
    assert np.abs(err).max() / std < gen.MAX_TOLERANCE
    # a model that skips its token shift is a different model
    broken = ProGen(ProGenConfig(**{**TINY, "dtype": "bfloat16"},
                                 scan_layers=True, shift_tokens=False))
    bad = np.asarray(broken.apply({"params": params}, tokens[None])[0] - want)
    assert np.sqrt((bad ** 2).mean()) / std > 2 * gen.RMS_TOLERANCE
