"""The per-slot cache write of the serving pool: vmapped over slots that
each write at a start of their own, ``layers._write_rows`` traces one
``dynamic_update_slice`` per slot (``layers._update_at``) where the plain
update's batching rule would trace a scatter, which the TPU compiler runs
as a serial loop over the slots. Same values either way."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from progen_tpu.config import ProGenConfig
from progen_tpu.models.layers import _update_at
from progen_tpu.models.progen import ProGen
from progen_tpu.serving import ServeEngine
from progen_tpu.serving import engine as E

BUF = jnp.arange(3 * 2 * 8 * 4, dtype=jnp.float32).reshape(3, 2, 8, 4)
NEW = -1.0 - jnp.arange(3 * 2 * 2 * 4, dtype=jnp.float32).reshape(3, 2, 2, 4)
START = jnp.asarray([1, 5, 7])  # the last one is clamped to 6, as lax does


def plain(buf, new, start):
    return jax.lax.dynamic_update_slice_in_dim(buf, new, start, axis=1)


CASES = {
    # name: (in_axes over (buf, new, start), operands)
    "a_start_per_slot": ((0, 0, 0), (BUF, NEW, START)),
    "a_negative_start_wraps": ((0, 0, 0), (BUF, NEW, jnp.asarray([-8, -3, -1]))),
    "one_start_for_all": ((0, 0, None), (BUF, NEW, jnp.int32(3))),
    "one_row_for_all": ((0, None, 0), (BUF, NEW[0], START)),
    "one_buffer_for_all": ((None, 0, 0), (BUF[0], NEW, START)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_unrolled_write_equals_the_vmapped_update(case):
    in_axes, operands = CASES[case]
    want = jax.vmap(plain, in_axes=in_axes)(*operands)
    got = jax.jit(jax.vmap(_update_at(1), in_axes=in_axes))(*operands)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_without_vmap_it_is_the_plain_update():
    got = _update_at(1)(BUF[0], NEW[0], jnp.int32(2))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(plain(BUF[0], NEW[0], 2))
    )
    jaxpr = jax.make_jaxpr(_update_at(1))(BUF[0], NEW[0], jnp.int32(2))
    assert "scatter" not in str(jaxpr)


@pytest.mark.parametrize("slots", [1, 3])
def test_the_decode_step_writes_its_caches_without_a_scatter(slots):
    cfg = ProGenConfig(
        num_tokens=32, dim=32, seq_len=32, depth=3, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2,
    )
    model = ProGen(cfg)
    params = meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.seq_len), jnp.int32)
    ))["params"]
    eng = ServeEngine(model, params, max_slots=slots, max_len=24)
    text = E._decode_step.lower(
        eng.model, eng.served_params, eng.slots
    ).as_text()
    # the one scatter left writes the sampled tokens (``_write_sampled``)
    assert text.count('"stablehlo.scatter"') == 1
    # K ring, V ring and position ring of each layer, one SGU history
    written = 3 * cfg.depth + cfg.global_mlp_depth
    assert text.count("stablehlo.dynamic_update_slice") == slots * written
