"""A decode step's cache writes: each leaf's new rows for all slots at once.

``layers._update_at``'s batching rule writes one row per slot of a pooled
leaf through the row-write kernel (``ops/pallas_row_write.py``) where the
backend is a TPU and the kernel fits the leaf, through one select over a
small leaf it does not fit, and as one update a slot anywhere else. Here
the rule's backend test is turned (``on_tpu`` — the kernel then runs in
interpret mode) and every form is held, bit for bit, to the written-out
one: at each leaf shape the benchmark's cells write (their widths, fewer
rows), with starts that wrap, operands left unbatched, blocks of several
rows and rows that are not live; through ``latent_moe``'s decode call and
through a ProGen engine; and ``latent_moe``'s decode holds no scatter or
gather under ``cache_write`` whichever form runs.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.models.layers import _update_at, row_write_paths
from progen_tpu.ops import pallas_decode_attention as D
from progen_tpu.ops import pallas_row_write as K

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture
def on_tpu(monkeypatch):
    """The rule's backend test answers yes; programs traced under the
    other answer are dropped before and after."""
    jax.clear_caches()
    monkeypatch.setattr(D, "on_tpu", lambda: True)
    yield
    jax.clear_caches()


def _draw(shape, dtype, seed):
    rng = np.random.default_rng(seed)
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.asarray(rng.integers(-1, 1 << 20, size=shape), dtype)
    return jnp.asarray(rng.normal(size=shape), dtype)


def _written_out(buf, new, start, axis, live=None):
    """The reference: one ``dynamic_update_slice`` a slot, a negative
    start wrapped once, a row that is not live keeping the buffer's."""
    out = []
    for s in range(buf.shape[0]):
        b, n, i = buf[s], new[s], int(start[s])
        if i < 0:
            i += b.shape[axis]
        if live is not None:
            old = jax.lax.dynamic_slice_in_dim(b, i, n.shape[axis], axis)
            shape = [1] * n.ndim
            shape[axis] = n.shape[axis]
            n = jnp.where(live[s].reshape(shape), n, old)
        out.append(jax.lax.dynamic_update_slice_in_dim(b, n, i, axis))
    return jnp.stack(out)


def _rule(buf, new, start, axis, live=None):
    """The batched rule, jitted, with the paths its trace took."""
    if live is None:
        fn = jax.jit(jax.vmap(_update_at(axis)))
        args = (buf, new, start)
    else:
        fn = jax.jit(jax.vmap(_update_at(axis, masked=True)))
        args = (buf, new, start, live)
    with row_write_paths() as paths:
        out = fn(*args)
    return out, dict(paths)


def _same(a, b):
    return np.array_equal(np.asarray(a).view(np.uint8),
                          np.asarray(b).view(np.uint8))


# (per-slot leaf shape, its type, the written axis, the form the rule takes
# on a TPU): each leaf the cells write, at its widths with fewer rows
LEAVES = {
    "progen_kv_ring": ((1, 14, 64, 128), BF16, 2, "kernel"),
    "progen_slot_pos": ((64,), I32, 0, "select"),
    "progen_gate_history": ((1, 32, 256), F32, 1, "kernel"),
    "latent_rows": ((48, 512), BF16, 0, "kernel"),
    "latent_rope_key": ((48, 64), BF16, 0, "select"),
    "sparse_grouped_rows": ((2 * 32, 128), BF16, 0, "kernel"),
}


@pytest.mark.parametrize("leaf", list(LEAVES))
@pytest.mark.parametrize("masked", [False, True], ids=["all", "live"])
def test_the_rule_writes_each_leaf_as_the_written_out_updates(
        on_tpu, leaf, masked):
    shape, dtype, axis, form = LEAVES[leaf]
    slots = 5
    size = shape[axis]
    buf = _draw((slots,) + shape, dtype, 0)
    new_shape = list(shape)
    new_shape[axis] = 1
    new = _draw((slots,) + tuple(new_shape), dtype, 1)
    # first and last row, a wrap from below, one past the end (clamped)
    start = jnp.asarray([0, size - 1, -1, -size, size + 3], I32)
    live = jnp.asarray([[True], [False], [True], [False], [True]])
    got, paths = _rule(buf, new, start, axis, live if masked else None)
    want = _written_out(buf, new, start, axis, live if masked else None)
    assert paths == {form: 1}
    assert _same(got, want)


@pytest.mark.parametrize("masked", [False, True], ids=["all", "live"])
def test_off_the_tpu_the_rule_is_the_loop_and_writes_the_same(masked):
    shape, dtype, axis, _ = LEAVES["progen_kv_ring"]
    buf = _draw((4,) + shape, dtype, 2)
    new = _draw((4, 1, 14, 1, 128), dtype, 3)
    start = jnp.asarray([5, -3, 63, 0], I32)
    live = jnp.asarray([[False], [True], [True], [False]])
    got, paths = _rule(buf, new, start, axis, live if masked else None)
    assert paths == {"loop": 1}
    assert _same(got, _written_out(buf, new, start, axis,
                                   live if masked else None))


def test_unbatched_operands_are_broadcast_over_the_slots(on_tpu):
    """A leaf shared by every slot, or one start for all, is written as
    if each slot held its own copy."""
    buf = _draw((48, 512), BF16, 4)
    new = _draw((3, 1, 512), BF16, 5)
    start = jnp.asarray([7, 40, -2], I32)
    with row_write_paths() as paths:
        got = jax.vmap(_update_at(0), in_axes=(None, 0, 0))(buf, new, start)
        one = jax.vmap(_update_at(0), in_axes=(0, 0, None))(
            jnp.broadcast_to(buf, (3, 48, 512)), new, jnp.int32(17))
    assert dict(paths) == {"kernel": 2}
    assert _same(got, _written_out(
        jnp.broadcast_to(buf, (3, 48, 512)), new, start, 0))
    assert _same(one, _written_out(
        jnp.broadcast_to(buf, (3, 48, 512)), new, jnp.full((3,), 17), 0))


@pytest.mark.parametrize("masked", [False, True], ids=["all", "live"])
def test_a_block_of_several_rows_keeps_the_loop(on_tpu, masked):
    """T > 1 rows a slot (a prefill block under a batch) is not the
    kernel's: the written-out updates, dead rows kept."""
    buf = _draw((3, 48, 512), BF16, 6)
    new = _draw((3, 4, 512), BF16, 7)
    start = jnp.asarray([0, 44, -8], I32)
    live = jnp.asarray([[True, True, False, False], [False, True, True, True],
                        [True, False, False, False]])
    got, paths = _rule(buf, new, start, 0, live if masked else None)
    assert paths == {"loop": 1}
    assert _same(got, _written_out(buf, new, start, 0,
                                   live if masked else None))


def test_one_slot_keeps_its_single_update(on_tpu):
    """A batch of one (a slot-batched family's prefill at B = 1) keeps the
    one ``dynamic_update_slice``: the kernel is for many slots."""
    buf = _draw((1, 48, 512), BF16, 8)
    new = _draw((1, 1, 512), BF16, 9)
    start = jnp.asarray([9], I32)
    got, paths = _rule(buf, new, start, 0)
    assert paths == {"loop": 1}
    assert _same(got, _written_out(buf, new, start, 0))


@pytest.mark.parametrize(
    "shape,dtype,fits",
    [
        ((32, 1, 14, 1024, 128), BF16, True),  # ProGen-large's K/V rings
        ((32, 1, 1024, 3584), F32, True),  # its gate histories
        ((32, 1536, 512), BF16, True),  # kanana2-30b-a3b's latent rows
        ((32, 1536, 64), BF16, False),  # its rope keys: half the lanes
        ((16, 65536, 128), BF16, True),  # minicpm-sala's grouped rows
        ((32, 1, 14, 1000, 128), BF16, False),  # rows not whole tiles
        ((32, 1, 80, 1024, 128), F32, False),  # over the VMEM budget
        ((32, 1024), I32, False),  # written along its lanes
    ],
)
def test_the_kernel_fits_the_leaves_it_can_copy_whole_tiles_of(
        shape, dtype, fits):
    assert K.fits(shape, dtype, len(shape) - 2) == fits


# ----- through the models ----------------------------------------------------


def _latent_model(**over):
    from progen_tpu.config import load_toml_config
    from progen_tpu.models import build_model
    from pathlib import Path

    cfg = load_toml_config(str(Path(__file__).resolve().parents[1]
                               / "configs" / "model" / "latent-moe-small.toml"))
    return build_model({**cfg, **over})


def _latent_decode_call(model):
    """(decode model, params, pool cache, toks, pos, live) for a pool of
    four slots at different positions, one of them dead."""
    from flax.core import meta

    from progen_tpu.models import decode_model

    params = meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    dec = decode_model(model, 256)
    cache1 = dec.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 1), jnp.int32))["cache"]
    rng = np.random.default_rng(10)
    cache = jax.tree.map(
        lambda c: jnp.asarray(rng.normal(size=(4,) + c.shape), c.dtype)
        if jnp.issubdtype(c.dtype, jnp.floating)
        else jnp.broadcast_to(c[None], (4,) + c.shape), cache1)
    toks = jnp.asarray([3, 7, 11, 13], jnp.int32)
    pos = jnp.asarray([0, 31, 200, 255], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    return dec, params, cache, toks, pos, live


def test_latent_moes_decode_writes_the_same_through_the_kernel(monkeypatch):
    """The family's decode call with a latent width the kernel takes: the
    same logits and cache, bit for bit, whichever form writes; the dead
    slot's rows are kept."""
    model = _latent_model(kv_lora_rank=128, dtype="bfloat16",
                          param_dtype="bfloat16")
    dec, params, cache, toks, pos, live = _latent_decode_call(model)
    decode = jax.jit(dec.decode_slots)  # traced anew after each clear
    outs = {}
    for tpu in (False, True):
        jax.clear_caches()
        monkeypatch.setattr(D, "on_tpu", lambda tpu=tpu: tpu)
        with row_write_paths() as paths:
            outs[tpu] = decode(params, cache, toks, pos, live)
        outs[tpu] += (dict(paths),)
    jax.clear_caches()
    layers = model.config.num_hidden_layers
    assert outs[False][3] == {"loop": 2 * layers}
    assert outs[True][3] == {"kernel": layers, "select": layers}
    for a, b in zip(jax.tree.leaves(outs[False][:3]),
                    jax.tree.leaves(outs[True][:3])):
        assert _same(a, b)
    new_cache = outs[True][1]
    for name in ("c", "k_rope"):
        got = new_cache["attn0"][name][2]
        assert _same(got, cache["attn0"][name][2])  # the dead slot's
        assert not _same(new_cache["attn0"][name][0],
                         cache["attn0"][name][0])


def _under_cache_write(jaxpr, inside=False) -> list:
    """The primitives of the equations under a ``cache_write`` scope, into
    the jaxprs they call (whose stacks start afresh)."""
    found = []
    for eqn in jaxpr.eqns:
        here = inside or "cache_write" in str(eqn.source_info.name_stack)
        if here:
            found.append(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _under_cache_write(sub, here)
    return found


@pytest.mark.parametrize("tpu", [False, True], ids=["loop", "kernel"])
def test_latent_moes_decode_holds_no_scatter_under_cache_write(
        monkeypatch, tpu):
    """The vmapped slice-and-update that wrote the latent rows became a
    gather and a scatter; the rule's forms are slices and updates, or the
    kernel and a select."""
    model = _latent_model(kv_lora_rank=128)
    dec, params, cache, toks, pos, live = _latent_decode_call(model)
    jax.clear_caches()
    monkeypatch.setattr(D, "on_tpu", lambda: tpu)
    closed = jax.make_jaxpr(dec.decode_slots)(params, cache, toks, pos, live)
    jax.clear_caches()
    prims = _under_cache_write(closed.jaxpr)
    assert not [p for p in prims if "scatter" in p or "gather" in p]
    assert ("pallas_call" in prims) == tpu
    assert ("dynamic_update_slice" in prims) != tpu


def test_a_progen_engine_decodes_the_same_through_the_kernel(monkeypatch):
    """A ProGen pool whose K/V rings and gate history the kernel takes
    (heads of 128, a window of 128, bf16): the same tokens as the loop,
    step for step, and the engine's gauges say which form wrote."""
    from flax.core import meta

    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen
    from progen_tpu.serving import ServeEngine

    cfg = ProGenConfig(num_tokens=32, dim=128, seq_len=256, depth=2,
                       window_size=128, global_mlp_depth=1, heads=1,
                       dim_head=128, ff_mult=2, dtype="bfloat16")
    model = ProGen(cfg)
    params = meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.seq_len), jnp.int32)
    ))["params"]
    # the attention's own kernel stays out: it rounds otherwise than its
    # plain form, and the sampled tokens would follow that
    monkeypatch.setattr(D, "kernel_block", lambda *a: None)
    runs = {}
    for tpu in (False, True):
        jax.clear_caches()
        monkeypatch.setattr(D, "on_tpu", lambda tpu=tpu: tpu)
        eng = ServeEngine(model, params, max_slots=3, max_len=160)
        for slot, (prime, length) in enumerate(((5, 40), (120, 150),
                                                (9, 20))):
            eng.prefill(slot, np.arange(1, prime + 1) % 31 + 1, length,
                        top_k=8, seed=slot)
        steps = [eng.decode_step()[0] for _ in range(30)]
        runs[tpu] = (np.stack(steps), eng.state_bytes())
    jax.clear_caches()
    np.testing.assert_array_equal(runs[False][0], runs[True][0])
    loop, kernel = runs[False][1], runs[True][1]
    assert loop["cache_write_leaves_kernel"] == 0
    assert loop["cache_write_leaves_loop"] > 0
    assert kernel["cache_write_leaves_loop"] == 0
    # per attention layer the K and V rings, per gMLP layer the history;
    # each layer's slot_pos is a lane-wise leaf: the select
    assert kernel["cache_write_leaves_kernel"] == 2 * cfg.depth + 1
    assert kernel["cache_write_leaves_select"] == cfg.depth
