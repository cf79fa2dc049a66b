"""The served tree: the engine holds, beside the tree as handed in, the
tree its programs take — every leaf they would convert to the compute type
at each use held converted, every other leaf the raw array itself. Same
bits as the raw tree through every program; norm scales and the SGU's
spatial matrices stay float32; a family or a precision with nothing to
convert gets its own leaves back; a reload builds the candidate's served
tree off the loop thread; int8 quantizes from the raw tree."""

import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from progen_tpu.config import ProGenConfig, load_toml_config
from progen_tpu.models import build_model
from progen_tpu.models.progen import ProGen
from progen_tpu.ops.quant import quantize_tree
from progen_tpu.sampling import feed_tokens
from progen_tpu.serving import Scheduler, ServeEngine
from progen_tpu.serving import engine as E
from progen_tpu.serving import served_tree
from progen_tpu.serving.metrics import HELP
from progen_tpu.serving.served_tree import cast_mask, promoted_mask

REPO = Path(__file__).resolve().parents[1]
SMALL_MOE = load_toml_config(str(REPO / "configs/model/latent-moe-small.toml"))

# the size ISSUE 29's scratch run used: float32 parameters computed in
# bfloat16, two gMLP layers among four
MIXED = dict(
    num_tokens=32, dim=64, seq_len=64, depth=4, window_size=8,
    global_mlp_depth=2, heads=2, dim_head=16, ff_mult=2, dtype="bfloat16",
)
SLOTS, LEN = 3, 48
GAUGES = ("raw_weight_bytes", "served_weight_bytes", "served_leaves_cast",
          "cache_write_leaves_kernel", "cache_write_leaves_select",
          "cache_write_leaves_loop")


def progen(**over):
    cfg = ProGenConfig(**{**MIXED, **over})
    model = ProGen(cfg)
    params = meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.seq_len), jnp.int32)
    ))["params"]

    # off the initial values: a norm scale of exactly 1 or a bias of
    # exactly 0 would hide a leaf held in the wrong type
    def nudge(path, a):
        k = jax.random.PRNGKey(sum(jax.tree_util.keystr(path).encode()))
        return a + 0.05 * jax.random.normal(k, a.shape, a.dtype)

    return model, jax.tree_util.tree_map_with_path(nudge, params)


def latent_moe(dtype):
    model = build_model({**SMALL_MOE, "dtype": dtype, "param_dtype": dtype})
    params = model.init(
        jax.random.PRNGKey(1), jnp.zeros((1, 8), jnp.int32)
    )["params"]
    return model, params


@pytest.fixture(scope="module")
def mixed():
    """An engine over float32 parameters computed in bfloat16, with its
    pool filled: three requests at different depths, a few steps in."""
    model, params = progen()
    eng = ServeEngine(model, params, max_slots=SLOTS, max_len=LEN)
    rng = np.random.default_rng(0)
    for slot, n in enumerate((5, 11, 20)):
        assert eng.acquire() == slot
        prime = rng.integers(1, 32, size=n).astype(np.int32)
        eng.prefill(slot, prime, LEN, key=jax.random.PRNGKey(slot), top_k=8)
    for _ in range(3):
        eng.decode_step()
    return eng, params


def copy(tree):
    # the decode step and the prefill donate the pool (and the prefill the
    # batch-1 cache it is fed, which is why each run takes a new one)
    return jax.tree.map(jnp.copy, tree)


def same_bits(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype and x.shape == y.shape
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def row_of(n, seed=3):
    row = np.zeros((LEN,), np.int32)
    row[:n] = np.random.default_rng(seed).integers(1, 32, size=n)
    return jnp.asarray(row)


# ----- (a) the same bits through every program ----------------------------


def run_decode(eng, tree):
    slots, outs = copy(eng.slots), []
    for _ in range(10):
        slots, sampled, live, finished = E._decode_step(eng.model, tree, slots)
        outs.append((sampled, live, finished))
    return slots, outs


def run_chunk(eng, tree):
    # two chunks, the second resuming mid-block
    cache = E._prefill_chunk(eng.model, tree, eng.new_cache(), row_of(30),
                             jnp.int32(0), jnp.int32(13))
    return E._prefill_chunk(eng.model, tree, cache, row_of(30),
                            jnp.int32(13), jnp.int32(29))


def run_prefill(eng, tree):
    # a whole prime as the chunk program's one chunk, then into the pool
    cache = E._prefill_chunk(eng.model, tree, eng.new_cache(), row_of(17),
                             jnp.int32(0), jnp.int32(16))
    return E._prefill_finish(
        copy(eng.slots), cache, jnp.int32(1),
        row_of(17), jnp.int32(17), jnp.int32(LEN), jax.random.PRNGKey(9),
        jnp.float32(1.0), jnp.float32(E._TOP_P_OFF), jnp.int32(8),
        jnp.asarray(True), jnp.zeros((LEN,), jnp.int32),
        jnp.zeros((LEN,), bool), new_cache=eng._build_cache,
    )


def run_logits(eng, tree):
    # ISSUE 29's scratch run: 20 positions fed in blocks, then 10 decode
    # steps through the cache, the logits of each kept
    row = row_of(31)
    cache = feed_tokens(eng.model, tree, eng.new_cache(), row[None], 0, 20)
    logits = []
    for i in range(20, 30):
        out, mut = eng.model.apply(
            {"params": tree, "cache": cache}, row[None, i:i + 1],
            mutable=["cache"],
        )
        cache = mut["cache"]
        logits.append(out)
    return logits, cache


@pytest.mark.parametrize(
    "program", [run_decode, run_chunk, run_prefill, run_logits],
    ids=["decode_step", "prefill_chunk", "prefill", "logits"],
)
def test_served_and_raw_tree_give_the_same_bits(mixed, program):
    eng, _ = mixed
    assert eng.state_bytes()["served_leaves_cast"] > 0
    same_bits(program(eng, eng.served_params), program(eng, eng.params))


def test_a_request_streams_the_same_tokens_as_sample_fast():
    """End to end through the scheduler-facing calls: the engine (served
    tree) against the standalone decoder (raw tree)."""
    from progen_tpu.sampling import sample_fast

    model, params = progen()
    eng = ServeEngine(model, params, max_slots=2, max_len=LEN)
    prime = np.asarray([3, 7, 2, 9, 4, 11], np.int32)
    slot = eng.acquire()
    pending = eng.begin_prefill(slot, prime, 40, key=jax.random.PRNGKey(5),
                                top_k=8)
    while not eng.advance_prefill(pending, 2):
        pass
    while eng.slots.live[slot]:
        eng.decode_step()
    want = sample_fast(jax.random.PRNGKey(5), model, params, prime, 40,
                       top_k=8)
    np.testing.assert_array_equal(eng.collect(slot), np.asarray(want))


# ----- (b) nothing left to convert in the programs -------------------------


def conversions_of_inputs(jaxpr, n_inputs):
    """Input numbers (< n_inputs) of ``jaxpr`` that some
    ``convert_element_type`` consumes, looking through calls and loops."""
    found = set()

    def walk(jp, var, i):
        for eqn, k in served_tree._uses(jp, index).get(var, ()):
            if eqn.primitive.name == "convert_element_type":
                found.add(i)
            for sub, v in served_tree._callees(eqn, k) or ():
                walk(sub, v, i)

    index = {}
    for i, var in enumerate(jaxpr.invars[:n_inputs]):
        walk(jaxpr, var, i)
    return found


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_the_lowered_programs_convert_no_parameter(mixed, program):
    eng, _ = mixed
    args = {
        "decode_step": (eng.slots,),
        "prefill_chunk": (eng.new_cache(), row_of(30), jnp.int32(0),
                          jnp.int32(13)),
    }[program]
    jitted = {"decode_step": E._decode_step,
              "prefill_chunk": E._prefill_chunk}[program]
    n = len(jax.tree.leaves(eng.params))

    served = jitted.trace(eng.model, eng.served_params, *args).jaxpr.jaxpr
    assert conversions_of_inputs(served, n) == set()
    # the same walk does find them with the raw tree: one per cast leaf
    raw = jitted.trace(eng.model, eng.params, *args).jaxpr.jaxpr
    assert len(conversions_of_inputs(raw, n)) == \
        eng.state_bytes()["served_leaves_cast"]

    # and no float32 parameter of the lowered program is larger than the
    # SGU's (seq_len, seq_len) matrix
    lowered = jitted.lower(eng.model, eng.served_params, *args)
    params_in = jax.tree.leaves(lowered.args_info)[:n]
    widest = max(a._aval.size for a in params_in
                 if a._aval.dtype == jnp.float32)
    assert widest == eng.model.config.seq_len ** 2


# ----- (c) what stays float32, and what engine.params is -------------------


def test_scales_and_spatial_matrices_stay_float32_and_params_is_the_raw_tree(
    mixed,
):
    eng, params = mixed
    raw = jax.tree_util.tree_flatten_with_path(params)[0]
    served = jax.tree.leaves(eng.served_params)
    held = jax.tree.leaves(eng.params)
    cast = 0
    for (path, leaf), s, h in zip(raw, served, held):
        name = jax.tree_util.keystr(path)
        assert h is leaf, name  # the handed-in tree, dtype for dtype
        if path[-1].key in ("scale", "spatial_weights", "spatial_biases"):
            assert s is leaf and s.dtype == jnp.float32, name
        else:
            assert path[-1].key in ("kernel", "bias", "embedding"), name
            assert s.dtype == jnp.bfloat16, name
            np.testing.assert_array_equal(
                np.asarray(s), np.asarray(leaf.astype(jnp.bfloat16))
            )
            cast += 1
    got = eng.state_bytes()
    assert got["served_leaves_cast"] == cast
    assert got["raw_weight_bytes"] == sum(x.nbytes for x in held)
    assert got["served_weight_bytes"] == sum(x.nbytes for x in served)
    assert got["served_weight_bytes"] < 0.6 * got["raw_weight_bytes"]


def test_a_scanned_checkpoint_is_unrolled_then_served():
    model, params = progen(scan_layers=True)
    eng = ServeEngine(model, params, max_slots=2, max_len=LEN)
    flat = jax.tree_util.tree_flatten_with_path(eng.served_params)[0]
    for path, leaf in flat:
        wide = path[-1].key in ("scale", "spatial_weights", "spatial_biases")
        assert leaf.dtype == (jnp.float32 if wide else jnp.bfloat16)
    same_bits(run_chunk(eng, eng.served_params), run_chunk(eng, eng.params))


# ----- the rule the engine uses, held to a trace of its programs -----------


@pytest.mark.parametrize("layout", ["unrolled", "scanned", "no_gmlp"])
def test_the_rule_casts_what_a_trace_of_the_programs_casts(layout):
    """``promoted_mask`` (Flax's rule, free) against ``cast_mask`` (what
    the decode step and the block feed do with each leaf, traced)."""
    model, params = progen(**{
        "unrolled": {}, "scanned": {"scan_layers": True},
        "no_gmlp": {"global_mlp_depth": 0},
    }[layout])
    eng = ServeEngine(model, params, max_slots=2, max_len=LEN)

    def programs(params, slots, cache, row, lo, hi):
        return (
            E._decode_step_impl(eng.model, params, slots),
            feed_tokens(eng.model, params, cache, row[None], lo, hi),
        )

    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    traced = cast_mask(
        programs, eng.params, jnp.bfloat16, eng.slots, eng.new_cache(),
        jax.ShapeDtypeStruct((LEN,), jnp.int32), scalar, scalar,
    )
    assert eng._cast == promoted_mask(eng.params, jnp.bfloat16) == traced
    assert 0 < sum(traced) < len(traced)


def test_the_rule_narrows_only_and_knows_only_flaxs_own_names():
    f32, bf16 = jnp.float32, jnp.bfloat16
    tree = {
        "dense": {"kernel": jnp.ones((2, 2), f32), "bias": jnp.ones((2,), f32)},
        "embed": {"embedding": jnp.ones((4, 2), f32)},
        "norm": {"scale": jnp.ones((2,), f32)},
        "own": {"w_q": jnp.ones((2, 2), f32), "steps": jnp.ones((), jnp.int32)},
        "half": {"kernel": jnp.ones((2, 2), bf16)},
    }
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    got = dict(zip((jax.tree_util.keystr(p) for p, _ in flat),
                   promoted_mask(tree, bf16)))
    assert got == {
        "['dense']['bias']": True, "['dense']['kernel']": True,
        "['embed']['embedding']": True, "['half']['kernel']": False,
        "['norm']['scale']": False, "['own']['steps']": False,
        "['own']['w_q']": False,
    }
    assert not any(promoted_mask(tree, f32))  # nothing is ever widened


# ----- (d) nothing to convert: the raw leaves themselves -------------------


@pytest.mark.parametrize("family", [
    "progen_float32", "latent_moe_float32", "latent_moe_bfloat16",
])
def test_a_tree_already_in_the_compute_type_is_served_as_it_is(family):
    if family == "progen_float32":
        model, params = progen(dtype="float32")
    else:
        model, params = latent_moe(family.rsplit("_", 1)[1])
    eng = ServeEngine(model, params, max_slots=2, max_len=32)
    for s, r in zip(jax.tree.leaves(eng.served_params),
                    jax.tree.leaves(eng.params)):
        assert s is r
    got = eng.state_bytes()
    assert got["served_leaves_cast"] == 0
    assert got["served_weight_bytes"] == got["raw_weight_bytes"] > 0
    assert ("latent_cache_bytes" in got) == family.startswith("latent_moe")


def stream(eng, seed):
    """One request through chunked admission to its end; its tokens."""
    slot = eng.acquire()
    pending = eng.begin_prefill(
        slot, np.asarray([3, 7, 2, 9, 4, 11], np.int32), 30,
        key=jax.random.PRNGKey(seed), top_k=8,
    )
    while not eng.advance_prefill(pending, 4):
        pass
    while eng.slots.live[slot]:
        eng.decode_step()
    out = eng.collect(slot)
    eng.release(slot)
    return out


def test_an_idle_engine_drops_the_served_tree_and_the_next_request_rebuilds_it():
    model, params = progen()
    eng = ServeEngine(model, params, max_slots=2, max_len=LEN)
    assert eng._served is None  # nothing held yet: nothing built
    first = stream(eng, seed=4)
    assert eng._served is None  # the last slot went back
    counts = (eng.decode_compile_count(), eng.prefill_compile_count())

    held = eng.acquire()  # a second request keeps the engine busy
    tree = eng.served_params
    again = stream(eng, seed=4)
    assert eng.served_params is tree  # not dropped while a slot is held
    eng.release(held)
    assert eng._served is None

    np.testing.assert_array_equal(first, again)
    assert (eng.decode_compile_count(),
            eng.prefill_compile_count()) == counts
    # and what it gives back is the raw tree's leaves, never a copy of it
    for s, r in zip(jax.tree.leaves(eng.served_params),
                    jax.tree.leaves(params)):
        assert (s is r) == (s.dtype == r.dtype)


# ----- (e) hot reload ------------------------------------------------------


def test_a_reload_checks_the_raw_tree_builds_off_thread_and_recompiles_nothing():
    model, params = progen()
    eng = ServeEngine(model, params, max_slots=2, max_len=LEN)
    slot = eng.acquire()
    pending = eng.begin_prefill(slot, np.asarray([3, 7, 2, 9], np.int32),
                                LEN, key=jax.random.PRNGKey(0))
    eng.advance_prefill(pending, 2)
    eng.advance_prefill(pending)
    eng.decode_step()
    before = (eng.decode_compile_count(), eng.prefill_compile_count())

    # a candidate in the SERVED tree's types is not the raw tree's: refused
    with pytest.raises(ValueError, match="hot reload needs a restart"):
        eng.prepare_params(eng.served_params)

    candidate = jax.tree.map(lambda x: x * 1.5, params)
    box = {}
    worker = threading.Thread(
        target=lambda: box.update(p=eng.prepare_params(candidate))
    )
    worker.start()
    worker.join(120)
    prepared = box["p"]
    live_raw, live_served = eng.params, eng.served_params  # untouched so far
    assert live_raw is not prepared.params
    for s, r, old in zip(jax.tree.leaves(prepared.served),
                         jax.tree.leaves(prepared.params),
                         jax.tree.leaves(live_served)):
        assert s.dtype == old.dtype and s.shape == old.shape
        if s.dtype == r.dtype:
            assert s is r
        else:
            np.testing.assert_array_equal(
                np.asarray(s), np.asarray(r.astype(s.dtype))
            )

    eng.commit_params(prepared)
    assert eng.params is prepared.params
    assert eng.served_params is prepared.served
    slot = eng.acquire()
    pending = eng.begin_prefill(slot, np.asarray([5, 1, 8, 2, 6], np.int32),
                                LEN, key=jax.random.PRNGKey(1))
    eng.advance_prefill(pending, 2)
    eng.advance_prefill(pending)
    eng.decode_step()
    assert (eng.decode_compile_count(),
            eng.prefill_compile_count()) == before
    assert eng.state_bytes()["served_leaves_cast"] > 0


# ----- (f) int8 quantizes from the raw tree --------------------------------


def test_an_int8_engine_quantizes_the_raw_tree_and_builds_no_copy():
    model, params = progen()
    eng = ServeEngine(model, params, max_slots=2, max_len=LEN,
                      quantize_int8=True)
    q, scales, leaves = quantize_tree(params)
    assert eng.quant_report["leaves"] == leaves
    same_bits(eng.served_params.q, q)
    same_bits(eng.served_params.scales, scales)
    got = eng.state_bytes()
    assert got["served_leaves_cast"] == 0
    assert got["served_weight_bytes"] == got["raw_weight_bytes"]
    # the served tree is the quantized pair: a leaf is the raw float32
    # array itself or its int8 kernel, never a cast copy
    for s, r, p in zip(jax.tree.leaves(eng.served_params.q),
                       jax.tree.leaves(eng.params), jax.tree.leaves(params)):
        assert r is p and p.dtype == jnp.float32
        assert s is r or s.dtype == jnp.int8
    prepared = eng.prepare_params(jax.tree.map(lambda x: x * 1.5, params))
    for s, r in zip(jax.tree.leaves(prepared.served.q),
                    jax.tree.leaves(prepared.params)):
        assert s is r or s.dtype == jnp.int8


# ----- the gauges -----------------------------------------------------------


@pytest.mark.parametrize("name", GAUGES)
def test_the_scheduler_publishes_the_gauge_with_a_help_line(mixed, name):
    eng, _ = mixed
    sched = Scheduler(eng, max_queue=4)
    assert sched.metrics.gauges[name] == eng.state_bytes()[name]
    assert name in HELP and name in sched.metrics.structured()["help"]


# ----- the walker, on programs small enough to read -------------------------

W = jnp.ones((4, 4), jnp.float32)
X = jnp.ones((4,), jnp.bfloat16)


def cast(w):
    return w.astype(jnp.bfloat16)


def loop_reads(w, x):
    return jax.lax.fori_loop(0, 3, lambda i, c: cast(w) @ c, x)


def loop_carries(w, x):
    return jax.lax.fori_loop(0, 3, lambda i, c: c * 2, w)


def scan_reads(w, x):
    return jax.lax.scan(lambda c, _: (cast(w) @ c, ()), x, None, length=3)[0]


def both_branches(w, x):
    return jax.lax.cond(x[0] > 0, lambda: cast(w) @ x, lambda: cast(w).T @ x)


def one_branch_raw(w, x):
    return jax.lax.cond(x[0] > 0, lambda: cast(w) @ x,
                        lambda: (w @ x.astype(w.dtype)).astype(x.dtype))


WALKER = {
    "cast_then_used": (lambda w, x: cast(w) @ x, True),
    "cast_twice": (lambda w, x: cast(w) @ x + cast(w).T @ x, True),
    "inside_a_jitted_call": (lambda w, x: jax.jit(cast)(w) @ x, True),
    "under_vmap": (
        lambda w, x: jax.vmap(lambda r: cast(w) @ r)(jnp.stack([x, x])), True,
    ),
    "read_by_a_loop_body": (loop_reads, True),
    "read_by_a_scan_body": (scan_reads, True),
    "cast_in_both_branches": (both_branches, True),
    "used_raw_as_well": (lambda w, x: cast(w) @ x + w[0].astype(x.dtype), False),
    "used_raw_only": (lambda w, x: w @ x.astype(w.dtype), False),
    "raw_in_one_branch": (one_branch_raw, False),
    "carried_by_a_loop": (loop_carries, False),
    "returned": (lambda w, x: (cast(w) @ x, w), False),
    "cast_to_another_type": (lambda w, x: w.astype(jnp.float16)[0], False),
    "never_used": (lambda w, x: x * 2, False),
}


@pytest.mark.parametrize("case", list(WALKER))
def test_the_walker_casts_only_what_every_use_converts(case):
    fn, want = WALKER[case]
    assert cast_mask(fn, W, jnp.bfloat16, X) == [want]


def test_the_walker_narrows_only_and_skips_the_trace_when_nothing_is_wider():
    def boom(w, x):
        raise AssertionError("traced")

    assert cast_mask(boom, {"w": W.astype(jnp.bfloat16)}, jnp.bfloat16,
                     X) == [False]
    # bfloat16 parameters of a float32 computation stay as they are
    assert cast_mask(boom, {"w": W.astype(jnp.bfloat16),
                            "n": jnp.ones((3,), jnp.int32)},
                     jnp.float32, X) == [False, False]
