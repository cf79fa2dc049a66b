"""The latent_moe family (latent attention, routed + shared experts) at a
small size on the CPU: against its plain reference, through the block
prefill and the slot pool, through the scheduler and through cli.serve;
the pool-wide sampler against the per-slot one; what the family refuses;
and ProGen's served programs, which must lower as they did before."""

import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import latent_moe_ref
from progen_tpu.config import load_toml_config
from progen_tpu.models import build_model, decode_model
from progen_tpu.models.latent_moe import FEED_ROWS, LatentMoEConfig
from progen_tpu.sampling import (
    _TOP_K_PARTIAL,
    _TOP_P_OFF,
    gumbel_step_dynamic,
    gumbel_step_slots,
)
from progen_tpu.serving import Request, Scheduler, ServeEngine

REPO = Path(__file__).resolve().parents[1]
SMALL = load_toml_config(str(REPO / "configs/model/latent-moe-small.toml"))


def build(dtype="float32", seed=1):
    model = build_model({**SMALL, "dtype": dtype, "param_dtype": dtype})
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    # move every leaf off its initial value: norms, and the router's bias
    # (zero as published, so that it would decide nothing)
    def nudge(path, a):
        k = jax.random.PRNGKey(sum(jax.tree_util.keystr(path).encode()))
        return (a + 0.05 * jax.random.normal(k, a.shape)).astype(a.dtype)

    return model, jax.tree_util.tree_map_with_path(nudge, params)


def ids(n, seed):
    return np.random.default_rng(seed).integers(1, 512, size=n).astype(np.int32)


# ----- the full-sequence forward against the reference -------------------


@pytest.mark.parametrize("n,seed", [(40, 0), (200, 1)])
def test_float32_forward_routes_and_computes_as_the_reference(n, seed):
    model, params = build()
    tokens = jnp.asarray(ids(n, seed))
    got, aux = model.apply({"params": params}, tokens[None],
                           mutable=["intermediates"])
    want, routing = latent_moe_ref.forward(
        params, tokens, model.config.to_dict(), return_routing=True
    )
    chosen = [aux["intermediates"][f"ffn{i}"]["experts"][0] for i in (1, 2)]
    for mine, theirs in zip(chosen, routing["experts"]):  # identical sets, every token
        assert (np.sort(np.asarray(mine), -1) == np.sort(np.asarray(theirs), -1)).all()
    assert float(jnp.abs(got[0] - want).max()) < 1e-4
    assert float(jnp.abs(want).max()) > 1.0  # the comparison is not of zeros


def test_the_router_computes_in_float32_whatever_the_model_computes_in():
    """The published router is float32. A router rounded to bfloat16
    chooses so nearly the same that no comparison of outputs tells it
    apart (chip reading, PR 28: slack 0.0024 against the sound system's
    0.0034-0.0074), so its arithmetic is held here, by its jaxpr."""
    from progen_tpu.models.latent_moe import route

    c = LatentMoEConfig.from_dict(
        {**SMALL, "dtype": "bfloat16", "param_dtype": "bfloat16"})
    jaxpr = jax.make_jaxpr(lambda u, w, b: route(u, w, b, c))(
        jnp.zeros((5, c.hidden_size), jnp.bfloat16),
        jnp.zeros((c.hidden_size, c.n_routed_experts), jnp.bfloat16),
        jnp.zeros((c.n_routed_experts,), jnp.float32),
    )
    seen = set()
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "convert_element_type":
            continue
        seen.add(eqn.primitive.name)
        for v in eqn.outvars:
            if jnp.issubdtype(v.aval.dtype, jnp.floating):
                assert v.aval.dtype == jnp.float32, eqn
        if eqn.primitive.name == "dot_general":
            assert eqn.params["precision"] is not None  # HIGHEST, not a bf16 pass
    assert {"dot_general", "logistic", "top_k"} <= seen


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_bfloat16_forward_stays_near_the_float32_reference(seed):
    """bfloat16 weights and products against float32 arithmetic on the
    SAME (bfloat16) weights: what is left is the rounding of activations,
    2^-9 relative at each product, 3 layers deep — and a choice of expert
    that flips where two scores tie to within it. Root-mean-square error
    over the logits' deviation, read here over these seeds: the system
    0.9-3.8%; the reference with every matrix rounded to an 8-bit float
    (5 exponent bits, 2 of mantissa: the nearest precision below) 21-31%
    against its float32 self. The limit stands between. The LARGEST error
    is not judged at this size: with 8 experts and 2 chosen, one flipped
    choice moves a token by more than the logits' deviation (read: 5-112%
    for the system, 190-230% for 8 bits), and decisions are not handed
    over here as the chip's check hands them
    (benchmark/drivers/gen_latent_moe.py)."""
    model, params = build("bfloat16", seed=seed)
    tokens, cfg = jnp.asarray(ids(96, seed)), model.config.to_dict()
    want = np.asarray(latent_moe_ref.forward(params, tokens, cfg), np.float64)

    def rms(got):
        err = np.asarray(got, np.float64) - want
        return np.sqrt((err ** 2).mean()) / want.std()

    assert rms(model.apply({"params": params}, tokens[None])[0]) < 0.07
    assert rms(latent_moe_ref.forward(
        params, tokens, cfg, weight_bits=(5, 2))) > 0.07


# ----- block prefill and decode through the slot pool --------------------


def prefill_then_decode(engine, row, n_prompt, chunk):
    """Logits at positions n_prompt.. of ``row`` (BOS first) through the
    engine's chunked admission and the POOL's cache; also the slot's cache
    as the prefill left it."""
    slot = engine.acquire()
    pending = engine.begin_prefill(slot, row[1:n_prompt + 1], len(row) + 1,
                                   add_bos=True, top_k=None)
    while not engine.advance_prefill(pending, chunk):
        pass
    left = jax.tree.map(lambda c: np.asarray(c[slot]), engine.slots.cache)
    cache, live = engine.slots.cache, jnp.arange(engine.max_slots) == slot
    got = []
    for i in range(n_prompt, len(row)):
        logits, cache, _ = engine.model.decode_slots(
            engine.params, cache, jnp.full((engine.max_slots,), row[i]),
            jnp.full((engine.max_slots,), i), live,
        )
        got.append(np.asarray(logits[slot]))
    engine.release(slot)
    return np.stack(got), left


def test_block_prefill_under_three_splits_then_decode_matches_the_reference():
    model, params = build()
    engine = ServeEngine(model, params, max_slots=3, max_len=320)
    row = np.concatenate([[0], ids(299, 5)]).astype(np.int32)
    n_prompt = 270  # two whole blocks and a part of the third
    want = np.asarray(latent_moe_ref.forward(
        params, jnp.asarray(row), model.config.to_dict()
    ))[n_prompt:]
    caches = []
    for chunk in (None, FEED_ROWS, 37):
        got, left = prefill_then_decode(engine, row, n_prompt, chunk)
        assert np.abs(got - want).max() < 1e-4
        left.pop("moe_feed")  # counts passes, which the split changes
        caches.append(left)
    for other in caches[1:]:  # bit-equal however the prompt was split
        for a, b in zip(jax.tree.leaves(caches[0]), jax.tree.leaves(other)):
            assert (a == b).all()


def test_absorbed_attention_equals_the_expanded_form():
    """The decode mode never expands keys and values; fed a whole row
    through the cache (blocks, then single positions) it must give the
    full-sequence forward's logits."""
    model, params = build()
    dec = decode_model(model, 160)
    row = jnp.asarray(ids(150, 6))
    want = model.apply({"params": params}, row[None])[0]
    cache = dec.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["cache"]
    cache = dec.feed_tokens(params, cache, row[None], 0, 140)
    got = []
    for i in range(140, 150):
        (logits, _), mut = dec.apply(
            {"params": params, "cache": cache}, row[None, i:i + 1],
            jnp.full((1, 1), i), None, mutable=["cache"],
        )
        cache = mut["cache"]
        got.append(logits[0, 0])
    assert float(jnp.abs(jnp.stack(got) - want[140:]).max()) < 1e-4


# ----- through the scheduler ---------------------------------------------


def serve(model, params, requests, **sched):
    engine = ServeEngine(model, params, max_slots=3, max_len=200)
    scheduler = Scheduler(engine, **sched)
    for req in requests:
        assert scheduler.submit(req) == (True, None)
    _, completions = scheduler.run_to_completion(max_steps=2000)
    return {c.request_id: c.tokens for c in completions}, scheduler


def request(i, n_prompt=130, length=160, **kw):
    return Request(id=f"r{i}", prime=ids(n_prompt, 10 + i), length=length,
                   add_bos=True, seed=i, **kw)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_request_in_company_is_bit_identical_to_the_same_request_alone(dtype):
    model, params = build(dtype)
    alone, _ = serve(model, params, [request(0)], prefill_chunk=FEED_ROWS)
    company = [request(0), request(1, 140, 170, temperature=0.8, top_p=0.9),
               request(2, 135, 150, top_k=200), request(3, 131, 165)]
    together, sched = serve(model, params, company, prefill_chunk=FEED_ROWS)
    assert (alone["r0"] == together["r0"]).all()
    assert len(together) == 4 and (together["r0"][131:] != 0).any()
    m = sched.metrics.snapshot()
    # two expert layers, two experts a token, nothing dropped
    assert m["moe_expert_layer_steps"] == 2 * m["decode_steps"]
    assert m["moe_assignments"] == 2 * 2 * m["decode_tokens"]
    assert 1 <= m["moe_experts_touched"] / m["moe_expert_layer_steps"] <= 8
    assert m["moe_feed_expert_layer_blocks"] == 2 * m["prefill_blocks"]
    assert m["latent_cache_bytes"] > 0


def test_monolithic_and_chunked_admission_give_one_stream():
    model, params = build()
    whole, _ = serve(model, params, [request(0)])
    chunked, _ = serve(model, params, [request(0)], prefill_chunk=50)
    assert (whole["r0"] == chunked["r0"]).all()


# ----- the pool-wide sampler ----------------------------------------------


@pytest.mark.parametrize("vocab", [64, 512])
def test_the_pool_sampler_draws_what_the_per_slot_sampler_draws(vocab):
    s = 6
    keys = jax.random.split(jax.random.PRNGKey(4), s)
    logits = 3 * jax.random.normal(jax.random.PRNGKey(5), (s, vocab))
    top_k = jnp.asarray([25, 0, 1, _TOP_K_PARTIAL + 70, 5, 40])
    live = jnp.ones((s,), bool)
    pool_sampler = jax.jit(gumbel_step_slots)
    for parity, temp, top_p in (
        ([True] * s, [1.0] * s, [_TOP_P_OFF] * s),  # the partial selection
        ([True, False, False, True, False, False],
         [1.0, 0.7, 1.3, 1.0, 0.9, 1.0], [_TOP_P_OFF, 0.9, _TOP_P_OFF, _TOP_P_OFF, 0.5, 0.99]),
    ):
        args = (keys, logits, jnp.clip(top_k, 0, vocab), jnp.asarray(parity),
                jnp.asarray(temp, jnp.float32), jnp.asarray(top_p, jnp.float32))
        for k_cap in (top_k, jnp.minimum(top_k, 40)):  # sorted whole, and not
            a = (args[0], args[1], jnp.clip(k_cap, 0, vocab)) + args[3:]
            want_keys, want = jax.vmap(gumbel_step_dynamic)(*a)
            got_keys, got = pool_sampler(*a, live)
            assert (got == want).all() and (got_keys == want_keys).all()


# ----- what the family refuses, with a reason ------------------------------


def test_refusals_name_their_reason():
    model, params = build()
    with pytest.raises(ValueError, match="int8"):
        ServeEngine(model, params, max_slots=2, max_len=64, quantize_int8=True)
    engine = ServeEngine(model, params, max_slots=2, max_len=64)
    with pytest.raises(ValueError, match="prefix cache"):
        engine.set_prefix_cache(object())
    sched = Scheduler(engine)
    ok, why = sched.submit(Request(id="e", prime=ids(4, 0), length=8, kind="embed"))
    assert not ok and "embeddings" in why
    ok, why = sched.submit(Request(id="v", prime=np.asarray([1, 512]), length=8))
    assert not ok and "[0, 512)" in why
    ok, why = sched.submit(Request(id="k", prime=ids(4, 0), length=8, top_k=513))
    assert not ok and "[1, 512]" in why
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("scoring_func", "softmax"), ("rope_scaling", {"type": "yarn"})):
        with pytest.raises(ValueError, match=key):
            LatentMoEConfig.from_dict({**SMALL, key: value})
    with pytest.raises(ValueError, match="unknown model family"):
        build_model({"family": "mamba"})


# ----- cli.serve over stdin with token ids ----------------------------------


def test_cli_serve_takes_and_answers_token_ids(tmp_path):
    from progen_tpu.checkpoint import Package, get_checkpoint_fns

    model, params = build()
    _, _, save = get_checkpoint_fns(str(tmp_path / "ck"))
    save(Package(0, {"params": params}, model.config.to_dict(), "latent-moe"))
    lines = [json.dumps({"id": f"r{i}", "tokens": [5 + i, 400, 17, 300],
                         "length": 20, "seed": i}) for i in range(3)]
    lines.append(json.dumps({"id": "bytes", "prime": "MKV", "length": 20}))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{REPO}{os.pathsep}" + os.environ.get("PYTHONPATH", "")}
    p = subprocess.run(
        [sys.executable, "-m", "progen_tpu.cli.serve", "--checkpoint_path",
         str(tmp_path / "ck"), "--max-slots", "2", "--max-len", "32",
         "--prefill_chunk", "4", "--journal_dir", str(tmp_path / "j")],
        input="\n".join(lines) + "\n", capture_output=True, text=True,
        env=env, cwd=tmp_path, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    events = [json.loads(line) for line in p.stdout.splitlines()]
    done = {e["id"]: e for e in events if e["event"] == "done"}
    assert set(done) == {"r0", "r1", "r2"}
    for e in done.values():  # ids out, no text: the family has no byte codec
        assert "text" not in e and e["n_generated"] == 15
        assert len(e["tokens"]) == 15 and all(0 <= t < 512 for t in e["tokens"])
    tokens = [e for e in events if e["event"] == "token"]
    assert tokens and all("text" not in e for e in tokens)
    rejected = [e for e in events if e["event"] == "rejected"]
    assert [e["id"] for e in rejected] == ["bytes"] and "tokens" in rejected[0]["reason"]
    # the same request straight through the engine gives the same ids
    engine = ServeEngine(model, params, max_slots=2, max_len=32)
    sched = Scheduler(engine, prefill_chunk=4)
    sched.submit(Request(id="r1", prime=np.asarray([6, 400, 17, 300]), length=20,
                         add_bos=True, seed=1))
    _, comps = sched.run_to_completion(max_steps=100)
    assert list(comps[0].tokens[5:]) == done["r1"]["tokens"]


# ----- ProGen's served programs lower as before ----------------------------

# sha256 of the StableHLO text of ProGen's decode step and prefill chunk at
# a small size (jax 0.9.0). They move with any change to what ProGen's
# served path traces: such a change has to say so, and take new values from
# a tree without it. Taken at the commit before this family was added, and
# again in PR 29, which changed what the cache write traces (one update per
# slot in place of a scatter, ``layers._update_at``) and nothing else here:
# the programs are lowered with ``engine.params``, the raw tree. The chunk's
# values are PR 37's: the program takes its cache donated (a
# ``tf.aliasing_output`` mark on each cache leaf's argument) and a block
# hands each cache leaf on under a layout constraint (a ``LayoutConstraint``
# custom call a leaf, ``sampling._row_major``), and nothing else — with the
# constraint an identity and the marks taken out of the text, both layouts
# hashed to the values that stood here before (3f9a1635…95fe, 798d0341…f6ab).
PROGEN_PROGRAMS = {
    ("unrolled", "decode"): "2a417fde763406947fa7303fafc43240af15b26f9167e8616cfffe7bb4de2cbd",
    ("unrolled", "chunk"): "b31c52813d43f9959425c37026532185b5e440e4cc8c6755bae6ca170734b208",
    ("scanned", "decode"): "ad668887b715d9a6bea1a18afae30311ad075662fb00cb4645e39805f2636df7",
    ("scanned", "chunk"): "cfa1e037ecd62078fa97102e41326a7dd1dc19c4c060d781d303d82e0a3283c6",
}


@pytest.mark.parametrize("layout", ["unrolled", "scanned"])
def test_progens_decode_and_prefill_programs_lower_unchanged(layout):
    from flax.core import meta

    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen
    from progen_tpu.serving import engine as E

    over = {"scan_layers": True, "depth": 4} if layout == "scanned" else {}
    cfg = ProGenConfig(**{**dict(
        num_tokens=256, dim=32, seq_len=32, depth=3, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2, dtype="bfloat16",
    ), **over})
    model = ProGen(cfg)
    params = meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, cfg.seq_len), jnp.int32)
    ))["params"]
    eng = ServeEngine(model, params, max_slots=3, max_len=24)
    row = jnp.zeros((24,), jnp.int32)
    texts = {
        "decode": E._decode_step.lower(eng.model, eng.params, eng.slots),
        "chunk": E._prefill_chunk.lower(eng.model, eng.params, eng.new_cache(),
                                        row, jnp.int32(0), jnp.int32(5)),
    }
    for name, lowered in texts.items():
        digest = hashlib.sha256(lowered.as_text().encode()).hexdigest()
        assert digest == PROGEN_PROGRAMS[(layout, name)], name
