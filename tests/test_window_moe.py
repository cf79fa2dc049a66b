"""The window_moe family (sliding-window and full attention side by side,
routed experts of which a layer holds a share) at a small size on the CPU:
against its plain reference, through the block prefill and the slot pool
across the window's edge and the ring's wrap, the pool's batched decode
against batch-1 decode, shares of the experts that add up to the uncut
layer, through the scheduler and cli.serve, what the family refuses; and
the latent_moe programs, which must trace as they did before the expert
product learnt to hold a share."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import window_moe_ref
from progen_tpu.config import load_toml_config
from progen_tpu.models import build_model
from progen_tpu.models import window_moe as wm
from progen_tpu.serving import Request, Scheduler, ServeEngine

REPO = Path(__file__).resolve().parents[1]
SMALL = load_toml_config(str(REPO / "configs/model/window-moe-small.toml"))
RING = SMALL["sliding_window"] + SMALL["feed_rows"]  # 40 rows


def build(dtype="float32", seed=1, **over):
    model = build_model({**SMALL, "dtype": dtype, "param_dtype": dtype, **over})
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    # move every leaf off its initial value: norms from one, the router's
    # bias from zero, matrices far enough that attention is not uniform
    def nudge(path, a):
        k = jax.random.PRNGKey(sum(jax.tree_util.keystr(path).encode()))
        by = 0.3 if a.ndim >= 2 and "w_" in jax.tree_util.keystr(path) else 0.05
        return (a + by * jax.random.normal(k, a.shape)).astype(a.dtype)

    return model, jax.tree_util.tree_map_with_path(nudge, params)


def ids(n, seed):
    return np.random.default_rng(seed).integers(1, 512, size=n).astype(np.int32)


def reference(params, tokens, model, **kw):
    return window_moe_ref.forward(params, tokens, model.config.to_dict(), **kw)


# ----- the full-sequence forward against the reference -------------------


@pytest.mark.parametrize("n,seed", [(24, 0), (100, 1)])
def test_float32_forward_routes_and_computes_as_the_reference(n, seed):
    """Below the window and well past it: the window's mask, RoPE on the
    sliding layers alone, the held share and the sandwich norms."""
    model, params = build()
    tokens = jnp.asarray(ids(n, seed))
    got, aux = model.apply({"params": params}, tokens[None],
                           mutable=["intermediates"])
    want, ref = reference(params, tokens, model, return_aux=True,
                          attention_of=(0, 2))
    for i, theirs in zip((1, 2, 3), ref["experts"]):  # the router's own choices
        mine = aux["intermediates"][f"ffn{i}"]["experts"][0]
        assert (np.sort(np.asarray(mine), -1) == np.sort(np.asarray(theirs), -1)).all()
    for i in (0, 2):
        mine = aux["intermediates"][f"attn{i}"]["out"][0][0]
        assert float(jnp.abs(mine - ref["attention"][i]).max()) < 1e-4
    assert float(jnp.abs(got[0] - want).max()) < 1e-4
    assert float(jnp.abs(want).max()) > 1.0  # the comparison is not of zeros


def test_the_comparison_sees_the_window_and_the_rope_rule(monkeypatch):
    """The reference's logits past the window move when the system ignores
    the window or turns the full layer too: the test above is not blind
    to either."""
    model, params = build()
    tokens = jnp.asarray(ids(100, 2))
    want = reference(params, tokens, model)
    sound = wm.visible
    for name, value in (("visible", lambda rp, t, window: sound(rp, t, None)),
                        ("ROPE_KINDS", (wm.SLIDING, wm.FULL))):
        with monkeypatch.context() as m:
            m.setattr(wm, name, value)
            got = model.apply({"params": params}, tokens[None])[0]
        assert float(jnp.abs(got[40:] - want[40:]).max()) > 1e-2, name


def test_the_router_computes_in_float32_whatever_the_model_computes_in():
    """The published router is float32, over all of its 16 experts here
    whatever the layer holds."""
    model, params = build("bfloat16")
    jaxpr = jax.make_jaxpr(
        lambda p, u: wm.HeldMoE(model.config).apply({"params": p}, u,
                                                    jnp.ones((1, 5), bool))
    )(params["ffn1"], jnp.zeros((1, 5, 64), jnp.bfloat16))
    route = None
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "top_k":
            route = eqn
            break
    assert route is not None and route.invars[0].aval.dtype == jnp.float32
    assert route.invars[0].aval.shape == (5, 16)
    dots = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"
            and e.outvars[0].aval.shape == (5, 16)]
    assert dots and all(d.outvars[0].aval.dtype == jnp.float32
                        and d.params["precision"] is not None for d in dots)


@pytest.mark.parametrize("seed", [3, 4])
def test_bfloat16_forward_stays_near_the_float32_reference(seed):
    """bfloat16 weights and products against float32 arithmetic on the
    same weights, at the system's own choices: the RMS error over the
    logits' deviation, read here over seeds 3-6: 2.8-11% (a near-tie among
    16 scores flips now and then); the reference with every matrix
    rounded to the 8-bit float below bfloat16 (5 exponent, 2 mantissa
    bits): 28-36% against its float32 self. The limit stands between."""
    model, params = build("bfloat16", seed=seed)
    tokens = jnp.asarray(ids(96, seed))
    want = np.asarray(reference(params, tokens, model), np.float64)

    def rms(got):
        err = np.asarray(got, np.float64) - want
        return np.sqrt((err ** 2).mean()) / want.std()

    assert rms(model.apply({"params": params}, tokens[None])[0]) < 0.2
    assert rms(reference(params, tokens, model, weight_bits=(5, 2))) > 0.2


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


def test_block_prefill_under_three_splits_then_decode_across_the_wrap():
    """A prompt of 60 positions (the rings of 40 rows have wrapped once)
    fed whole, a block at a time and in chunks of 5, then 40 positions
    decoded through the pool: every ring wraps again and the full layer's
    rows grow past 100; logits against the reference at every decoded
    position, the caches bit-equal under the three splits."""
    model, params = build()
    engine = ServeEngine(model, params, max_slots=3, max_len=128)
    row = np.concatenate([[0], ids(100, 5)]).astype(np.int32)
    n_prompt = 60
    want = np.asarray(reference(params, jnp.asarray(row), model))[n_prompt:]
    caches = []
    for chunk in (None, SMALL["feed_rows"], 5):
        got, left = prefill_then_decode(engine, row, n_prompt, chunk)
        assert np.abs(got - want).max() < 1e-4
        left.pop("moe_feed")  # counts passes, which the split changes
        caches.append(left)
    assert caches[0]["attn0"]["ring_k"].shape == (1, 2, RING, 16)
    assert caches[0]["attn2"]["k"].shape == (1, 2, 128, 16)
    for other in caches[1:]:  # bit-equal however the prompt was split
        for a, b in zip(jax.tree.leaves(caches[0]), jax.tree.leaves(other)):
            assert (a == b).all()


def test_a_ring_row_holds_the_position_its_query_reads_from_it():
    """The visibility rule without stored positions: row r of a ring of R
    rows holds p - ((p - r) mod R) for a query at p, and a block of
    feed_rows written where it stands overwrites only what no query of
    the block sees."""
    size, window, block = RING, SMALL["sliding_window"], SMALL["feed_rows"]
    for start in range(0, 200, block):
        held = {}  # row -> position, after writing the block
        for p in range(start + block):
            held[p % size] = p
        for p in range(start, start + block):
            rows = np.arange(size)
            got = np.asarray(wm.held_position(jnp.int32(p), rows, size))
            ok = np.asarray(wm.visible(got, p, window))
            want = [s for s in range(max(0, p - window + 1), p + 1)]
            assert sorted(got[ok]) == want
            for r in rows[ok]:  # what the row really holds
                assert held[r] == got[r]


def test_the_pool_decode_equals_batch_one_decode():
    """The slot-batched decode of three slots at different depths, each
    row against the same row decoded alone through a batch-1 cache."""
    model, params = build()
    engine = ServeEngine(model, params, max_slots=3, max_len=128)
    dec = engine.model
    rows = [np.concatenate([[0], ids(90, 20 + i)]).astype(np.int32) for i in range(3)]
    depths = [10, 45, 70]
    slots = []
    for row, d in zip(rows, depths):
        slots.append(engine.acquire())
        pending = engine.begin_prefill(slots[-1], row[1:d + 1], 100,
                                       add_bos=True, top_k=None)
        while not engine.advance_prefill(pending, None):
            pass
    alone = []
    for row, d in zip(rows, depths):
        cache = dec.init(jax.random.PRNGKey(0), jnp.zeros((1, 1), jnp.int32))["cache"]
        cache = dec.feed_tokens(engine.params, cache, jnp.asarray(row)[None], 0, d)
        out = []
        for i in range(d, d + 12):
            (lg, _), mut = dec.apply({"params": engine.params, "cache": cache},
                                     jnp.asarray(row)[None, i:i + 1],
                                     jnp.full((1, 1), i), None, mutable=["cache"])
            cache = mut["cache"]
            out.append(np.asarray(lg[0, 0]))
        alone.append(np.stack(out))
    cache, live = engine.slots.cache, jnp.ones((3,), bool)
    for j in range(12):
        toks = jnp.asarray([row[d + j] for row, d in zip(rows, depths)])
        pos = jnp.asarray([d + j for d in depths])
        logits, cache, counts = dec.decode_slots(engine.params, cache, toks, pos, live)
        for k, s in enumerate(slots):
            assert np.abs(np.asarray(logits[s]) - alone[k][j]).max() < 1e-4
    folded = dec.fold_counts(np.asarray(counts), 3)
    assert folded["moe_assignments"] == 3 * 3 * 4
    assert 0 <= folded["moe_held_assignments"] <= folded["moe_assignments"]
    for s in slots:
        engine.release(s)


# ----- the share ---------------------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight layers each holding 2 of the router's 16 experts, fed the same
    rows: their outputs less the shared expert, summed, plus the shared
    expert once, are the layer that holds all 16; and that layer is the
    reference's."""
    model, params = build(num_experts=16, router_experts=16, first_expert=0)
    p = params["ffn1"]
    u = jax.random.normal(jax.random.PRNGKey(7), (2, 9, 64))
    live = jnp.ones((2, 9), bool)
    whole, stats = wm.HeldMoE(model.config).apply({"params": p}, u, live)
    shared = wm.DenseFFN(model.config, 32).apply({"params": p["shared"]}, u)
    total, held = shared, 0
    for s in range(8):
        c = wm.WindowMoEConfig.from_dict(
            {**SMALL, "num_experts": 2, "router_experts": 16, "first_expert": 2 * s})
        part = {**p, "w_gate_up": p["w_gate_up"][2 * s:2 * s + 2],
                "w_down": p["w_down"][2 * s:2 * s + 2]}
        y, st = wm.HeldMoE(c).apply({"params": part}, u, live)
        total, held = total + (y - shared), held + int(st[2])
    assert float(jnp.abs(total - whole).max()) < 1e-5
    assert held == int(stats[2]) == 2 * 9 * 4  # every assignment held once
    # the uncut layer against the reference's experts and shared expert
    cfg = tuple(sorted((k, v) for k, v in model.config.to_dict().items()
                       if isinstance(v, (int, float, bool, str)) or v is None))
    x = u.reshape(-1, 64)
    _, dense, _ = window_moe_ref._route(p["w_router"], p["expert_bias"], x,
                                        jnp.full((18, 4), -1), cfg)
    want = window_moe_ref._dense_ffn(p["shared"], x, None) + window_moe_ref._experts(
        p["w_gate_up"], p["w_down"], x, dense, None)
    assert float(jnp.abs(whole.reshape(-1, 64) - want).max()) < 1e-4


# ----- through the scheduler ---------------------------------------------


def serve(model, params, requests, **sched):
    engine = ServeEngine(model, params, max_slots=3, max_len=128)
    scheduler = Scheduler(engine, **sched)
    for req in requests:
        assert scheduler.submit(req) == (True, None)
    _, completions = scheduler.run_to_completion(max_steps=2000)
    return {c.request_id: c.tokens for c in completions}, scheduler


def request(i, n_prompt=50, length=100, **kw):
    return Request(id=f"r{i}", prime=ids(n_prompt, 10 + i), length=length,
                   add_bos=True, seed=i, **kw)


def test_a_request_in_company_is_bit_identical_to_the_same_request_alone():
    model, params = build("bfloat16")
    alone, _ = serve(model, params, [request(0)], prefill_chunk=8)
    company = [request(0), request(1, 30, 90, temperature=0.8, top_p=0.9),
               request(2, 70, 110, top_k=200), request(3, 9, 60)]
    together, sched = serve(model, params, company, prefill_chunk=8)
    assert (alone["r0"] == together["r0"]).all()
    assert len(together) == 4 and (together["r0"][51:] != 0).any()
    m = sched.metrics.snapshot()
    # three expert layers, four experts a token, a quarter of them held
    assert m["moe_expert_layer_steps"] == 3 * m["decode_steps"]
    assert m["moe_assignments"] == 3 * 4 * m["decode_tokens"]
    assert 0 < m["moe_held_assignments"] < m["moe_assignments"]
    assert m["moe_feed_expert_layer_blocks"] == 3 * m["prefill_blocks"]
    assert 0 < m["moe_feed_held_assignments"] < m["moe_feed_assignments"]
    assert m["moe_feed_assignments"] == 3 * 4 * m["prefill_tokens"]
    # a block's 8 rows at top-4 are 32 assignments: under every rung
    assert m["moe_feed_product_rows"] == 32 * m["moe_feed_expert_layer_blocks"]
    # three rings of 40 rows and one full layer of 128, K and V, 3 slots
    assert m["window_cache_bytes"] == 3 * 2 * 3 * 2 * RING * 16 * 2
    assert m["kv_cache_bytes"] == 3 * 2 * 1 * 2 * 128 * 16 * 2
    # off the TPU a decode step reads those leaves whole
    assert m["attend_rows_read"] == m["attend_rows_held"] == (
        (3 * RING + 128) * m["decode_tokens"])


# ----- the decode kernel through the engine --------------------------------

# the smallest shapes the decode kernel's tiling fits: heads of 128, a
# window of 256 in a ring of 384 rows, a full layer of 640, blocks of 128
KERNEL_SHAPES = dict(head_dim=128, sliding_window=256, feed_rows=128)


def _decode_through_the_engine(rule, monkeypatch):
    """Three slots admitted at 250, 380 and 3 positions, then 12 decode
    steps (the first slot crosses the window's edge, the second the ring's
    end), under the kernel's backend test answered ``rule == "kernel"``:
    (tokens a step, live slots a step, the engine's counters, the kernel's
    calls)."""
    from progen_tpu.ops import pallas_window_decode_attention as K

    monkeypatch.setattr(K, "on_tpu", lambda: rule == "kernel")
    calls = []
    real = wm.window_decode_attention
    monkeypatch.setattr(wm, "window_decode_attention",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    jax.clear_caches()
    model, params = build(**KERNEL_SHAPES)
    engine = ServeEngine(model, params, max_slots=3, max_len=640)
    for i, n in enumerate((250, 380, 3)):
        pending = engine.begin_prefill(engine.acquire(), ids(n, 40 + i), 500,
                                       add_bos=True, top_k=None, seed=i)
        while not engine.advance_prefill(pending, None):
            pass
    engine.pop_counters()
    tokens, live = [], []
    for _ in range(12):
        sampled, was_live, _ = engine.decode_step()
        tokens.append(np.asarray(sampled))
        live.append(np.asarray(was_live))
    jax.clear_caches()
    return np.stack(tokens), np.stack(live), engine.pop_counters(), calls


def test_the_decode_kernel_reads_the_blocks_the_host_counts(monkeypatch):
    """The same slots decoded by the plain form and by the kernel (interpret
    mode, its backend test turned): the same tokens; the plain form reads
    every leaf whole, the kernel the blocks that hold a visible row, as a
    brute-force count of them says."""
    with monkeypatch.context() as m:
        want, live, plain, none = _decode_through_the_engine("plain", m)
    got, live_k, counted, calls = _decode_through_the_engine("kernel", monkeypatch)
    assert none == [] and calls
    assert {kw["block"] for kw in calls} == {128}
    assert (live == live_k).all() and live.all()
    assert (got == want).all()
    kinds = SMALL["layer_types"]
    held = read = 0
    for step in range(12):
        for t in (250 + step, 380 + step, 3 + step):
            for kind in kinds:
                rows, window = (384, 256) if kind == wm.SLIDING else (640, None)
                seen = np.asarray(wm.visible(wm.held_position(
                    t, jnp.arange(rows), rows), t, window))
                read += 128 * int(seen.reshape(-1, 128).any(axis=1).sum())
                held += rows
    assert plain["attend_rows_read"] == plain["attend_rows_held"] == held
    assert counted["attend_rows_held"] == held
    assert counted["attend_rows_read"] == read
    assert 0.3 < read / held < 1.0


# ----- what the family refuses, with a reason ------------------------------


def test_refusals_name_their_reason():
    model, params = build()
    with pytest.raises(ValueError, match="int8"):
        ServeEngine(model, params, max_slots=2, max_len=64, quantize_int8=True)
    for key, value in (("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", True), ("score_func", "softmax"),
                       ("n_group", 8), ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            wm.WindowMoEConfig.from_dict({**SMALL, key: value})
    for over, why in (({"layer_types": ["sliding_attention"] * 3}, "layer_types"),
                      ({"layer_types": ["chunked_attention"] * 4}, "unknown layer type"),
                      ({"first_expert": 13}, "share"),
                      ({"sliding_window": 36}, "feed_rows")):
        with pytest.raises(ValueError, match=why):
            wm.WindowMoEConfig.from_dict({**SMALL, **over})


# ----- cli.serve over stdin with token ids ----------------------------------


def test_cli_serve_takes_and_answers_token_ids(tmp_path):
    from progen_tpu.checkpoint import Package, get_checkpoint_fns

    model, params = build()
    _, _, save = get_checkpoint_fns(str(tmp_path / "ck"))
    save(Package(0, {"params": params}, model.config.to_dict(), "window-moe"))
    lines = [json.dumps({"id": f"r{i}", "tokens": [5 + i, 400, 17, 300],
                         "length": 20, "seed": i}) for i in range(2)]
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{REPO}{os.pathsep}" + os.environ.get("PYTHONPATH", "")}
    p = subprocess.run(
        [sys.executable, "-m", "progen_tpu.cli.serve", "--checkpoint_path",
         str(tmp_path / "ck"), "--max-slots", "2", "--max-len", "32",
         "--prefill_chunk", "8"],
        input="\n".join(lines) + "\n", capture_output=True, text=True,
        env=env, cwd=tmp_path, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    events = [json.loads(line) for line in p.stdout.splitlines()]
    done = {e["id"]: e for e in events if e["event"] == "done"}
    assert set(done) == {"r0", "r1"}
    for e in done.values():  # ids out, no text: the family has no byte codec
        assert "text" not in e and e["n_generated"] == 15
        assert all(0 <= t < 512 for t in e["tokens"])


# ----- latent_moe's programs trace as before --------------------------------


def _grouped_experts_before(x, idx, weights, live, w_gate_up, w_down):
    """``latent_moe.grouped_experts`` as it stood before it took a held
    share, verbatim."""
    n, k = idx.shape
    e = w_gate_up.shape[0]
    flat = jnp.where(live[:, None], idx, e).reshape(-1)  # dead: no expert
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    h = jax.lax.ragged_dot(x[order // k], w_gate_up, sizes)
    gate, up = jnp.split(h, 2, axis=-1)
    ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down, sizes)
    ys = jnp.where((jnp.arange(n * k) < jnp.sum(sizes))[:, None], ys, 0)
    y = ys[jnp.argsort(order)].reshape(n, k, -1).astype(jnp.float32)
    y = jnp.einsum("nk,nkd->nd", weights, y,
                   precision=jax.lax.Precision.HIGHEST)
    return y, jnp.sum((sizes > 0).astype(jnp.int32)), jnp.max(sizes)


def _grouped_experts_all_rows(x, idx, weights, live, w_gate_up, w_down,
                              held=None):
    """``latent_moe.grouped_experts`` as it stood before a held share's
    products ran over a rung of rows, verbatim: every product over all
    N*K sorted rows."""
    n, k = idx.shape
    e = w_gate_up.shape[0]
    keep = live[:, None] if held is None else live[:, None] & held
    flat = jnp.where(keep, idx, e).reshape(-1)  # dead: no expert
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.zeros((e + 1,), jnp.int32).at[flat].add(1)[:e]
    h = jax.lax.ragged_dot(x[order // k], w_gate_up, sizes)
    gate, up = jnp.split(h, 2, axis=-1)
    ys = jax.lax.ragged_dot(jax.nn.silu(gate) * up, w_down, sizes)
    # rows past the last group belong to no expert: whatever is there
    ys = jnp.where((jnp.arange(n * k) < jnp.sum(sizes))[:, None], ys, 0)
    # back to (row, its k-th choice): each row sums its own products in
    # its own top-k order, whatever else the call held
    y = ys[jnp.argsort(order)].reshape(n, k, -1).astype(jnp.float32)
    y = jnp.einsum("nk,nkd->nd", weights, y,
                   precision=jax.lax.Precision.HIGHEST)
    return y, jnp.sum((sizes > 0).astype(jnp.int32)), jnp.max(sizes)


# kept assignments of a 512-row block's 2,048, and the rung each runs over
_RUNG_CASES = [(0, 192), (1, 192), (192, 192), (193, 448), (448, 448),
               (449, 960), (511, 960), (513, 960), (961, 1984), (1985, 2048),
               (2048, 2048)]


@pytest.mark.parametrize("kept,rung", _RUNG_CASES + [("part", None)])
def test_a_held_share_runs_its_products_over_the_rung_that_holds_its_rows(
        kept, rung):
    """A block of 512 rows, top-4 over 8 held experts: the products over
    the smallest rung that holds the kept assignments give what the
    products over all 2,048 rows give, bit for bit, with the same experts
    touched and busiest rows; ``product_rows`` names the rung; every
    assignment held takes all 2,048 rows. ``part``: a quarter of the rows
    live and a random share held."""
    from progen_tpu.models import latent_moe

    n, k, e, d, f = 512, 4, 8, 16, 8
    keys = jax.random.split(jax.random.PRNGKey(11), 5)
    x = jax.random.normal(keys[0], (n, d), jnp.bfloat16)
    idx = jax.random.randint(keys[1], (n, k), 0, e)
    weights = jax.random.uniform(keys[2], (n, k))
    w_gate_up = 0.3 * jax.random.normal(keys[3], (e, d, 2 * f), jnp.bfloat16)
    w_down = 0.3 * jax.random.normal(keys[4], (e, f, d), jnp.bfloat16)
    rng = np.random.default_rng(3)
    if kept == "part":
        live = jnp.asarray(rng.random(n) < 0.25)
        held = jnp.asarray(rng.random((n, k)) < 0.3)
        kept = int((live[:, None] & held).sum())
        rung = 192
        assert 0 < kept < 192  # a block whose rows are partly live
    else:
        live = jnp.ones((n,), bool)
        held = np.zeros(n * k, bool)
        held[rng.permutation(n * k)[:kept]] = True
        held = jnp.asarray(held.reshape(n, k))
    args = (x, idx, weights, live, w_gate_up, w_down)
    got = latent_moe.grouped_experts(*args, held=held)
    want = _grouped_experts_all_rows(*args, held=held)
    assert int(latent_moe.product_rows(jnp.int32(kept), n * k)) == rung
    assert (np.asarray(got[0]) == np.asarray(want[0])).all()
    assert int(got[1]) == int(want[1]) and int(got[2]) == int(want[2])
    if kept:
        assert np.abs(np.asarray(want[0])).max() > 0  # not a comparison of zeros


def test_fold_counts_reads_six_columns_of_what_blocks_fed():
    model, _ = build()
    n = model.config.n_expert_layers
    step = np.tile([2, 3, 5], (n, 1))
    fed = np.tile([4, 7, 9, 40, 96, 128], (n, 1))
    got = model.fold_counts(np.concatenate([step.reshape(-1),
                                            fed.reshape(-1)]), 6)
    assert got["moe_held_assignments"] == 5 * n
    assert got["moe_feed_expert_layer_blocks"] == 4 * n
    assert got["moe_feed_assignments"] == 96 * n
    assert got["moe_feed_product_rows"] == 128 * n


def test_window_moe_decode_program_traces_as_before(monkeypatch):
    """32 slots at top-4 are 128 assignment rows, the cell's decode step:
    no rung lies below them, so the step is the program it was."""
    from progen_tpu.serving import engine as E

    model, params = build("bfloat16")
    eng = ServeEngine(model, params, max_slots=32, max_len=64)

    def program():
        return str(jax.make_jaxpr(
            lambda p, s: E._decode_step_impl(eng.model, p, s))(
                eng.params, eng.slots))

    now = program()
    monkeypatch.setattr(wm, "grouped_experts", _grouped_experts_all_rows)
    assert program() == now


def test_the_lowered_programs_products_take_each_rungs_rows():
    """Blocks of 512 rows, top-4 (2,048 assignments a block): each expert
    layer of the chunk program holds the products of every rung, and the
    decode step's of 32 slots take 128 rows alone."""
    import re

    from progen_tpu.models import latent_moe
    from progen_tpu.serving import engine as E

    model, params = build("bfloat16", feed_rows=512, sliding_window=512)
    eng = ServeEngine(model, params, max_slots=32, max_len=1024)

    def rows(traced):  # StableHLO for a TPU: ragged_dot stays whole
        text = traced.lower(lowering_platforms=("tpu",)).as_text()
        return [int(r) for r in re.findall(
            r"chlo.ragged_dot.*\(tensor<(\d+)x\d+x", text)]

    chunk = rows(E._prefill_chunk.trace(
        eng.model, eng.params, eng.new_cache(), jnp.zeros((1024,), jnp.int32),
        jnp.int32(0), jnp.int32(600)))
    decode = rows(E._decode_step.trace(eng.model, eng.params, eng.slots))
    layers = model.config.n_expert_layers
    assert latent_moe._rungs(2048) == (192, 448, 960, 1984, 2048)
    assert sorted(chunk) == sorted(
        [r for r in latent_moe._rungs(2048) for _ in range(2 * layers)])
    assert decode == [128] * 2 * layers


def test_latent_moe_decode_and_chunk_programs_trace_as_before(monkeypatch):
    from progen_tpu.models import latent_moe
    from progen_tpu.serving import engine as E

    small = load_toml_config(str(REPO / "configs/model/latent-moe-small.toml"))
    model = build_model({**small, "dtype": "bfloat16", "param_dtype": "bfloat16"})
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    eng = ServeEngine(model, params, max_slots=3, max_len=256)
    row = jnp.zeros((256,), jnp.int32)

    def programs():
        return (
            str(jax.make_jaxpr(lambda p, s: E._decode_step_impl(eng.model, p, s))(
                eng.params, eng.slots)),
            str(jax.make_jaxpr(lambda p, c: E.feed_tokens(
                eng.model, p, c, row[None], jnp.int32(0), jnp.int32(200)))(
                eng.params, eng.new_cache())),
        )

    now = programs()
    monkeypatch.setattr(latent_moe, "grouped_experts", _grouped_experts_before)
    assert programs() == now
