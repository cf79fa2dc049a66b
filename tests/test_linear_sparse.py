"""The linear_sparse family (linear-attention layers with a recurrent
state, block-sparse attention layers with an index cache) at a small size
on the CPU: against its plain reference, through the blocked scan and the
block prefill, the selection against the reference's, what a split of a
prefill leaves behind, through the scheduler and through cli.serve, what
the family refuses, and three planted faults that the comparison with the
reference must see."""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import linear_sparse_ref
from progen_tpu.config import load_toml_config
from progen_tpu.models import (FAMILIES, build_model, decode_model,
                               require_progen)
from progen_tpu.models import linear_sparse as ls
from progen_tpu.serving import Request, Scheduler, ServeEngine

REPO = Path(__file__).resolve().parents[1]
SMALL = load_toml_config(str(REPO / "configs/model/linear-sparse-small.toml"))
N = 160  # past dense_len 32 by far: 20 blocks, of which a query chooses 6


def build(dtype="float32", seed=1, **over):
    model = build_model({**SMALL, "dtype": dtype, "param_dtype": dtype, **over})
    params = model.init(
        jax.random.PRNGKey(seed), jnp.zeros((1, 8), jnp.int32)
    )["params"]

    # move every leaf off its initial value: norms from one, and matrices
    # far enough that attention and the selection are not near uniform
    def nudge(path, a):
        k = jax.random.PRNGKey(sum(jax.tree_util.keystr(path).encode()))
        by = 0.3 if a.ndim >= 2 else 0.05
        return (a + by * jax.random.normal(k, a.shape)).astype(a.dtype)

    return model, jax.tree_util.tree_map_with_path(nudge, params)


def ids(n, seed):
    return np.random.default_rng(seed).integers(1, 512, size=n).astype(np.int32)


def fresh_cache(dec, batch=1):
    return dec.init(jax.random.PRNGKey(0), jnp.zeros((batch, 1), jnp.int32))["cache"]


def _feed(dec, params, cache, tokens, lo, hi):
    return dec.feed_tokens(params, cache, tokens, lo, hi)


def _one_row(dec, params, cache, tok, pos, keep=("cache",)):
    return dec.apply({"params": params, "cache": cache}, tok, pos, None,
                     mutable=list(keep))


FEED = jax.jit(_feed, static_argnames=("dec",))
ONE_ROW = jax.jit(_one_row, static_argnames=("dec", "keep"))
BOTH = ("cache", "intermediates")


def chosen_sets(blocks, n_blocks):
    """A (..., K) array of block ids (padding: negative or >= n_blocks)
    as a boolean (..., n_blocks) membership."""
    blocks = np.asarray(blocks)
    ok = (blocks >= 0) & (blocks < n_blocks)
    hit = (blocks[..., None] == np.arange(n_blocks)) & ok[..., None]
    return hit.any(-2)


# ----- the full-sequence forward against the reference -------------------


@pytest.mark.parametrize("n,seed", [(40, 0), (N, 1), (N + 13, 2)])
def test_float32_forward_computes_and_chooses_as_the_reference(n, seed):
    model, params = build()
    tokens = jnp.asarray(ids(n, seed))
    got, aux = model.apply({"params": params}, tokens[None],
                           mutable=["intermediates"])
    want, ref = linear_sparse_ref.forward(
        params, tokens, model.config.to_dict(), return_aux=True, rows=64
    )
    assert float(jnp.abs(got[0] - want).max()) < 2e-4
    assert float(jnp.abs(want).max()) > 1.0  # the comparison is not of zeros
    n_blocks = -(-n // 8)
    for name, theirs in zip(("mix0", "mix3"), ref["blocks"]):
        mine = aux["intermediates"][name]["blocks"][0][0]  # (G, T, K)
        mine = chosen_sets(np.moveaxis(np.asarray(mine), 0, 1), n_blocks)
        theirs = chosen_sets(theirs, n_blocks)
        # identical choices at every position that selects
        assert (mine[32:] == theirs[32:]).all()
        if n > 64:  # and selection leaves blocks out
            assert theirs[-1].sum(-1).max() == 6 < n_blocks


@pytest.mark.parametrize("seed", [3, 4, 5])
def test_bfloat16_forward_stays_near_the_float32_reference(seed):
    model, params = build("bfloat16")
    tokens = jnp.asarray(ids(N, seed))
    got = model.apply({"params": params}, tokens[None])[0]
    want = linear_sparse_ref.forward(
        params, tokens, model.config.to_dict(), rows=64
    )
    err = np.asarray(got, np.float64) - np.asarray(want, np.float64)
    # at its own choices a near-tie exchanges a block now and then: the
    # bulk of the logits stays within bfloat16's rounding over 8 blocks
    assert np.sqrt((err ** 2).mean()) / float(want.std()) < 0.08


# ----- the decode mode: blocks, one row, the two kinds of state -------------


def test_block_prefill_then_decode_through_the_cache_matches_the_reference():
    model, params = build()
    row = jnp.asarray(ids(N, 7))
    want, ref = linear_sparse_ref.forward(
        params, row, model.config.to_dict(), return_aux=True, rows=64,
        state_at=140,
    )
    dec = decode_model(model, 200)
    cache = FEED(dec, params, fresh_cache(dec), row[None], 0, 140)
    # the recurrent state after the prompt is the token recurrence's
    for name, state in zip(("mix1", "mix2"), ref["state"]):
        got = np.asarray(cache[name]["state"][0])
        assert np.abs(got - np.asarray(state)).max() < 1e-4 * np.abs(state).max()
    n_blocks = N // 8
    for i in range(140, N):
        (logits, counts), mut = ONE_ROW(dec, params, cache, row[None, i:i + 1],
                                        jnp.full((1, 1), i), BOTH)
        cache = mut["cache"]
        assert float(jnp.abs(logits[0, 0] - want[i]).max()) < 2e-4
        for name, theirs in zip(("mix0", "mix3"), ref["blocks"]):
            mine = mut["intermediates"][name]["blocks"][0][0, :, 0]  # (G, K)
            assert (chosen_sets(mine, n_blocks)
                    == chosen_sets(theirs[i], n_blocks)).all()
        # one live query a sparse layer: it could see i + 1 rows and read
        # 6 blocks of 8 less what stands behind it in its own
        assert (np.asarray(counts) == [i + 1, 48 - (7 - i % 8), 6]).all()


def test_the_blocked_scan_is_the_token_recurrence():
    """60 positions a row at a time, and as two blocks of 32: the same
    K/V rows and pooled keys bit for bit where no lightning layer stands
    before them, and everything equal to float32 rounding."""
    model, params = build()
    row = jnp.asarray(ids(64, 8))
    dec = decode_model(model, 64)
    one = fresh_cache(dec)
    for i in range(60):
        one = ONE_ROW(dec, params, one, row[None, i:i + 1],
                      jnp.full((1, 1), i))[1]["cache"]
    blocked = FEED(dec, params, fresh_cache(dec), row[None], 0, 60)
    def rows(cache, name, leaf, upto):  # (B, G x S, d) as (B, G, rows, d)
        x = np.asarray(cache[name][leaf])
        return x.reshape(1, 2, -1, x.shape[-1])[:, :, 1:upto]

    for leaf in ("k", "v"):
        assert (rows(one, "mix0", leaf, 60) == rows(blocked, "mix0", leaf, 60)).all()
    for name in ("mix1", "mix2"):
        a, b = np.asarray(one[name]["state"]), np.asarray(blocked[name]["state"])
        assert np.abs(a - b).max() < 1e-5 * np.abs(b).max()
    for leaf, upto in (("k", 60), ("v", 60), ("ck", 30)):
        a, b = rows(one, "mix3", leaf, upto), rows(blocked, "mix3", leaf, upto)
        assert np.abs(a - b).max() < 1e-5 * np.abs(b).max() and np.abs(b).max() > 0.1


def test_forced_blocks_are_always_chosen_and_the_rest_are_the_best():
    model, params = build()
    tokens = jnp.asarray(ids(N, 9))
    _, aux = model.apply({"params": params}, tokens[None],
                         mutable=["intermediates"])
    n_blocks = N // 8
    for name in ("mix0", "mix3"):
        blocks = np.asarray(aux["intermediates"][name]["blocks"][0][0])
        for t in range(32, N):
            for g in range(2):
                mine = set(int(b) for b in blocks[g, t] if b >= 0)
                assert {0, t // 8, t // 8 - 1} <= mine and len(mine) == min(6, t // 8 + 1)
                assert max(mine) <= t // 8  # nothing from the future


def test_what_a_split_of_a_prefill_leaves_behind():
    """``LinearSparse.feed_tokens`` states the contract: bit-equal on block
    boundaries; inside a block the recurrent state equal to float32
    rounding and, behind it, whatever it feeds."""
    model, params = build()
    row = jnp.asarray(ids(150, 10))
    dec = decode_model(model, 160)
    def through(*cuts):
        cache, lo = fresh_cache(dec), 0
        for hi in (*cuts, 150):
            cache, lo = FEED(dec, params, cache, row[None], lo, hi), hi
        return jax.tree.map(np.asarray, cache)

    whole = through()
    on_blocks = through(32, 96)
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(on_blocks)):
        assert (a == b).all()
    inside = through(50, 51, 117)
    feed_words = whole.pop("sparse_feed"), inside.pop("sparse_feed")
    # the first sparse layer stands before every lightning layer
    for leaf in ("k", "v", "ck"):
        assert (whole["mix0"][leaf] == inside["mix0"][leaf]).all()
    # float32: a block's sum cut in two, each half rounded once more
    for path, a in jax.tree_util.tree_leaves_with_path(whole):
        b = inside[path[0].key][path[1].key]
        assert np.abs(a - b).max() <= 2e-5 * np.abs(a).max(), path
    # the counts of what was fed do not depend on the split
    fold = dec.fold_counts
    zeros = np.zeros(6, np.int64)
    totals = [fold(np.concatenate([zeros, f[0].reshape(-1)]), 0)
              for f in feed_words]
    for key in ("sparse_feed_rows_visible", "sparse_feed_rows_attended",
                "sparse_feed_blocks_selected"):
        assert totals[0][key] == totals[1][key] > 0
    assert totals[0]["sparse_feed_rows_visible"] == 2 * 150 * 151 // 2


def test_a_long_prompts_row_counts_pass_32_bits_whole():
    model, _ = build()
    n = model.config.n_sparse_layers
    words = np.zeros((n, 7), np.int64)
    words[:, 0], words[:, 1], words[:, 2] = 40, 5000, 17  # 5000 * 2^20 + 17
    got = model.fold_counts(np.concatenate([np.zeros(3 * n), words.reshape(-1)]).astype(np.int64), 0)
    assert got["sparse_feed_rows_visible"] == n * (5000 * 2 ** 20 + 17) > 2 ** 32
    assert got["sparse_feed_layer_blocks"] == n * 40


# ----- through the scheduler ---------------------------------------------


def serve(model, params, requests, max_len=224, **sched):
    engine = ServeEngine(model, params, max_slots=3, max_len=max_len)
    scheduler = Scheduler(engine, **sched)
    for req in requests:
        assert scheduler.submit(req) == (True, None)
    _, completions = scheduler.run_to_completion(max_steps=2000)
    return {c.request_id: c for c in completions}, scheduler


def request(i, n_prompt=130, length=160, **kw):
    return Request(id=f"r{i}", prime=ids(n_prompt, 10 + i), length=length,
                   add_bos=True, seed=i, **kw)


def frozen_prompt(i, n_prompt, out_len):
    """The benchmark's request: a template freezes the prompt, so exactly
    ``out_len`` tokens come back."""
    from benchmark.drivers.gen import make_request

    return make_request(f"f{i}", ids(n_prompt, 30 + i), out_len,
                        {"top_k": 25, "temperature": 1.0})


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_a_request_in_company_is_bit_identical_to_the_same_request_alone(dtype):
    model, params = build(dtype)
    alone, _ = serve(model, params, [request(0)], prefill_chunk=32)
    company = [request(0), request(1, 140, 170, temperature=0.8, top_p=0.9),
               request(2, 135, 150, top_k=200), request(3, 131, 165)]
    together, sched = serve(model, params, company, prefill_chunk=32)
    assert (alone["r0"].tokens == together["r0"].tokens).all()
    assert len(together) == 4 and (together["r0"].tokens[131:] != 0).any()
    m = sched.metrics.snapshot()
    # two sparse layers a step; every live query sees more than it reads
    assert m["sparse_layer_steps"] == 2 * m["decode_steps"]
    assert m["sparse_blocks_selected"] == 6 * 2 * m["decode_tokens"]
    assert 0 < m["sparse_rows_attended"] < 0.5 * m["sparse_rows_visible"]
    assert m["sparse_feed_layer_blocks"] == 2 * m["prefill_blocks"]
    assert m["sparse_feed_rows_attended"] < m["sparse_feed_rows_visible"]
    # the pool's two kinds of state, counted apart
    rows = 224
    assert m["kv_cache_bytes"] == 3 * 2 * 2 * rows * 2 * 16 * jnp.dtype(dtype).itemsize
    assert m["index_cache_bytes"] * 2 * 2 == m["kv_cache_bytes"]
    assert m["linear_state_bytes"] == 3 * 2 * 4 * 16 * 16 * 4  # float32 always


def test_exact_output_lengths_and_one_stream_under_block_aligned_chunks():
    model, params = build()
    reqs = [frozen_prompt(0, 96, 12), frozen_prompt(1, 128, 20),
            frozen_prompt(2, 160, 7)]
    whole, _ = serve(model, params, reqs)
    chunked, _ = serve(model, params, reqs, prefill_chunk=64)
    for r, out in zip(reqs, (12, 20, 7)):
        assert whole[r.id].n_generated == out == chunked[r.id].n_generated
        assert (whole[r.id].tokens == chunked[r.id].tokens).all()
        assert (whole[r.id].tokens[1:-out] == r.prime).all()


# ----- what the family refuses, with a reason --------------------------------


def test_a_config_it_cannot_compute_is_refused_by_name():
    for key, value in (("attention_bias", True), ("attn_use_rope", True),
                       ("lightning_use_rope", False),
                       ("tie_word_embeddings", True), ("hidden_act", "gelu"),
                       ("lightning_nkv", 2),
                       ("mixer_types", ["minicpm4", "mamba2"] * 2)):
        with pytest.raises(ValueError, match="mixer type|" + key):
            ls.LinearSparseConfig.from_dict({**SMALL, key: value})


def test_build_model_names_the_known_families_from_its_table():
    with pytest.raises(ValueError, match="unknown model family 'mamba'.*"
                       + ", ".join(FAMILIES)):
        build_model({"family": "mamba"})
    assert list(FAMILIES) == ["progen", "latent_moe", "linear_sparse", "window_moe"]
    with pytest.raises(ValueError, match="linear_sparse family runs on one chip"):
        build_model(SMALL, mesh=object())


def test_require_progen_names_the_family_and_where_it_is_served():
    model, _ = build()
    with pytest.raises(SystemExit, match="cli.scan runs the progen family only; "
                       "a LinearSparse checkpoint is served by cli.serve.*"
                       "latent_moe, linear_sparse"):
        require_progen(model, "cli.scan")


def test_the_engine_refuses_int8_with_a_reason_true_of_the_family():
    model, params = build()
    with pytest.raises(ValueError, match="LinearSparse cannot be served in "
                       "int8.*slot-batched family names its weights itself"):
        ServeEngine(model, params, max_slots=2, max_len=64, quantize_int8=True)


def test_the_engine_refuses_a_prefix_cache_with_a_reason_true_of_the_family():
    model, params = build()
    engine = ServeEngine(model, params, max_slots=2, max_len=64)
    with pytest.raises(ValueError, match="LinearSparse cannot take a prefix "
                       "cache.*recurrence.*stored at alone"):
        engine.set_prefix_cache(object())
    sched = Scheduler(engine)
    ok, why = sched.submit(Request(id="e", prime=ids(4, 0), length=8, kind="embed"))
    assert not ok and "embeddings" in why
    ok, why = sched.submit(Request(id="v", prime=np.asarray([1, 512]), length=8))
    assert not ok and "[0, 512)" in why


def test_cli_train_refuses_the_family_by_name(tmp_path):
    (tmp_path / "configs" / "model").mkdir(parents=True)
    (tmp_path / "configs" / "model" / "ls.toml").write_text(
        (REPO / "configs/model/linear-sparse-small.toml").read_text())
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{REPO}{os.pathsep}" + os.environ.get("PYTHONPATH", "")}
    p = subprocess.run(
        [sys.executable, "-m", "progen_tpu.cli.train", "--model_name", "ls",
         "--config_path", str(tmp_path / "configs" / "model"),
         "--checkpoint_path", str(tmp_path / "ck"), "--wandb_off"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=600,
    )
    assert p.returncode != 0
    assert "trains the progen family only: 'linear_sparse'" in p.stderr


# ----- cli.serve over stdin with token ids ----------------------------------


def test_cli_serve_takes_and_answers_token_ids(tmp_path):
    from progen_tpu.checkpoint import Package, get_checkpoint_fns

    model, params = build()
    _, _, save = get_checkpoint_fns(str(tmp_path / "ck"))
    save(Package(0, {"params": params}, model.config.to_dict(), "linear-sparse"))
    prompt = [int(t) for t in ids(40, 3)]
    lines = [json.dumps({"id": f"r{i}", "tokens": prompt[i:], "length": 60,
                         "seed": i}) for i in range(3)]
    lines.append(json.dumps({"id": "bytes", "prime": "MKV", "length": 20}))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "PYTHONPATH": f"{REPO}{os.pathsep}" + os.environ.get("PYTHONPATH", "")}
    p = subprocess.run(
        [sys.executable, "-m", "progen_tpu.cli.serve", "--checkpoint_path",
         str(tmp_path / "ck"), "--max-slots", "2", "--max-len", "64",
         "--prefill_chunk", "32", "--journal_dir", str(tmp_path / "j")],
        input="\n".join(lines) + "\n", capture_output=True, text=True,
        env=env, cwd=tmp_path, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-2000:]
    events = [json.loads(line) for line in p.stdout.splitlines()]
    done = {e["id"]: e for e in events if e["event"] == "done"}
    assert set(done) == {"r0", "r1", "r2"}
    for e in done.values():  # ids out, no text: the family has no byte codec
        assert "text" not in e and 1 <= e["n_generated"] == len(e["tokens"])
        assert all(0 <= t < 512 for t in e["tokens"])
    rejected = [e for e in events if e["event"] == "rejected"]
    assert [e["id"] for e in rejected] == ["bytes"] and "tokens" in rejected[0]["reason"]
    # the same request straight through the engine gives the same ids
    engine = ServeEngine(model, params, max_slots=2, max_len=64)
    sched = Scheduler(engine, prefill_chunk=32)
    sched.submit(Request(id="r1", prime=np.asarray(prompt[1:]), length=60,
                         add_bos=True, seed=1))
    _, comps = sched.run_to_completion(max_steps=100)
    got = [int(t) for t in comps[0].tokens[40:40 + done["r1"]["n_generated"]]]
    assert got == done["r1"]["tokens"]


# ----- planted faults: each must fail the comparison --------------------------


def compare(model, params, seed=11):
    """The comparison the benchmark's check makes, at this size: prefill
    140 positions, decode 20 through the cache, the reference handed the
    blocks the system chose at the decoded positions. Returns the numbers
    it judges."""
    row = jnp.asarray(ids(N, seed))
    dec = decode_model(model, 200)
    cache = FEED(dec, params, fresh_cache(dec), row[None], 0, 140)
    state = np.asarray(cache["mix1"]["state"][0], np.float32)
    logits, chosen = [], {"mix0": [], "mix3": []}
    for i in range(140, N):
        (out, _), mut = ONE_ROW(dec, params, cache, row[None, i:i + 1],
                                jnp.full((1, 1), i), BOTH)
        cache = mut["cache"]
        logits.append(np.asarray(out[0, 0]))
        for name in chosen:
            chosen[name].append(np.asarray(mut["intermediates"][name]["blocks"][0][0, :, 0]))
    own = np.full((140, 2, 6), -1, np.int32)
    want, ref = linear_sparse_ref.forward(
        params, row, model.config.to_dict(), return_aux=True, rows=64,
        state_at=140, logits_from=140,
        blocks=[np.concatenate([own, np.stack(chosen[n])]) for n in chosen],
    )
    err = np.stack(logits) - np.asarray(want)
    return {
        "rms": float(np.sqrt((err ** 2).mean()) / np.asarray(want).std()),
        "state": float(np.abs(state - np.asarray(ref["state"][0])).max()
                       / np.abs(ref["state"][0]).max()),
        "slack": max(float(np.asarray(s).max()) for s in ref["slack"]),
        "forced_missing": sum(int(np.asarray(m).sum()) for m in ref["forced_missing"]),
    }


@pytest.fixture
def fresh_programs():
    """A patched function changes no jit key: compile anew, and leave no
    faulty program behind for the tests after this one."""
    jax.clear_caches()
    yield
    jax.clear_caches()


SOUND = {"rms": 1e-4, "state": 1e-4, "slack": 1e-5, "forced_missing": 0}


def test_the_sound_model_passes_the_comparison():
    got = compare(*build())
    assert all(got[k] <= SOUND[k] for k in SOUND), got


@pytest.mark.parametrize("fault,seen_by", [
    ("bfloat16_state", "state"), ("decay_of_the_next_layer", "rms"),
    ("no_forced_blocks", "forced_missing"),
])
def test_the_comparison_sees_a_planted_fault(fault, seen_by, monkeypatch,
                                             fresh_programs):
    if fault == "bfloat16_state":
        monkeypatch.setattr(ls, "STATE_DTYPE", jnp.bfloat16)
    elif fault == "decay_of_the_next_layer":
        sound = ls.decay_slopes
        monkeypatch.setattr(ls, "decay_slopes",
                            lambda config, layer: sound(config, layer + 1))
    else:
        def unforced(t, n_blocks, config):
            blk = jnp.arange(n_blocks)
            seen = blk <= (t // config.sparse_block_size)[..., None]
            return jnp.zeros_like(seen), seen

        monkeypatch.setattr(ls, "forced_blocks", unforced)
    got = compare(*build())
    assert got[seen_by] > 5 * SOUND[seen_by], got  # far over the limit
