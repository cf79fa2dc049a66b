"""The latent-attention, routed-expert cell rehearsed on the CPU at a tiny
size through the harness's own ``execute``; its count functions against
the model's own parameter tree; its reference against the model. Nothing
here is a measurement."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops_latent_moe as lm
from benchmark import manifest
from benchmark import run as bm
from benchmark.reference import latent_moe_ref
from progen_tpu.models import build_model

ROOT = Path(__file__).resolve().parents[2]
CELL = "kanana2-30b-a3b.gen-chat"
TINY = dict(family="latent_moe", vocab_size=512, hidden_size=64,
            intermediate_size=128, moe_intermediate_size=32,
            num_hidden_layers=3, num_attention_heads=4, kv_lora_rank=32,
            qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
            n_routed_experts=8, n_shared_experts=2, num_experts_per_tok=2,
            first_k_dense_replace=1, norm_topk_prob=True,
            routed_scaling_factor=2.448, rms_norm_eps=1e-6,
            rope_theta=1000000, rope_interleave=True,
            max_position_embeddings=1024, dtype="bfloat16",
            param_dtype="bfloat16")
GEN_TINY = dict(max_slots=4, max_len=256, clients=4,
                prompt_lengths=[128, 130, 140], output_lengths=[6, 9, 12],
                ramp_completions=2, check_positions=8, trace_seconds=0.4)


def rehearse(tmp_path, trace):
    manifest_, cell, _, traffic = bm.load_cell(CELL)
    traffic.update(GEN_TINY)
    line = bm.execute(manifest_, cell, TINY, traffic, seed=2**31 + 28,
                      seconds=1.0, trace=trace, devices=jax.devices()[:1],
                      out_dir=tmp_path / "out")
    detail = json.loads((tmp_path / "out" / "detail.json").read_text())
    return line, detail


def test_the_manifest_with_the_new_cell_is_consistent():
    assert manifest.check(ROOT) == []
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "gen-chat"
    entry = next(c for c in man["configs"] if c["name"] == cell["config"])
    assert entry["reduced"] == ["num_hidden_layers"]


def test_what_this_cell_added_is_still_there():
    """This cell's own entries, guarded by presence as every PR that adds
    a cell guards its own: the five metrics it brought list it, and it
    stands on every list of the served path (``bm_floor.served_lists``
    holds the general rule). What else the manifest holds, and how much,
    is for ``bm_floor`` and the cells that came later."""
    import bm_floor

    assert bm_floor.faults(ROOT) == []
    man = bm_floor.load(ROOT)
    by = {m["name"]: m for m in man["end_to_end"] + man["per_layer"]}
    for name in bm_floor.ADDED:
        assert CELL in by[name]["workloads"]
    for name in ("moe.experts_touched", "moe.load_max_over_mean"):
        assert by[name]["workloads"][0] == CELL  # the family's own
    for name in ("serve_tok_s_chip", "itl_p50_s", "itl_p95_s"):
        assert by[name]["workloads"][:2] == ["large.gen-closed", CELL]
    shared = [m for m in man["per_layer"]
              if "large.gen-closed" in m.get("workloads", [])]
    assert len(shared) >= 20 and all(CELL in m["workloads"] for m in shared)
    for name in ("env.compile_s", "env.compile_load_s", "env.cache_misses"):
        assert "workloads" not in by[name]  # every cell, this one too


def test_the_configuration_keeps_every_published_width():
    cfg = json.loads((ROOT / "benchmark/configs/kanana2-30b-a3b.json").read_text())
    assert cfg["num_hidden_layers"] == 8 and cfg["published"] == {"num_hidden_layers": 48}
    model = build_model(cfg)  # every key the family refuses is checked here
    c = model.config
    assert (c.hidden_size, c.n_routed_experts, c.num_experts_per_tok,
            c.vocab_size, c.kv_lora_rank) == (2048, 128, 6, 128256, 512)
    # what the device holds: the count function against the real tree
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    assert lm.num_params(cfg) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
    ) == 5_069_642_624


def test_gen_driver_counts_every_token_and_compiles_nothing_in_the_window(tmp_path):
    line, detail = rehearse(tmp_path, 0)
    c = detail["counters"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"serve_tok_s_chip", "itl_p50_s",
                                    "itl_p95_s", "setup_s"}
    assert c["tokens"] == c["decode_tokens"] > 0
    assert c["engine_compiles_in_window"] == 0 and c["xla_compiles_in_window"] == 0
    assert c["requests_completed"] > 0
    # two expert layers a step, two experts a live slot, none dropped
    assert c["moe_expert_layer_steps"] == 2 * c["decode_steps"]
    assert c["moe_assignments"] == 2 * 2 * c["decode_tokens"]
    assert 1 <= c["moe_experts_touched"] / c["moe_expert_layer_steps"] <= 8
    assert detail["check"]["positions"] == 8 and detail["check"]["ok"]
    assert detail["notes"]["latent_cache_bytes"] == 3 * 4 * 256 * (32 + 8) * 2 + 4 * 2 * 3 * 4


def test_gen_driver_traced_reports_the_per_layer_metrics_it_can(tmp_path):
    line, detail = rehearse(tmp_path, 1)
    got = set(line["metrics"])
    assert {"sched.occupancy", "sched.ttft_p50_s", "sched.host_ms_per_step",
            "engine.compiles_in_window", "moe.experts_touched",
            "moe.load_max_over_mean", "sched.emit_ms_per_step"} <= got
    # no device in the trace and no peak on a CPU: nothing to read there
    assert not {"engine.decode_roofline", "engine.prefill_roofline",
                "serve.mfu", "engine.decode_device_ms"} & got
    assert 1 <= line["metrics"]["moe.experts_touched"]["value"] <= 8
    assert line["metrics"]["moe.load_max_over_mean"]["value"] >= 1.0
    assert detail["span_count"]["sched.step"] == detail["span_count"]["engine.decode_step"]


def test_the_yardsticks_follow_the_counters():
    from benchmark.readers import served_yardsticks as y

    cfg = json.loads((ROOT / "benchmark/configs/kanana2-30b-a3b.json").read_text())
    # the issue's own arithmetic, by part
    p = lm.layer_params(cfg)
    assert (p["attention"], p["shared"], p["router"], p["expert"]) == (
        26_345_472, 9_437_184, 262_144, 4_718_592)
    assert lm.latent_row_bytes(cfg) == 8 * 1152
    nbytes = lm.pass_bytes(cfg, 100, 32 * 700, head=True)
    assert 7.9e9 < nbytes < 8.1e9  # ~8 GB a decode step, as reckoned

    class Run:
        config, trace, notes = cfg, None, {}
        peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        counters = {"moe_expert_layer_steps": 70, "decode_steps": 10,
                    "decode_tokens": 320, "decode_context_sum": 224000,
                    "prefill_tokens": 512, "prefill_context_sum": 131328,
                    "moe_experts_touched": 7000, "window_s": 0.25, "chips": 1}

    assert y.counts_for(cfg).__name__ == "benchmark.counts.latent_moe"
    mfu = y.read(Run, {"what": "mfu"})
    assert 0 < mfu < 100
    assert y.read(Run, {"what": "decode", "match": "^jit__decode_step"}) is None
    Run.counters = {}
    assert y.read(Run, {"what": "mfu"}) is None  # a program without counters


def test_the_two_copies_of_the_reference_are_one():
    assert (ROOT / "benchmark/reference/latent_moe_ref.py").read_bytes() == (
        ROOT / "tests/latent_moe_ref.py").read_bytes()


@pytest.mark.parametrize("n", [24, 40])
def test_the_reference_matches_the_model_in_float32(n):
    cfg = {**TINY, "dtype": "float32", "param_dtype": "float32"}
    model = build_model(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(n), (n,), 1, 512)
    params = model.init(jax.random.PRNGKey(1), tokens[None])["params"]
    params = jax.tree.map(
        lambda a: a + 0.05 * jax.random.normal(jax.random.PRNGKey(a.size), a.shape),
        params,
    )
    want = model.apply({"params": params}, tokens[None])[0]
    got = latent_moe_ref.forward(params, tokens, model.config.to_dict(),
                                 expert_group=3, vocab_slices=3)
    assert float(jnp.abs(got - want).max()) < 1e-4
    assert float(jnp.abs(want).max()) > 1.0  # the comparison is not of zeros


# ----- faults planted under the check: what each limit is there to see -----

def _next_best_for_kth(u, w_router, bias, c):
    """The k-th choice is the (k+1)-th best: the least wrong a choice can be."""
    s = jax.nn.sigmoid(u.astype(jnp.float32) @ w_router.astype(jnp.float32))
    _, idx = jax.lax.top_k(s + bias, c.num_experts_per_tok + 1)
    idx = jnp.concatenate([idx[:, :-2], idx[:, -1:]], axis=-1)
    w = jnp.take_along_axis(s, idx, axis=-1)
    return idx.astype(jnp.int32), (
        w / w.sum(-1, keepdims=True) * c.routed_scaling_factor)


@pytest.fixture
def fresh_programs():
    """A patched function changes no jit key: compile anew, and leave no
    faulty program behind for the tests after this one."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault", ["bias_ignored", "next_best_for_kth",
                                   "wrong_expert", "timed_sampler_ignores_top_k"])
def test_the_check_sees_a_planted_fault(fault, tmp_path, monkeypatch, fresh_programs):
    from types import SimpleNamespace

    from benchmark.drivers import gen_latent_moe as drv
    from progen_tpu.models import latent_moe
    from progen_tpu.serving import engine as engine_mod

    _, _, _, traffic = bm.load_cell(CELL)
    traffic.update(GEN_TINY, journal=False)
    run = SimpleNamespace(traffic=traffic, config=TINY, seed=28,
                          devices=jax.devices()[:1], tmp=tmp_path)
    plain, slots = latent_moe.route, engine_mod.gumbel_step_slots
    if fault == "next_best_for_kth":
        monkeypatch.setattr(latent_moe, "route", _next_best_for_kth)
    elif fault == "wrong_expert":
        def wrong(u, w_router, bias, c):
            idx, w = plain(u, w_router, bias, c)
            e = w_router.shape[-1]
            return idx.at[:, -1].set((idx[:, -1] + e // 2) % e), w
        monkeypatch.setattr(latent_moe, "route", wrong)
    elif fault == "timed_sampler_ignores_top_k":  # the timed program alone
        monkeypatch.setattr(
            engine_mod, "gumbel_step_slots",
            lambda keys, logits, top_k, *rest: slots(
                keys, logits, jnp.zeros_like(top_k), *rest))
    _, engine, _ = drv.build(run)
    reference_params = None
    if fault == "bias_ignored":
        # the checkpoint's bias is twenty times what the system routes by
        # (at this size the scores lie far apart: a bias of the cell's own
        # scale decides too few of 16 choices to tell)
        reference_params = jax.tree_util.tree_map_with_path(
            lambda p, a: 20 * a if "e_score_correction_bias"
            in jax.tree_util.keystr(p) else a, engine.params)
    check = drv.check_against_reference(run, engine, reference_params)
    assert not check["ok"]
    logits_pass = (check["rms_err_over_std"] <= drv.RMS_TOLERANCE
                   and check["max_err_over_std"] <= drv.MAX_TOLERANCE)
    routing_pass = (check["routing_slack"] <= drv.SLACK_TOLERANCE
                    and check["exchanged_share"] <= drv.EXCHANGED_TOLERANCE)
    redrawn_pass = (check["served_tokens_redrawn"]
                    >= drv.REDRAWN_AT_LEAST * check["positions"])
    if fault == "timed_sampler_ignores_top_k":
        # the logits are read from the check's own program and are right:
        # only the redraw of the timed program's tokens sees this one
        assert logits_pass and routing_pass and not redrawn_pass
    else:
        assert not routing_pass and redrawn_pass
        if fault != "wrong_expert":  # the arithmetic at the handed choices
            assert logits_pass       # is right: only the routing limits see it
