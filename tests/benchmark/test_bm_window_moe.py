"""The sliding-window / full-attention, held-expert cell rehearsed on the
CPU at a tiny size through the harness's own ``execute``; its entries in
the manifest, guarded by presence; its configuration against its published
values and the model's own tree; its count functions on hand-worked
positions; the reader of its two attention metrics; and the check seeing planted
faults. Nothing here is a measurement."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark import run as bm
from benchmark.counts import window_moe as counts
from progen_tpu.models import build_model

ROOT = Path(__file__).resolve().parents[2]
CELL = "trinity-large-preview.gen-window-mix"
CONFIG = json.loads(
    (ROOT / "benchmark/configs/trinity-large-preview.json").read_text())
TINY = dict(
    CONFIG, vocab_size=512, hidden_size=64, intermediate_size=128,
    moe_intermediate_size=32, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, sliding_window=32, feed_rows=8, num_experts=4,
    router_experts=16, max_position_embeddings=1024,
)
GEN_TINY = dict(max_slots=4, max_len=128, clients=4, max_queue=8,
                prefill_chunk=8, prompt_lengths=[16, 24, 48, 64],
                output_lengths=[6, 9, 12], ramp_completions=2,
                check_prompt=48, check_positions=8, trace_seconds=0.4)


def rehearse(tmp_path, trace, monkeypatch):
    from benchmark.drivers import gen_window_moe as drv

    # At 16 a head and 56 keys the first full layer's output under the
    # cell's post-attention scale is a sum that nearly cancels, and its
    # error over its norm passes the 5% that holds at the published widths
    # (0.9% at 640 and at 2,048 keys on the CPU): the mechanics rehearsed
    # here run at unit scale.
    monkeypatch.setattr(drv, "POST_ATTN_NORM_SCALE", 1.0)
    manifest_, cell, _, traffic = bm.load_cell(CELL)
    traffic.update(GEN_TINY)
    line = bm.execute(manifest_, cell, TINY, traffic, seed=2**31 + 40,
                      seconds=1.0, trace=trace, devices=jax.devices()[:1],
                      out_dir=tmp_path / "out")
    detail = json.loads((tmp_path / "out" / "detail.json").read_text())
    return line, detail


def test_what_this_cell_added_is_still_there():
    """This cell's own entries, guarded by presence: the configuration,
    the cell with its traffic, its three metrics, and its name on
    every list of the served path (``bm_floor.served_lists`` holds the
    general rule) and on ``moe.experts_touched``."""
    import bm_floor

    assert manifest.check(ROOT) == [] and bm_floor.faults(ROOT) == []
    man = bm_floor.load(ROOT)
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-preview", "gen-window-mix", 1)
    entry = next(c for c in man["configs"] if c["name"] == "trinity-large-preview")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types",
                                "num_dense_layers", "num_experts", "vocab_size"]
    assert entry["source"] == CONFIG["source"]
    by = {m["name"]: m for m in man["end_to_end"] + man["per_layer"]}
    for name, layer, moves in (
            ("engine.decode_window_attend_ms", "engine", "itl_p50_s"),
            ("engine.decode_full_attend_ms", "engine", "itl_p50_s"),
            ("moe.held_share", "model", "serve_tok_s_chip")):
        assert by[name]["workloads"] == [CELL]
        assert (by[name]["layer"], by[name]["moves"]) == (layer, moves)
    for name in ("serve_tok_s_chip", "itl_p50_s", "itl_p95_s", "serve.mfu",
                 "engine.decode_roofline", "engine.prefill_roofline",
                 "moe.experts_touched"):
        assert by[name]["workloads"][-1] == CELL
    assert CELL not in by["moe.load_max_over_mean"]["workloads"]
    shared = [m for m in man["per_layer"]
              if "large.gen-closed" in m.get("workloads", [])]
    assert len(shared) >= 20 and all(CELL in m["workloads"] for m in shared)
    traffic = json.loads((ROOT / "benchmark/traffic/gen-window-mix.json").read_text())
    want = dict(driver="gen_window_moe", max_slots=32, clients=32,
                max_len=11264, max_queue=64, prefill_chunk=512, journal=True,
                top_k=25, temperature=1.0, ramp_completions=8,
                check_prompt=7168, check_positions=32, trace_seconds=3,
                size_order_seed=0,
                prompt_lengths=[1024, 2048, 3072, 5120, 7168, 9216],
                output_lengths=[512, 768, 1024, 1536])
    assert {k: traffic[k] for k in want} == want


def test_the_configuration_keeps_every_published_key_but_the_cut():
    published = CONFIG["published"]
    for key in CONFIG["reduced"]:
        assert published[key] != CONFIG[key], key
    # published layers 0, 6, 7, 8, 9: the dense layer and one whole period
    assert CONFIG["layer_types"] == [published["layer_types"][i]
                                     for i in (0, 6, 7, 8, 9)]
    assert (CONFIG["num_hidden_layers"], CONFIG["num_dense_layers"],
            CONFIG["num_experts"], CONFIG["router_experts"],
            CONFIG["first_expert"], CONFIG["vocab_size"]) == (
        5, 1, 32, 256, 0, 25024)
    assert published["num_experts"] == 256 and published["vocab_size"] == 200192
    for group in ("published", "assumed", "deployment", "cut", "share"):
        assert CONFIG[group]
    model = build_model(CONFIG)  # every key the family refuses is checked here
    c = model.config
    assert (c.hidden_size, c.num_attention_heads, c.num_key_value_heads,
            c.head_dim, c.sliding_window, c.n_router, c.num_experts_per_tok,
            c.moe_intermediate_size, c.intermediate_size, c.ring_rows) == (
        3072, 48, 8, 128, 4096, 256, 4, 3072, 12288, 4608)
    # what the device holds: the count function against the real tree, and
    # the cut's reckoning of 4,321.9M parameters, 8.64 GB in bfloat16
    # (matrices 4,321,837,056, norm scales 65,792, the routers' biases 1,024)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    leaves = jax.tree.leaves(shapes)
    assert counts.num_params(CONFIG) == sum(
        int(np.prod(a.shape)) for a in leaves) == 4_321_903_872
    assert counts.weight_bytes(CONFIG) == sum(
        int(np.prod(a.shape)) * a.dtype.itemsize for a in leaves)
    assert 8.64e9 < counts.weight_bytes(CONFIG) < 8.65e9


def test_the_counts_on_hand_worked_positions():
    """One decode step at 32 live slots, half of them at position 1,000
    (below the window: 1,001 rows in every layer) and half at 8,000 (4,096
    rows in a sliding layer, 8,001 in the full one), 12.5 held experts
    touched a layer and 16 held assignments a layer."""
    from benchmark.readers import served_yardsticks as y

    assert counts.attention_params(CONFIG) == 62_914_560
    assert counts.expert_params(CONFIG) == 28_311_552
    shared = counts.shared_params(CONFIG)
    assert shared == 5 * 62_914_560 + 113_246_208 + 4 * (786_432 + 28_311_552)
    assert counts.row_bytes(CONFIG) == 4096
    window_rows = 16 * 1001 + 16 * 4096
    context = 16 * 1001 + 16 * 8001
    k = {"moe_expert_layer_steps": 4, "decode_steps": 1, "decode_tokens": 32,
         "moe_experts_touched": 4 * 12.5, "moe_held_assignments": 4 * 16,
         "decode_window_rows_sum": window_rows, "decode_context_sum": context,
         "prefill_tokens": 512, "moe_feed_expert_layer_blocks": 4,
         "moe_feed_experts_touched": 4 * 32, "moe_feed_held_assignments": 4 * 256,
         "prefill_context_tokens": 1024, "prefill_context_sum": 1024 * 1025 // 2,
         "prefill_window_context_sum": 1024 * 1025 // 2,
         "window_s": 0.05, "chips": 1}
    flops, nbytes = counts.decode_need(CONFIG, k)
    head = 3072 * 25024
    weights = 2 * (shared + head) + 32 * 3072 * 2 + 50 * 2 * 28_311_552
    rows = (4 * window_rows + context) * 4096
    assert nbytes == pytest.approx(weights + rows)
    assert 3.9e9 < weights < 4.3e9  # the cut's reckoning: 4.1 GB a step
    attention = 48 * 4 * 128 * (4 * window_rows + context)
    assert flops == pytest.approx(
        32 * 2 * (shared + head) + 64 * 2 * 28_311_552 + attention)
    # a block of 512 positions 0..511 of a prompt: every held expert
    flops, nbytes = counts.prefill_need(CONFIG, k)
    seen = 1025 / 2  # mean rows a position sees
    assert flops == pytest.approx(
        512 * 2 * shared + 4 * 256 * 2 * 28_311_552 + 48 * 4 * 128 * 5 * 512 * seen)
    assert nbytes == pytest.approx(
        2 * shared + 512 * 3072 * 2 + 4 * 32 * 2 * 28_311_552 + 5 * seen * 4096)
    assert counts.window_flops(CONFIG, k) == pytest.approx(
        32 * 2 * (shared + head) + 64 * 2 * 28_311_552 + attention + flops)
    from benchmark.drivers.gen_window_moe import window_rows as wr
    assert wr(1000, 4096) == 1000 * 1001 // 2
    assert wr(5000, 4096) == 4096 * 4097 // 2 + 904 * 4096

    class Run:
        config, trace, notes = CONFIG, None, {}
        peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        counters = k

    assert y.counts_for(CONFIG).__name__ == "benchmark.counts.window_moe"
    assert 0 < y.read(Run, {"what": "mfu"}) < 100
    Run.counters = {}
    assert y.read(Run, {"what": "mfu"}) is None  # a program without counters
    assert counts.prefill_need(CONFIG, {"moe_expert_layer_steps": 4}) is None


def test_the_subscope_reader_files_an_op_under_its_detail():
    from benchmark.readers import device_scopes
    from benchmark.readers.device_subscopes import detail

    classes = ("project", "attend", "cache_write", "ffn")
    assert detail("jit(f)/project/attn0/attend/window/dot_general", classes) == "attend/window"
    assert detail("jit(f)/project/attn2/attend/full/while/body/exp", classes) == "attend/full"
    assert detail("jit(f)/project/attn0/attend/dot_general", classes) == ""
    assert detail("jit(f)/attend/window/cache_write/dynamic_update_slice",
                  classes) == ""
    # the class reader files the same ops as before
    assert device_scopes.classify(
        "jit(f)/project/attn0/attend/window/dot_general", classes) == "attend"


def test_gen_driver_counts_every_token_and_compares_what_it_says(tmp_path, monkeypatch):
    line, detail = rehearse(tmp_path, 0, monkeypatch)
    c = detail["counters"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"serve_tok_s_chip", "itl_p50_s",
                                    "itl_p95_s", "setup_s"}
    assert list(line["compared"]) == [
        "logits_rms_over_std", "logits_max_over_std",
        "window_attn_err_over_norm", "full_attn_err_over_norm",
        "routing_slack", "exchanged_share", "served_tokens_redrawn_at_least",
        "wrong_output_lengths", "compiles_in_window"]
    assert all(len(pair) == 2 for pair in line["compared"].values())
    assert c["tokens"] == c["decode_tokens"] > 0
    assert c["engine_compiles_in_window"] == 0 and c["xla_compiles_in_window"] == 0
    assert c["requests_completed"] > 0
    # four expert layers a step, four assignments a live slot and layer
    assert c["moe_expert_layer_steps"] == 4 * c["decode_steps"]
    assert c["moe_assignments"] == 4 * 4 * c["decode_tokens"]
    assert 0 < c["moe_held_assignments"] < c["moe_assignments"]
    assert c["decode_window_rows_sum"] <= c["decode_context_sum"]
    assert detail["check"]["positions"] == 8 and detail["check"]["ok"]
    n = detail["notes"]
    assert n["window_cache_bytes"] == 4 * 4 * 2 * 2 * 40 * 16 * 2
    assert n["kv_cache_bytes"] == 4 * 1 * 2 * 2 * 128 * 16 * 2


def test_gen_driver_traced_reports_the_per_layer_metrics_it_can(tmp_path, monkeypatch):
    line, detail = rehearse(tmp_path, 1, monkeypatch)
    got = set(line["metrics"])
    assert {"sched.occupancy", "sched.ttft_p50_s", "sched.host_ms_per_step",
            "engine.compiles_in_window", "moe.held_share",
            "moe.experts_touched", "sched.emit_ms_per_step"} <= got
    # no device in the trace and no peak on a CPU: nothing to read there
    assert not {"engine.decode_roofline", "engine.prefill_roofline",
                "serve.mfu", "engine.decode_device_ms",
                "engine.decode_window_attend_ms",
                "engine.decode_full_attend_ms"} & got
    assert 0 < line["metrics"]["moe.held_share"]["value"] < 100
    # a step with no slot live yet only feeds a prompt
    assert 0 < detail["span_count"]["engine.decode_step"] <= detail["span_count"]["sched.step"]


@pytest.fixture
def fresh_programs():
    """A patched function changes no jit key: compile anew, and leave no
    faulty program behind for the tests after this one."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault,seen_by", [
    ("window_ignored", "window_attn_err_over_norm"),
    ("rope_on_full_layers", "full_attn_err_over_norm"),
    ("reference_in_an_8_bit_float", "rms_err_over_std"),
])
def test_the_check_sees_a_planted_fault(fault, seen_by, tmp_path, monkeypatch,
                                        fresh_programs):
    """Two faults planted in the program and the cell's lower-precision
    control (the reference with every matrix rounded to a float of 5
    exponent and 2 mantissa bits, the nearest below bfloat16): each fails
    a limit."""
    from benchmark.drivers import gen_window_moe as drv
    from progen_tpu.models import window_moe as wm

    _, _, _, traffic = bm.load_cell(CELL)
    traffic.update(GEN_TINY, journal=False)
    run = SimpleNamespace(traffic=traffic, config=TINY, seed=40,
                          devices=jax.devices()[:1], tmp=tmp_path)
    reference = None
    if fault == "window_ignored":
        sound = wm.visible
        monkeypatch.setattr(wm, "visible",
                            lambda row_pos, t, window: sound(row_pos, t, None))
    elif fault == "rope_on_full_layers":
        monkeypatch.setattr(wm, "ROPE_KINDS", (wm.SLIDING, wm.FULL))
    else:
        reference = {"weight_bits": (5, 2)}
    _, engine, _ = drv.build(run)
    check = drv.check_against_reference(run, engine, reference)
    limit = dict(drv.LIMITS)[seen_by]
    assert not check["ok"] and check[seen_by] > limit, check
