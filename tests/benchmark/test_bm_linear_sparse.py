"""The linear-attention, block-sparse cell rehearsed on the CPU at a tiny
size through the harness's own ``execute``; its entries in the manifest,
guarded by presence; its count functions against hand arithmetic at the
cell's shapes and against the model's own tree; the keys its driver
compares; and the check seeing three planted faults. Nothing here is a
measurement."""

import json
from pathlib import Path
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark import run as bm
from benchmark.counts import linear_sparse as counts
from progen_tpu.models import build_model

ROOT = Path(__file__).resolve().parents[2]
CELL = "minicpm-sala.gen-longdoc"
CONFIG = json.loads((ROOT / "benchmark/configs/minicpm-sala.json").read_text())
TINY = dict(
    CONFIG, vocab_size=512, hidden_size=64, intermediate_size=128,
    num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, lightning_nh=4, lightning_nkv=4, lightning_head_dim=16,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    max_position_embeddings=1024, feed_rows=32,
    sparse_config=dict(kernel_size=4, kernel_stride=2, init_blocks=1,
                       block_size=8, window_size=16, topk=6, use_nope=False,
                       dense_len=32),
)
GEN_TINY = dict(max_slots=4, max_len=256, clients=4, max_queue=8,
                prefill_chunk=32, prompt_lengths=[128, 160, 192],
                output_lengths=[6, 9, 12], ramp_completions=2,
                check_positions=8, trace_seconds=0.4)


def rehearse(tmp_path, trace):
    manifest_, cell, _, traffic = bm.load_cell(CELL)
    traffic.update(GEN_TINY)
    line = bm.execute(manifest_, cell, TINY, traffic, seed=2**31 + 33,
                      seconds=1.0, trace=trace, devices=jax.devices()[:1],
                      out_dir=tmp_path / "out")
    detail = json.loads((tmp_path / "out" / "detail.json").read_text())
    return line, detail


def test_what_this_cell_added_is_still_there():
    """This cell's own entries, guarded by presence: the configuration,
    the cell with ISSUE 33's traffic, its two metrics, and its name on
    every list of the served path (``bm_floor.served_lists`` holds the
    general rule). What else the manifest holds is for ``bm_floor`` and
    the cells that come later."""
    import bm_floor

    assert manifest.check(ROOT) == [] and bm_floor.faults(ROOT) == []
    man = bm_floor.load(ROOT)
    cell = next(w for w in man["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "minicpm-sala", "gen-longdoc", 1)
    entry = next(c for c in man["configs"] if c["name"] == "minicpm-sala")
    assert entry["reduced"] == ["num_hidden_layers", "mixer_types"]
    assert entry["source"] == CONFIG["source"]
    by = {m["name"]: m for m in man["end_to_end"] + man["per_layer"]}
    for name, moves in (("sparse.rows_read_share", "itl_p50_s"),
                        ("sched.block_step_share", "itl_p50_s")):
        assert by[name]["workloads"] == [CELL] and by[name]["moves"] == moves
    assert by["sparse.rows_read_share"]["layer"] == "model"
    assert by["sched.block_step_share"]["layer"] == "scheduler"
    for name in ("serve_tok_s_chip", "itl_p50_s", "itl_p95_s", "serve.mfu",
                 "engine.decode_roofline", "engine.prefill_roofline"):
        assert CELL in by[name]["workloads"]
    shared = [m for m in man["per_layer"]
              if "large.gen-closed" in m.get("workloads", [])]
    assert len(shared) >= 20 and all(CELL in m["workloads"] for m in shared)
    traffic = json.loads((ROOT / "benchmark/traffic/gen-longdoc.json").read_text())
    want = dict(driver="gen_linear_sparse", max_slots=16, clients=16,
                max_len=32768, max_queue=32, prefill_chunk=512, journal=True,
                top_k=25, temperature=1.0, ramp_completions=4,
                check_positions=32, trace_seconds=3, size_order_seed=0,
                prompt_lengths=[10240, 14336, 18432, 22528],
                output_lengths=[512, 1024, 1536, 2048])
    assert {k: traffic[k] for k in want} == want


def test_the_configuration_keeps_every_published_key_but_the_cut():
    row = None
    catalog = Path("/opt/skills/guides/model-configs/architectures.jsonl")
    if catalog.exists():
        row = next(r for r in map(json.loads, catalog.read_text().splitlines())
                   if r["name"] == "MiniCPM-SALA")
        for key, value in row["config"].items():
            if key not in CONFIG["reduced"]:
                assert CONFIG[key] == value, key
        assert CONFIG["published"]["mixer_types"] == row["config"]["mixer_types"]
        assert CONFIG["source"] == row["source_url"]
    published = CONFIG["published"]["mixer_types"]
    assert CONFIG["mixer_types"] == published[9:21] and CONFIG["first_layer"] == 9
    assert CONFIG["num_hidden_layers"] == 12 and CONFIG["total_layers"] == 32 == len(published)
    # the published 8 : 24, three whole periods
    assert CONFIG["mixer_types"].count("minicpm4") == 3
    assert published.count("minicpm4") == 8
    for group in ("published", "assumed", "deployment", "cut"):
        assert CONFIG[group]
    model = build_model(CONFIG)  # every key the family refuses is checked here
    c = model.config
    assert (c.hidden_size, c.intermediate_size, c.vocab_size, c.head_dim,
            c.num_attention_heads, c.num_key_value_heads, c.lightning_nh,
            c.sparse_topk, c.sparse_dense_len, c.feed_rows) == (
        4096, 16384, 73448, 128, 32, 2, 32, 64, 8192, 512)
    # what the device holds: the count function against the real tree
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    )["params"]
    assert counts.num_params(CONFIG) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
    ) == 3_929_972_864


def test_the_counts_against_hand_arithmetic_at_the_cells_shapes():
    from benchmark.readers import served_yardsticks as y

    p = counts.layer_params(CONFIG)
    # ISSUE 33's own arithmetic, by part
    assert p["lightning-attn"] == 5 * 4096 * 4096 == 83_886_080
    assert p["minicpm4"] == 3 * 4096 * 4096 + 2 * 4096 * 256 == 52_428_800
    assert p["mlp"] == 3 * 4096 * 16384 == 201_326_592
    head = 73448 * 4096
    active = 9 * p["lightning-attn"] + 3 * p["minicpm4"] + 12 * p["mlp"]
    assert counts.active_params(CONFIG, head=False) == active == 3_328_180_224
    assert counts.active_params(CONFIG, head=True) == active + head
    assert counts.state_bytes(CONFIG) == 9 * 32 * 128 * 128 * 4 == 18_874_368
    assert counts.row_bytes(CONFIG) == 2 * 128 * 2
    # one decode step, 16 slots at 17,000 rows of context, 64 blocks read
    k = {"sparse_layer_steps": 3, "decode_steps": 1, "decode_tokens": 16,
         "sparse_rows_visible": 3 * 16 * 17000, "sparse_rows_attended": 3 * 16 * 4096,
         "prefill_tokens": 512, "sparse_feed_layer_blocks": 3,
         "sparse_feed_rows_visible": 3 * 512 * 17000,
         "sparse_feed_rows_attended": 3 * 512 * 4096,
         "window_s": 0.05, "chips": 1}
    flops, nbytes = counts.decode_need(CONFIG, k)
    weights = 2 * (active + head)
    assert nbytes == pytest.approx(
        weights + 16 * 4096 * 2 + 16 * 2 * 18_874_368
        + 3 * 16 * 17000 / 16 * 512 + 3 * 16 * 4096 * 2 * 512)
    assert 7.2e9 < weights < 7.3e9 and 8.0e9 < nbytes < 8.2e9  # ISSUE 33: 7.26 + 0.84
    mixers = 16 * 9 * 32 * 4 * 128 * 128 + 32 * 4 * 128 * 3 * 16 * 4096
    assert flops == pytest.approx(16 * 2 * (active + head) + mixers)
    flops, nbytes = counts.prefill_need(CONFIG, k)
    assert flops == pytest.approx(
        512 * (2 * active + 9 * 32 * 4 * 128 * 128) + 32 * 4 * 128 * 3 * 512 * 4096)
    assert 3.4e12 < flops < 3.6e12  # ISSUE 33: 512 x 6.66 GFLOP
    assert nbytes == pytest.approx(
        2 * active + 512 * 4096 * 2 + 2 * 18_874_368
        + 3 * 17000 / 16 * 512 + 3 * 4096 * 2 * 512)
    assert counts.window_flops(CONFIG, k) == pytest.approx(
        16 * 2 * (active + head) + mixers + flops)

    class Run:
        config, trace, notes = CONFIG, None, {}
        peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
        counters = k

    assert y.counts_for(CONFIG).__name__ == "benchmark.counts.linear_sparse"
    assert 0 < y.read(Run, {"what": "mfu"}) < 100
    assert y.read(Run, {"what": "decode", "match": "^jit__decode_step"}) is None
    Run.counters = {}
    assert y.read(Run, {"what": "mfu"}) is None  # a program without counters
    assert counts.prefill_need(CONFIG, {"sparse_layer_steps": 3}) is None


def test_gen_driver_counts_every_token_and_compares_what_it_says(tmp_path):
    line, detail = rehearse(tmp_path, 0)
    c = detail["counters"]
    assert line["correct"] and line["failed"] == 0 and line["attempted"] > 4
    assert set(line["metrics"]) == {"serve_tok_s_chip", "itl_p50_s",
                                    "itl_p95_s", "setup_s"}
    assert list(line["compared"]) == [
        "logits_rms_over_std", "logits_max_over_std", "selection_slack",
        "exchanged_share", "forced_blocks_missing", "state_err_over_norm",
        "served_tokens_redrawn_at_least", "wrong_output_lengths",
        "compiles_in_window"]
    assert all(len(pair) == 2 for pair in line["compared"].values())
    assert c["tokens"] == c["decode_tokens"] > 0
    assert c["engine_compiles_in_window"] == 0 and c["xla_compiles_in_window"] == 0
    assert c["requests_completed"] > 0
    # two sparse layers a step, six blocks a live slot and layer
    assert c["sparse_layer_steps"] == 2 * c["decode_steps"]
    assert c["sparse_blocks_selected"] == 6 * 2 * c["decode_tokens"]
    assert 0 < c["sparse_rows_attended"] < c["sparse_rows_visible"]
    # a prompt's blocks are reported with the decode step after its
    # admission: the window's edges cut at most a pool of prompts off
    assert abs(c["sparse_feed_layer_blocks"] - 2 * c["prefill_blocks"]) <= 2 * 4 * 6
    assert detail["check"]["positions"] == 8 and detail["check"]["ok"]
    n = detail["notes"]
    assert n["kv_cache_bytes"] == 4 * 2 * 2 * 2 * 256 * 16 * 2
    assert n["linear_state_bytes"] == 4 * 2 * 4 * 16 * 16 * 4


def test_the_seed_draws_the_ids_and_not_the_order_of_the_sizes():
    from benchmark.drivers.gen_linear_sparse import gen_requests

    traffic = json.loads((ROOT / "benchmark/traffic/gen-longdoc.json").read_text())
    a, b = gen_requests(traffic, 73448, 1), gen_requests(traffic, 73448, 2**31 + 5)
    first = [(next(a), next(b)) for _ in range(32)]
    assert all(len(x[0]) == len(y[0]) and x[1] == y[1] for x, y in first)
    assert any((x[0] != y[0]).any() for x, y in first)
    for cycle in (first[:16], first[16:]):  # every pair of the grid once
        assert sorted((len(x[0]), x[1]) for x, _ in cycle) == sorted(
            (p, o) for p in traffic["prompt_lengths"] for o in traffic["output_lengths"])
    assert all(1 <= x[0].min() and x[0].max() < 73448 for x, _ in first)


def test_gen_driver_traced_reports_the_per_layer_metrics_it_can(tmp_path):
    line, detail = rehearse(tmp_path, 1)
    got = set(line["metrics"])
    assert {"sched.occupancy", "sched.ttft_p50_s", "sched.host_ms_per_step",
            "engine.compiles_in_window", "sparse.rows_read_share",
            "sched.block_step_share", "sched.emit_ms_per_step"} <= got
    # no device in the trace and no peak on a CPU: nothing to read there
    assert not {"engine.decode_roofline", "engine.prefill_roofline",
                "serve.mfu", "engine.decode_device_ms"} & got
    assert 0 < line["metrics"]["sparse.rows_read_share"]["value"] < 60
    assert line["metrics"]["sched.block_step_share"]["value"] > 0
    assert detail["span_count"]["sched.step"] == detail["span_count"]["engine.decode_step"]


@pytest.fixture
def fresh_programs():
    """A patched function changes no jit key: compile anew, and leave no
    faulty program behind for the tests after this one."""
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("fault,seen_by", [
    ("reference_in_an_8_bit_float", "rms_err_over_std"),
    ("decay_of_the_next_layer", "state_err_over_norm"),
    ("no_forced_blocks", "forced_blocks_missing"),
])
def test_the_check_sees_a_planted_fault(fault, seen_by, tmp_path, monkeypatch,
                                        fresh_programs):
    """Two faults planted in the program and the cell's lower-precision
    control (the reference with every matrix rounded to a float of 5
    exponent and 2 mantissa bits, the nearest below bfloat16): each fails
    a limit. A state kept in bfloat16 is NOT among them: the check cannot
    tell it from sound (PERF.md section 6, PR 33), so
    tests/test_linear_sparse.py holds it at float32 compute."""
    from benchmark.drivers import gen_linear_sparse as drv
    from progen_tpu.models import linear_sparse as ls

    _, _, _, traffic = bm.load_cell(CELL)
    traffic.update(GEN_TINY, journal=False)
    run = SimpleNamespace(traffic=traffic, config=TINY, seed=33,
                          devices=jax.devices()[:1], tmp=tmp_path)
    reference = None
    if fault == "reference_in_an_8_bit_float":
        reference = {"weight_bits": (5, 2)}
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
    _, engine, _ = drv.build(run)
    check = drv.check_against_reference(run, engine, reference)
    limit = dict(drv.LIMITS)[seen_by]
    assert not check["ok"] and check[seen_by] > limit, check
