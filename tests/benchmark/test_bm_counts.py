"""The count modules behind the served yardsticks (benchmark/counts/) and
the reader that finds them by the configuration's family
(benchmark/readers/served_yardsticks.py): ProGen's counts against the
model's own tree and against brute force, the latent_moe counts against
what the reader they were moved out of gave, and nothing to read where a
family, a counter, a trace or a peak is missing. Nothing here is a
measurement."""

import json
import types
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import flops, xplane
from benchmark.counts import latent_moe, progen
from benchmark.readers import ratio, served_yardsticks as y

ROOT = Path(__file__).resolve().parents[2]
FIXTURE = ROOT / "benchmark" / "fixtures" / "trace_v5e_one_chip.json"
PEAK = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
SMALL = dict(num_tokens=256, dim=64, depth=3, heads=2, dim_head=32,
             window_size=8, seq_len=64, global_mlp_depth=1, ff_mult=4,
             dtype="bfloat16", param_dtype="float32")


def config(name):
    return json.loads((ROOT / "benchmark" / "configs" / f"{name}.json").read_text())


def fake_run(cfg, counters, trace=None, peak=PEAK):
    return types.SimpleNamespace(config=cfg, counters=counters, trace=trace,
                                 peak=peak, notes={})


# ----- ProGen ----------------------------------------------------------


def test_progen_weight_bytes_are_the_served_trees():
    """``large`` by shapes alone (nothing is allocated): the leaves the
    engine's own rule casts at 2 bytes, the kept ones at 4."""
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen
    from progen_tpu.serving.served_tree import promoted_mask

    cfg = config("large")
    model = ProGen(ProGenConfig.from_dict(cfg))
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.zeros((1, cfg["seq_len"]), jnp.int32))
    )["params"]
    from flax.core import meta

    leaves = jax.tree.leaves(meta.unbox(shapes))
    mask = promoted_mask(meta.unbox(shapes), jnp.bfloat16)
    sizes = [int(np.prod(a.shape)) for a in leaves]
    assert sum(sizes) == flops.num_params(cfg) == 1_223_815_168
    kept = sum(n for n, cast in zip(sizes, mask) if not cast)
    assert kept == progen.kept_params(cfg) == 2_194_176
    served = sum(n * (2 if cast else 4) for n, cast in zip(sizes, mask))
    # the engine's gauges served_weight_bytes and raw_weight_bytes (PR 29)
    assert served == progen.weight_bytes(cfg) == 2_452_018_688
    assert progen.weight_bytes({**cfg, "dtype": "float32"}) == 4 * sum(sizes) == 4_895_260_672
    assert progen.widths({**cfg, "param_dtype": "bfloat16"}) == (2, 2)


@pytest.mark.parametrize("w,n", [(8, 64), (16, 64), (4, 32)])
def test_progen_window_rows_are_the_visible_keys(w, n):
    """Brute force over the decode cache's own rule: a stored position is
    visible when it is not after the query and at most one window back."""
    c = {**SMALL, "window_size": w, "seq_len": n}
    for q in range(n):
        visible = sum(1 for j in range(q + 1) if q // w - j // w <= 1)
        assert progen.window_rows(c, q) == visible
    assert progen.window_rows(c, 0) == 1 and progen.window_rows(c, 2 * w - 1) == 2 * w
    assert max(progen.window_rows(c, q) for q in range(n)) == 2 * w


def test_progen_need_is_the_sum_of_its_parts():
    c = SMALL
    half, n = 128, 64
    params = flops.num_params(c)
    k = {"decode_steps": 10, "decode_tokens": 40, "decode_context_sum": 800,
         "decode_window_rows_sum": 480, "prefill_tokens": 60,
         "prefill_blocks": 5, "prefill_context_sum": 390,
         "prefill_window_rows_sum": 300}
    need_flops, need_bytes = progen.decode_need(c, k)
    # a step: 4 tokens, 48 K/V rows, 80 mixed gate rows between them
    assert need_flops == pytest.approx(
        4 * 2 * (params - n * n) + 3 * 2 * 4 * 32 * 48 + 2 * half * 80)
    kept = 64 * 7 + (half + n * n + n)
    weights = (params - kept) * 2 + kept * 4
    assert progen.weight_bytes(c) == weights
    assert need_bytes == pytest.approx(
        weights - n * n * 4 - 256 * 64 * 2   # no whole (n, n), no whole table
        + 4 * 64 * 2 + 80 * 4                # their rows
        + 48 * (3 * 2 * 2 * 32 * 2)          # K and V rows, bfloat16
        + 80 * half * 2)                     # gate history, bfloat16
    assert progen.kv_row_bytes(c) == 3 * 2 * 2 * 32 * 2
    # a chunk program: 12 positions a block, which see 5 rows on average
    p_flops, p_bytes = progen.prefill_need(c, k)
    assert p_flops == pytest.approx(
        (60 * 2 * (params - n * n) + 3 * 2 * 4 * 32 * 300 + 2 * half * 390) / 5)
    assert p_bytes == pytest.approx(
        weights - n * n * 4 - 256 * 64 * 2 + 12 * 64 * 2 + 6.5 * 4
        + 5 * (3 * 2 * 2 * 32 * 2) + 6.5 * half * 2)
    assert progen.window_flops(c, k) == pytest.approx(
        need_flops * 10 + p_flops * 5)


def test_progen_counts_nothing_without_the_drivers_counters():
    c = SMALL
    assert progen.decode_need(c, {}) is None
    assert progen.decode_need(c, {"decode_steps": 0, "decode_window_rows_sum": 0}) is None
    assert progen.prefill_need(c, {"prefill_blocks": 3}) is None
    assert progen.window_flops(c, {"decode_steps": 5, "decode_tokens": 9}) is None


def test_large_on_the_chips_own_numbers_reads_under_a_hundred():
    """The cell's arithmetic, from PERF.md's own readings (a step of
    16.18 ms over 32 slots that see 276 rows on average; a chunk of 5.60
    ms): shares of a roofline between 0 and 100, bound by bytes."""
    cfg = config("large")
    steps, slots, rows = 2249, 31.7, 276
    k = {"decode_steps": steps, "decode_tokens": steps * slots,
         "decode_context_sum": steps * slots * rows,
         "decode_window_rows_sum": steps * slots * rows,
         "prefill_tokens": 8928, "prefill_blocks": 611,
         "prefill_context_sum": 8928 * 28, "prefill_window_rows_sum": 8928 * 28,
         "window_s": 40.0, "chips": 1}
    trace = {"devices": [{"modules": [["jit__decode_step(1)", 0.0, 16.18e6],
                                      ["jit__prefill_chunk(2)", 2e7, 5.60e6]],
                          "ops": []}], "host": []}
    run = fake_run(cfg, k, trace)
    decode = y.read(run, {"what": "decode", "match": "^jit__decode_step"})
    prefill = y.read(run, {"what": "prefill", "match": "^jit__prefill_chunk"})
    mfu = y.read(run, {"what": "mfu"})
    assert 20 < decode < 45 and 45 < prefill < 60 and 1 < mfu < 5
    note = run.notes["roofline.decode"]
    assert note["bound"] == "memory" and 3.5e9 < note["bytes"] < 4.5e9
    assert set(note) == {"bound", "least_s", "device_s", "bytes", "flops"}
    assert note["device_s"] == pytest.approx(0.01618)


# ----- latent_moe: the bodies moved, the readings stayed ----------------


MADE_UP = {"moe_expert_layer_steps": 70, "decode_steps": 10,
           "decode_tokens": 320, "decode_context_sum": 224000,
           "prefill_tokens": 512, "prefill_context_sum": 131328,
           "moe_experts_touched": 7000, "window_s": 0.25, "chips": 1,
           "moe_feed_expert_layer_blocks": 28,
           "moe_feed_experts_touched": 2640}
# what readers/latent_moe_yardsticks.py read from these counters on the
# recorded trace (match ^jit_chain, median 1.802556 ms), written down
# before PR 32 deleted it
OLD = {"mfu": 2.1981729828385785, "decode": 539.9075540381552,
       "prefill": 464.9293377166465}
OLD_NEED = {"decode": (53414461440.0, 7970619392.0, 0.00973213600976801),
            "prefill": (137113894912.0, 6863720960.000001, 0.008380611672771675)}


@pytest.mark.parametrize("what", ["mfu", "decode", "prefill"])
def test_latent_moe_reads_what_the_old_reader_read(what):
    cfg = config("kanana2-30b-a3b")
    run = fake_run(cfg, dict(MADE_UP), json.loads(FIXTURE.read_text()))
    got = y.read(run, {"what": what, "match": "^jit_chain"})
    assert got == pytest.approx(OLD[what], rel=1e-12)
    if what != "mfu":
        note = run.notes[f"roofline.{what}"]
        assert (note["flops"], note["bytes"], note["least_s"]) == pytest.approx(
            OLD_NEED[what], rel=1e-12)
        assert note["bound"] == "memory"
        assert note["device_s"] == pytest.approx(0.001802556, rel=1e-12)


def test_latent_moe_counts_nothing_without_the_programs_counters():
    cfg = config("kanana2-30b-a3b")
    for fn in (latent_moe.window_flops, latent_moe.decode_need,
               latent_moe.prefill_need):
        assert fn(cfg, {"decode_steps": 10, "decode_tokens": 320}) is None
    no_feed = {k: v for k, v in MADE_UP.items() if not k.startswith("moe_feed")}
    assert latent_moe.prefill_need(cfg, no_feed) is None
    assert latent_moe.decode_need(cfg, no_feed) is not None


# ----- the reader -------------------------------------------------------


def test_a_config_without_family_is_progens_and_an_unknown_one_reads_none():
    assert y.counts_for({}) is progen
    assert y.counts_for({"family": "latent_moe"}) is latent_moe
    assert y.counts_for({"family": "no-such-family"}) is None
    run = fake_run({"family": "no-such-family"}, dict(MADE_UP),
                   json.loads(FIXTURE.read_text()))
    for what in ("mfu", "decode", "prefill"):
        assert y.read(run, {"what": what, "match": "^jit_chain"}) is None
    with pytest.raises(ValueError):
        y.read(run, {"what": "no-such-share"})


def test_no_trace_no_program_or_no_peak_reads_none():
    cfg = config("kanana2-30b-a3b")
    trace = json.loads(FIXTURE.read_text())
    spec = {"what": "decode", "match": "^jit_chain"}
    assert y.read(fake_run(cfg, dict(MADE_UP), None), spec) is None
    assert y.read(fake_run(cfg, dict(MADE_UP), {"devices": [], "host": []}), spec) is None
    assert y.read(fake_run(cfg, dict(MADE_UP), trace),
                  {"what": "decode", "match": "^jit_nothing"}) is None
    assert y.read(fake_run(cfg, dict(MADE_UP), trace, peak=None), spec) is None
    assert y.read(fake_run(cfg, dict(MADE_UP), None), {"what": "mfu"}) > 0  # needs no trace
    assert xplane.matching(trace["devices"][0]["modules"], "^jit_chain")


def test_every_count_module_has_the_three_functions_and_its_metric_files_name_the_reader():
    for path in (ROOT / "benchmark" / "counts").glob("*.py"):
        module = y.counts_for({"family": path.stem})
        for fn in ("window_flops", "decode_need", "prefill_need"):
            assert callable(getattr(module, fn)), (path.name, fn)
    for name, what in (("serve.mfu", "mfu"), ("engine.decode_roofline", "decode"),
                       ("engine.prefill_roofline", "prefill")):
        spec = json.loads((ROOT / "benchmark/metrics" / f"{name}.json").read_text())
        assert (spec["reader"], spec["what"]) == ("served_yardsticks", what)
    assert not (ROOT / "benchmark/readers/latent_moe_yardsticks.py").exists()


def test_steps_ahead_share_is_the_counters_ratio():
    spec = json.loads((ROOT / "benchmark/metrics/sched.steps_ahead_share.json").read_text())
    run = fake_run({}, {"decode_steps_ahead": 2243.0, "decode_steps": 2249.0})
    assert ratio.read(run, spec) == pytest.approx(100 * 2243 / 2249)
    assert ratio.read(fake_run({}, {"decode_steps": 5.0}), spec) is None
    assert ratio.read(fake_run({}, {"decode_steps_ahead": 0.0, "decode_steps": 0.0}), spec) is None
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    m = next(m for m in man["per_layer"] if m["name"] == "sched.steps_ahead_share")
    assert (m["unit"], m["better"], m["source"], m["layer"], m["moves"]) == (
        "%", "higher", "program_counter", "scheduler", "serve_tok_s_chip")
    assert m["workloads"][:2] == ["large.gen-closed", "kanana2-30b-a3b.gen-chat"]
