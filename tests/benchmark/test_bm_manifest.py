"""BENCHMARK.json against the benchmark's files; what was accepted, held
as a floor and not as a census (``bm_floor.py``); and the proof that a
later PR can add a served cell of a new family — a configuration, a
traffic mix, a driver, a count module, a metric and a reader — as new
files plus new entries, editing no file that is there, with the
manifest's check and the floor both passing."""

import json
import shutil
import sys
from pathlib import Path

import pytest

import bm_floor
from benchmark import manifest

ROOT = Path(__file__).resolve().parents[2]


@pytest.fixture()
def copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "benchmark", tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "tests" / "benchmark").mkdir(parents=True)
    return tmp_path


def edit(root, fn):
    man = json.loads((root / "BENCHMARK.json").read_text())
    fn(man)
    (root / "BENCHMARK.json").write_text(json.dumps(man))


def test_the_manifest_as_committed_is_consistent():
    assert manifest.check(ROOT) == []


def test_the_accepted_cells_and_their_chips():
    """The five accepted cells lead ``workloads`` with the configuration,
    traffic and chips they were accepted with; more may follow, to 24."""
    assert bm_floor.accepted_cells(ROOT) == []
    man = bm_floor.load(ROOT)
    assert [w["name"] for w in man["workloads"]][:5] == [
        "large.gen-closed", "large.score-batch", "long8k.train",
        "large.train-dp2tp2", "kanana2-30b-a3b.gen-chat"]
    assert {w["name"]: w["chips"] for w in man["workloads"]}[
        "large.train-dp2tp2"] == 4


def test_the_floor_holds_on_the_manifest_as_committed():
    assert bm_floor.faults(ROOT) == []


def test_every_file_under_benchmark_is_named_from_allowed_characters():
    for p in (ROOT / "benchmark").rglob("*"):
        if "__pycache__" in p.parts:
            continue
        assert all(c.isalnum() or c in "_.-" for c in p.name), p


def add_a_served_cell_of_a_new_family(root):
    """What a ``model_config`` PR brings for a served cell of a made-up
    family, as new files and appended entries alone."""
    b = root / "benchmark"
    (b / "configs" / "hybrid-7b.json").write_text(json.dumps(
        {"family": "hybrid", "hidden_size": 4096, "num_hidden_layers": 16}))
    (b / "traffic" / "gen-long.json").write_text(json.dumps(
        {"driver": "gen_hybrid", "prompt_lengths": [4096, 8192]}))
    (b / "drivers" / "gen_hybrid.py").write_text("def run(run):\n    return {}\n")
    (b / "reference" / "hybrid_ref.py").write_text("def forward(p, t, c):\n    return None\n")
    (b / "counts" / "hybrid.py").write_text(
        "def window_flops(c, k):\n    return 2.0 * c['hidden_size'] * k['tokens']\n"
        "def decode_need(c, k):\n    return 1.0, 2.0\n"
        "def prefill_need(c, k):\n    return None\n")
    (b / "metrics" / "state.rows_per_step.json").write_text(
        json.dumps({"reader": "rows"}))
    (b / "readers" / "rows.py").write_text("def read(run, spec):\n    return None\n")
    cell = "hybrid-7b.gen-long"

    def add(man):
        man["configs"].append({"name": "hybrid-7b", "source": "https://example.org/hybrid",
                               "file": "benchmark/configs/hybrid-7b.json",
                               "reduced": ["num_hidden_layers"],
                               "why": "linear and sparse attention layers in whole periods"})
        man["workloads"].append({"name": cell, "config": "hybrid-7b",
                                 "traffic": "gen-long", "chips": 1,
                                 "why": "long prompts, so the recurrent state does the work"})
        for m in man["end_to_end"] + man["per_layer"]:
            if bm_floor.SERVED in m.get("workloads", []):
                m["workloads"].append(cell)  # every list of the served path
        man["per_layer"].append({"name": "state.rows_per_step", "unit": "count",
                                 "better": "lower", "source": "program_counter",
                                 "layer": "model", "moves": "itl_p50_s",
                                 "workloads": [cell]})

    edit(root, add)
    return cell


def test_a_served_cell_of_a_new_family_is_added_as_new_files_and_entries(copy):
    before = {p: p.read_bytes() for p in (copy / "benchmark").rglob("*") if p.is_file()}
    accepted = bm_floor.load(copy)
    cell = add_a_served_cell_of_a_new_family(copy)
    assert manifest.check(copy) == []
    assert bm_floor.faults(copy) == []
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there was edited
    # every accepted list is a prefix of the new one, never its whole
    man = bm_floor.load(copy)
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert [x["name"] for x in man[kind]][:len(accepted[kind])] == [
            x["name"] for x in accepted[kind]]
    for was, now in zip(accepted["end_to_end"] + accepted["per_layer"],
                        man["end_to_end"] + man["per_layer"]):
        if "workloads" in was:
            assert now["workloads"][:len(was["workloads"])] == was["workloads"]
    # the cell reports the served rate, both gaps and every metric of the
    # scheduler and the engine that the first served cell reports
    mine = {m["name"] for m in man["end_to_end"] + man["per_layer"]
            if cell in m.get("workloads", [cell])}
    theirs = {m["name"] for m in man["end_to_end"] + man["per_layer"]
              if bm_floor.SERVED in m.get("workloads", [bm_floor.SERVED])}
    assert theirs <= mine and "state.rows_per_step" in mine - theirs


def test_the_yardsticks_read_the_new_familys_counts_by_its_name(copy, monkeypatch):
    import importlib.util
    import types

    from benchmark.readers import served_yardsticks

    add_a_served_cell_of_a_new_family(copy)
    spec = importlib.util.spec_from_file_location(
        "benchmark.counts.hybrid", copy / "benchmark" / "counts" / "hybrid.py")
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    config = json.loads((copy / "benchmark/configs/hybrid-7b.json").read_text())
    run = types.SimpleNamespace(
        config=config, trace=None, notes={},
        peak={"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        counters={"tokens": 1e9, "window_s": 2.0, "chips": 1})
    assert served_yardsticks.read(run, {"what": "mfu"}) is None  # no module yet
    monkeypatch.setitem(sys.modules, "benchmark.counts.hybrid", module)
    mfu = served_yardsticks.read(run, {"what": "mfu"})
    assert mfu == pytest.approx(100 * 2.0 * 4096 * 1e9 / (2.0 * 197e12))
    assert served_yardsticks.read(run, {"what": "prefill", "match": "^jit"}) is None


@pytest.mark.parametrize("metric", ["sched.occupancy", "serve.mfu",
                                    "engine.idle_ms_per_step", "itl_p95_s"])
def test_a_served_cell_left_off_one_list_fails_a_rule(copy, metric):
    cell = add_a_served_cell_of_a_new_family(copy)

    def drop(man):
        m = next(m for m in man["end_to_end"] + man["per_layer"]
                 if m["name"] == metric)
        m["workloads"].remove(cell)

    edit(copy, drop)
    faults = manifest.check(copy) + bm_floor.faults(copy)
    assert any(metric in f or cell in f for f in faults), faults


def test_the_floor_sees_an_accepted_cell_or_metric_taken_away(copy):
    edit(copy, lambda m: m["workloads"].reverse())
    assert any("prefix" in f for f in bm_floor.accepted_cells(copy))
    edit(copy, lambda m: m["per_layer"].pop(
        next(i for i, x in enumerate(m["per_layer"]) if x["name"] == "serve.mfu")))
    assert any("serve.mfu is gone" in f for f in bm_floor.accepted_metrics(copy))


@pytest.mark.parametrize("name,break_it,fault", [
    ("missing traffic file",
     lambda m: m["workloads"][0].update(traffic="nowhere"), "no traffic file"),
    ("moves names no end-to-end metric",
     lambda m: m["per_layer"][1].update(moves="ttft_p95_s"), "moves unknown"),
    ("moves a metric its cells do not report",
     lambda m: m["per_layer"][0].update(moves="itl_p50_s"), "not reported in all"),
    ("a space in a unit",
     lambda m: m["end_to_end"][0].update(unit="tokens per s"), "unit"),
    ("a slash in a name",
     lambda m: m["workloads"][1].update(name="large/score"), "characters"),
    ("a second four-chip cell",
     lambda m: m["workloads"][0].update(chips=4), "four-chip"),
    ("a bound over the limit",
     lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
    ("no setup_s",
     lambda m: m["end_to_end"].pop(), "setup_s"),
    ("an extra key on a metric",
     lambda m: m["per_layer"][0].update(why="because"), "wrong keys"),
    ("the same pair twice",
     lambda m: m["workloads"].append(dict(m["workloads"][0], name="again")), "twice"),
])
def test_the_check_finds(copy, name, break_it, fault):
    edit(copy, break_it)
    faults = manifest.check(copy)
    assert any(fault in f for f in faults), (name, faults)


def test_every_metric_file_names_what_the_manifest_says():
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    for kind in ("end_to_end", "per_layer"):
        for m in man[kind]:
            spec = json.loads((ROOT / "benchmark" / "metrics" / f"{m['name']}.json").read_text())
            assert spec["name"] == m["name"] and spec["unit"] == m["unit"]
            assert spec["kind"] == kind
            if kind == "per_layer":
                assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]


def test_the_gen_traffic_file_holds_the_issues_parameters():
    t = json.loads((ROOT / "benchmark" / "traffic" / "gen-closed.json").read_text())
    assert (t["max_slots"], t["clients"], t["prefill_chunk"], t["journal"]) == (32, 32, 16, True)
    assert t["prompt_lengths"] == list(range(16, 81, 8))
    assert t["output_lengths"] == list(range(128, 641, 64))
    assert t["max_len"] == 1024 and t["top_k"] == 25 and t["temperature"] == 1.0
