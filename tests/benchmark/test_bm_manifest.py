"""BENCHMARK.json against the benchmark's files, and the proof that a later
PR can add a configuration, a traffic mix, a driver, a metric and a reader
as new files plus new entries, editing no file that is there."""

import json
import shutil
from pathlib import Path

import pytest

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


def test_the_four_cells_and_their_chips():
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    chips = {w["name"]: w["chips"] for w in man["workloads"]}
    assert chips == {"large.gen-closed": 1, "large.score-batch": 1,
                     "long8k.train": 1, "large.train-dp2tp2": 4}
    assert man["command"] == ["python3", "-m", "benchmark.run"]
    assert man["paths"] == ["benchmark", "tests/benchmark"]


def test_every_file_under_benchmark_is_named_from_allowed_characters():
    for p in (ROOT / "benchmark").rglob("*"):
        if "__pycache__" in p.parts:
            continue
        assert all(c.isalnum() or c in "_.-" for c in p.name), p


def test_one_of_each_can_be_added_as_new_files_and_entries(copy):
    before = {p: p.read_bytes() for p in (copy / "benchmark").rglob("*") if p.is_file()}
    b = copy / "benchmark"
    (b / "configs" / "base.json").write_text(json.dumps({"dim": 1024}))
    (b / "traffic" / "embed-burst.json").write_text(json.dumps({"driver": "embed"}))
    (b / "drivers" / "embed.py").write_text("def run(run):\n    return {}\n")
    (b / "metrics" / "embed.rows_per_call.json").write_text(
        json.dumps({"reader": "rows"}))
    (b / "metrics" / "embed_p95_s.json").write_text(json.dumps({"reader": "percentile"}))
    (b / "readers" / "rows.py").write_text("def read(run, spec):\n    return None\n")

    def add(man):
        man["configs"].append({"name": "base", "source": "https://example.org/base",
                               "file": "benchmark/configs/base.json",
                               "reduced": [], "why": "the width between the two"})
        man["workloads"].append({"name": "base.embed-burst", "config": "base",
                                 "traffic": "embed-burst", "chips": 1,
                                 "why": "embeddings in bursts of 8"})
        man["end_to_end"].append({"name": "embed_p95_s", "unit": "s",
                                  "better": "lower", "bound": 0.03,
                                  "source": "host_clock",
                                  "workloads": ["base.embed-burst"]})
        man["per_layer"].append({"name": "embed.rows_per_call", "unit": "count",
                                 "better": "higher", "source": "program_counter",
                                 "layer": "engine", "moves": "embed_p95_s",
                                 "workloads": ["base.embed-burst"]})

    edit(copy, add)
    assert manifest.check(copy) == []
    after = {p: p.read_bytes() for p in before}
    assert after == before  # nothing that was there was edited


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
