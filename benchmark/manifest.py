"""Consistency of BENCHMARK.json with the benchmark's files: the rules of
the benchmark's contract that can be checked without running anything.
``check(root)`` returns a list of faults, empty when all is well."""

from __future__ import annotations

import json
import re
from pathlib import Path

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def check(root) -> list:
    root = Path(root)
    faults = []
    man = json.loads((root / "BENCHMARK.json").read_text())
    bench = root / "benchmark"

    def bad(msg):
        faults.append(msg)

    want = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(man) != want:
        bad(f"keys {sorted(set(man) ^ want)} missing or extra")
    for path in man["paths"]:
        if not (root / path).is_dir():
            bad(f"path {path} is not a directory")
    if not 1 <= man["run_seconds"] <= 51:
        bad("run_seconds outside 1..51")

    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in man[k]]
    for n in names:
        if not NAME.match(n):
            bad(f"name {n!r} uses characters outside the allowed set")
    for kind in ("configs", "workloads"):
        ns = [x["name"] for x in man[kind]]
        if len(ns) != len(set(ns)):
            bad(f"duplicate name among {kind}")
    metric_names = [m["name"] for m in man["end_to_end"] + man["per_layer"]]
    if len(metric_names) != len(set(metric_names)):
        bad("duplicate metric name")

    configs = {c["name"]: c for c in man["configs"]}
    for c in man["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            bad(f"config {c['name']}: wrong keys")
        f = root / c["file"]
        if not f.is_file() or not any(
            c["file"].startswith(p + "/") for p in man["paths"]
        ):
            bad(f"config {c['name']}: file {c['file']} missing or outside paths")
        if not 1 <= len(c["source"]) <= 200 or not 1 <= len(c["why"]) <= 200:
            bad(f"config {c['name']}: source or why not 1..200 characters")
    used = set()
    pairs = set()
    cells = {}
    for w in man["workloads"]:
        cells[w["name"]] = w
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            bad(f"cell {w['name']}: wrong keys")
        if w["config"] not in configs:
            bad(f"cell {w['name']}: unknown config {w['config']}")
        used.add(w["config"])
        if (w["config"], w["traffic"]) in pairs:
            bad(f"cell {w['name']}: config and traffic pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            bad(f"cell {w['name']}: chips must be 1 or 4")
        if not 1 <= len(w["why"]) <= 200 or "\n" in w["why"]:
            bad(f"cell {w['name']}: why not one line of 1..200 characters")
        if not NAME.match(w["traffic"]):
            bad(f"cell {w['name']}: traffic name")
        tf = bench / "traffic" / f"{w['traffic']}.json"
        if not tf.is_file():
            bad(f"cell {w['name']}: no traffic file {tf.name}")
        else:
            driver = json.loads(tf.read_text()).get("driver")
            if not (bench / "drivers" / f"{driver}.py").is_file():
                bad(f"traffic {w['traffic']}: no driver {driver}")
    for c in configs:
        if c not in used:
            bad(f"config {c} is used by no cell")
    four = sum(1 for w in man["workloads"] if w["chips"] == 4)
    if four > max(1, len(man["workloads"]) // 4):
        bad(f"{four} four-chip cells of {len(man['workloads'])}")

    def cells_of(m):
        return m.get("workloads", list(cells))

    e2e = {m["name"]: m for m in man["end_to_end"]}
    if "setup_s" not in e2e:
        bad("no setup_s among end_to_end")
    for m in man["end_to_end"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "bound", "source"}:
            bad(f"metric {m['name']}: wrong keys")
        if not 0 < m["bound"] <= 0.1:
            bad(f"metric {m['name']}: bound outside (0, 0.1]")
        if m["source"] not in ("host_clock", "device_trace"):
            bad(f"metric {m['name']}: an end-to-end source is host_clock or device_trace")
    for m in man["per_layer"]:
        if set(m) - {"workloads"} != {"name", "unit", "better", "source",
                                     "layer", "moves"}:
            bad(f"metric {m['name']}: wrong keys")
        if m["source"] not in SOURCES:
            bad(f"metric {m['name']}: source {m['source']}")
        moved = e2e.get(m["moves"])
        if moved is None:
            bad(f"metric {m['name']}: moves unknown metric {m['moves']}")
        elif not set(cells_of(m)) <= set(cells_of(moved)):
            bad(f"metric {m['name']}: {m['moves']} is not reported in all its cells")
    for m in man["end_to_end"] + man["per_layer"]:
        if not UNIT.match(m["unit"]):
            bad(f"metric {m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad(f"metric {m['name']}: better")
        for w in m.get("workloads", []):
            if w not in cells:
                bad(f"metric {m['name']}: unknown cell {w}")
        mf = bench / "metrics" / f"{m['name']}.json"
        if not mf.is_file():
            bad(f"metric {m['name']}: no file {mf.name}")
            continue
        reader = json.loads(mf.read_text()).get("reader")
        if not (bench / "readers" / f"{reader}.py").is_file():
            bad(f"metric {m['name']}: no reader {reader}")
    for name, w in cells.items():
        mine = [m["name"] for m in man["end_to_end"] if name in cells_of(m)]
        if "setup_s" not in mine or len(mine) < 2:
            bad(f"cell {name}: needs setup_s and one more end-to-end metric")
        if not any(name in cells_of(m) for m in man["per_layer"]):
            bad(f"cell {name}: no per-layer metric")
    if len(json.dumps(man)) > 64 * 1024:
        bad("BENCHMARK.json over 64 KiB")
    return faults


if __name__ == "__main__":
    import sys

    problems = check(Path(__file__).resolve().parents[1])
    print("\n".join(problems) or "BENCHMARK.json is consistent")
    sys.exit(1 if problems else 0)
