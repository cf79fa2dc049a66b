"""What was accepted, held as a floor: rules on a root directory that a
later PR keeps by ADDING to BENCHMARK.json, never by editing a test here.
Each function returns a list of faults, empty when the rule holds. A PR
that adds a cell guards its own entries in its own test file, by
presence, as ``test_bm_latent_moe.py`` does."""

import json
from pathlib import Path

# (name, config, traffic, chips), in the order they were accepted
CELLS = [
    ("large.gen-closed", "large", "gen-closed", 1),
    ("large.score-batch", "large", "score-batch", 1),
    ("long8k.train", "long8k", "train", 1),
    ("large.train-dp2tp2", "large", "train-dp2tp2", 4),
    ("kanana2-30b-a3b.gen-chat", "kanana2-30b-a3b", "gen-chat", 1),
]
SERVED = "large.gen-closed"        # the first served cell: it carries every
SERVED_METRIC = "serve_tok_s_chip"  # shared scheduler and engine metric

# PR 26's per-layer metrics: name -> (source, layer, moves, better).
# ``engine.decode_overhead_ms`` was the twelfth; PR 32 retired it: since
# PR 31 the loop launches step N+1 before it fetches step N, so
# "serve/decode less the program inside it" reads negative (-1.60 and
# -1.31 ms, ledger, PR 31) and measures nothing; engine.idle_ms_per_step
# and sched.steps_ahead_share say what it used to.
NEW = {
    "sched.emit_ms_per_step": ("program_span", "scheduler", "itl_p50_s", "lower"),
    "sched.journal_ms_per_step": ("program_span", "scheduler", "itl_p50_s", "lower"),
    "sched.admit_ms": ("program_span", "scheduler", "itl_p95_s", "lower"),
    "engine.prefill_dispatch_ms": ("program_span", "engine", "itl_p95_s", "lower"),
    "sched.idle_ms_per_step": ("program_span", "scheduler", "serve_tok_s_chip", "lower"),
    "engine.idle_ms_per_step": ("program_span", "engine", "serve_tok_s_chip", "lower"),
    "score.host_ms_per_batch": ("program_span", "scoring", "tok_s_chip", "lower"),
    "env.compile_load_s": ("program_counter", "env / compile cache", "setup_s", "lower"),
    "env.cache_misses": ("program_counter", "env / compile cache", "setup_s", "lower"),
    "engine.prepare_ms": ("program_span", "engine", "itl_p95_s", "lower"),
    "engine.prefill_finish_ms": ("program_span", "engine", "itl_p95_s", "lower"),
}
RETIRED = ["engine.decode_overhead_ms"]
# PR 28's five
ADDED = {
    "serve.mfu": ("host_clock", "engine", "serve_tok_s_chip", "higher"),
    "engine.decode_roofline": ("device_trace", "engine", "itl_p50_s", "higher"),
    "engine.prefill_roofline": ("device_trace", "engine", "itl_p95_s", "higher"),
    "moe.experts_touched": ("program_counter", "model", "serve_tok_s_chip", "lower"),
    "moe.load_max_over_mean": ("program_counter", "model", "itl_p95_s", "lower"),
}


def load(root) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def cells_of(man: dict, metric: dict) -> list:
    return metric.get("workloads", [w["name"] for w in man["workloads"]])


def accepted_cells(root) -> list:
    """The accepted cells lead the list, unchanged; more may follow."""
    man, faults = load(root), []
    got = [(w["name"], w["config"], w["traffic"], w["chips"])
           for w in man["workloads"]]
    if got[:len(CELLS)] != CELLS:
        faults.append(f"the accepted cells {CELLS} are not a prefix of {got}")
    if len(got) > 24:
        faults.append(f"{len(got)} cells, over 24")
    if man["command"] != ["python3", "-m", "benchmark.run"]:
        faults.append(f"command {man['command']}")
    if man["paths"] != ["benchmark", "tests/benchmark"]:
        faults.append(f"paths {man['paths']}")
    return faults


def accepted_metrics(root) -> list:
    """PR 26's and PR 28's metrics are there by name, each as it was
    accepted, in the order they were accepted in; the retired are gone."""
    man, faults = load(root), []
    by = {m["name"]: m for m in man["per_layer"]}
    for name, want in {**NEW, **ADDED}.items():
        m = by.get(name)
        if m is None:
            faults.append(f"accepted metric {name} is gone")
        elif (m["source"], m["layer"], m["moves"], m["better"]) != want:
            faults.append(f"accepted metric {name} is no longer {want}")
    names = [m["name"] for m in man["per_layer"]]
    pinned = [n for n in names if n in NEW or n in ADDED]
    if pinned != [*NEW, *ADDED]:
        faults.append(f"accepted metrics out of their order: {pinned}")
    for name in RETIRED:
        if name in by:
            faults.append(f"retired metric {name} is back")
    return faults


def served_lists(root) -> list:
    """Every per-layer metric that lists the first served cell lists
    every cell that reports the served rate, the accepted ones first: a
    new served cell carries the shared scheduler and engine metrics."""
    man, faults = load(root), []
    served = next(cells_of(man, m) for m in man["end_to_end"]
                  if m["name"] == SERVED_METRIC)
    accepted = [c[0] for c in CELLS if c[0] in served]
    if served[:len(accepted)] != accepted:
        faults.append(f"{SERVED_METRIC}: {accepted} is not a prefix of {served}")
    for m in man["per_layer"]:
        listed = cells_of(man, m)
        if SERVED not in listed:
            continue
        if not set(served) <= set(listed):
            faults.append(f"{m['name']} lists {SERVED} but not "
                          f"{sorted(set(served) - set(listed))}")
        if "workloads" in m and listed[:len(accepted)] != accepted:
            faults.append(f"{m['name']}: {accepted} is not a prefix of {listed}")
    return faults


def faults(root) -> list:
    return accepted_cells(root) + accepted_metrics(root) + served_lists(root)
