"""The device_scopes reader (PR 38) on traces made by hand: the innermost
class rule, self time under nesting, the median over whole executions,
closure of the classes with ``(unscoped)``, the metadata decoded from an
XSpace's bytes; and this PR's eight entries, guarded by presence."""

import json
import types
from pathlib import Path

import pytest

from benchmark import manifest
from benchmark.readers import device_scopes as D

ROOT = Path(__file__).resolve().parents[2]
CLASSES = ("project", "attend", "cache_write", "ffn", "head", "sample",
           "optimizer")


@pytest.mark.parametrize("op_name, want", [
    ("jit(f)/project/attn0/attend/local/dot_general", "attend"),
    ("jit(f)/project/attn0/attend/local/cache_write/dynamic_update_slice",
     "cache_write"),
    ("jit(f)/ffn/ff2/sgu/attend/sgu/cache_write/x", "cache_write"),
    ("jit(f)/ffn/ff2/sgu/attend/sgu/mul", "attend"),
    ("jit(t)/while/body/closed_call/transpose(jvp(attend))/local/dot", "attend"),
    ("jit(t)/while/body/closed_call/transpose(jvp(ProGen))/ffn/ff0/add", "ffn"),
    ("jit(_decode_step)/sample/vmap()/gather", "sample"),
    ("jit(f)/attention_core/dot_general", D.UNSCOPED),  # a name, no class
    ("jit(f)/projection/attend_x/mul", D.UNSCOPED),  # whole components only
    ("params['ffn1']['w_down']", D.UNSCOPED),
    ("", D.UNSCOPED),
])
def test_an_op_is_filed_under_the_innermost_class(op_name, want):
    assert D.classify(op_name, CLASSES) == want


STACKS = {
    "%loop": "jit(_decode_step)/ffn/while",
    "%dot.1": "jit(_decode_step)/project/attn0/attend/local/dot",
    "%dus.1": "jit(_decode_step)/project/attn0/attend/cache_write/dus",
    "%mul.1": "jit(_decode_step)/ffn/ff0/mul",
    "%draw": "jit(_decode_step)/sample/argmax",
    "%copy.1": "",  # XLA's own
    "%blk": "jit(_prefill_chunk)/project/attn0/attend/local/dot",
}


def _decode(t0, fill=0.0):
    """One decode execution from t0 (ns): 100 long, a loop of 40 holding
    two ops (cache_write 10, attend 20), then ffn 20, sample 10, a copy 5."""
    ops = [["%loop", t0 + 0, 40], ["%dus.1", t0 + 5, 10],
           ["%dot.1", t0 + 15, 20], ["%mul.1", t0 + 40, 20 + fill],
           ["%draw", t0 + 60 + fill, 10], ["%copy.1", t0 + 70 + fill, 5]]
    return ops, ["jit__decode_step(7)", t0, 100 + fill]


def _trace():
    ops, modules = [], []
    for i, fill in enumerate([0.0, 10.0, 30.0, 0.0]):
        o, m = _decode(1000 * i, fill)
        ops += o
        modules.append(m)
    ops.append(["%blk", 5000, 50])  # a chunk execution
    modules.append(["jit__prefill_chunk(9)", 5000, 60])
    ops.append(["%mul.1", 7000, 3])  # outside any execution
    return {"ops": ops, "modules": modules}


def test_the_reduction_sums_self_time_per_execution_and_class():
    table = D.reduce(_trace(), (-1, 10000), STACKS, CLASSES)
    dec = table["programs"]["jit__decode_step"]
    # the loop's self time is its 40 less the 30 nested in it: ffn
    assert dec["whole"][0] == pytest.approx({
        "ffn": 30 / 1e9, "cache_write": 10 / 1e9, "attend": 20 / 1e9,
        "sample": 10 / 1e9, D.UNSCOPED: 5 / 1e9})
    assert len(dec["whole"]) == 4
    # closure: every op's self time lands in one class, in the window
    ops_ns = 4 * 75 + 40 + 50 + 3
    assert sum(table["window_s"].values()) == pytest.approx(ops_ns / 1e9)
    assert table["programs"]["jit__prefill_chunk"]["whole"] == [
        pytest.approx({"attend": 50 / 1e9})]
    assert dec["ops"][D.UNSCOPED] == {"%copy.1": pytest.approx(20 / 1e9)}
    assert table["unscoped"] == dec["ops"][D.UNSCOPED]
    assert set(table["programs"]["jit__prefill_chunk"]["ops"]) == {"attend"}


def test_executions_cut_by_the_window_are_not_whole():
    table = D.reduce(_trace(), (0, 10000), STACKS, CLASSES)
    # the first execution starts on the window's edge: it may be cut
    assert len(table["programs"]["jit__decode_step"]["whole"]) == 3


def _run(tmp_path, trace):
    return types.SimpleNamespace(
        trace={"devices": [trace]}, trace_window=(-1, 10000),
        out_dir=tmp_path, cell={"name": "c"}, seed=1)


def test_read_takes_the_median_over_whole_executions(tmp_path, monkeypatch):
    run = _run(tmp_path, _trace())
    monkeypatch.setattr(D, "op_stacks", lambda path: (STACKS, set()))
    monkeypatch.setattr(D.xplane, "find_xplane", lambda d: "x.xplane.pb")
    run._trace_dir = tmp_path
    spec = {"what": "per_execution", "match": "^jit__decode_step"}
    # ffn reads 30, 40, 60, 30 ns in the four executions
    assert D.read(run, {**spec, "classes": ["ffn"]}) == pytest.approx(35e-6)
    assert D.read(run, {**spec, "classes": ["project", "ffn", "head"]}) \
        == pytest.approx(35e-6)
    assert D.read(run, {**spec, "classes": ["attend"]}) == pytest.approx(2e-5)
    share = D.read(run, {"what": "share", "classes": [D.UNSCOPED]})
    assert share == pytest.approx(100 * 20 / (4 * 75 + 40 + 50 + 3))
    chunk = D.read(run, {"what": "share", "match": "^jit__prefill",
                         "classes": ["attend"]})
    assert chunk == pytest.approx(100.0)
    out = json.loads((tmp_path / "program_scopes.json").read_text())
    dec = out["programs"]["jit__decode_step"]
    assert dec["executions"] == 4
    assert dec["ms_median"]["attend"] == pytest.approx(2e-5)
    # per execution counted by device time: 440 ns of executions, the
    # longest 130
    steps = 440 / 130
    assert dec["steps_by_time"] == pytest.approx(steps)
    assert dec["top_ms"][D.UNSCOPED] == [["%copy.1",
                                          pytest.approx(20e-6 / steps)]]
    assert dec["top_ms"]["ffn"][0] == ["%mul.1", pytest.approx(120e-6 / steps)]
    assert sum(out["window_s"].values()) == pytest.approx(out["op_self_s"])
    name, secs, pct = out["unscoped_top"][0]
    assert name == "%copy.1" and pct == pytest.approx(100 * secs / 1.0001e-5)


def test_a_program_without_the_vocabulary_reads_nothing(tmp_path, monkeypatch):
    run = _run(tmp_path, _trace())
    run._trace_dir = tmp_path
    monkeypatch.setattr(D, "vocabulary", lambda: None)
    assert D.read(run, {"what": "share", "classes": [D.UNSCOPED]}) is None
    assert not (tmp_path / "program_scopes.json").exists()


XSPACE = '''
planes { id: 2 name: "/host:CPU"
  event_metadata { key: 1 value { id: 1 name: "host"
    stats { metadata_id: 2 str_value: "jit(f)/head/x:" } } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } } }
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops"
    events { metadata_id: 7 offset_ps: 1000 duration_ps: 5000 } }
  event_metadata { key: 7 value { id: 7 name: "%fusion.3 = bf16[2] fusion()"
    stats { metadata_id: 3 int64_value: 4 }
    stats { metadata_id: 4 uint64_value: 11 }
    stats { metadata_id: 2 str_value: "jit(f)/ffn/experts/gather:" } } }
  event_metadata { key: 8 value { id: 8 name: "%copy.1 = bf16[2] copy()"
    stats { metadata_id: 2 ref_value: 9 } } }
  event_metadata { key: 10 value { id: 10 name: "%copy.1 = bf16[2] copy()"
    stats { metadata_id: 2 str_value: "jit(g)/ffn/copy:" } } }
  event_metadata { key: 11 value { id: 11 name: "%no_stack" } }
  event_metadata { key: 12 value { id: 12
    name: "%ragged-dot-none.1 = bf16[2] custom-call(s32[1] %gte.1, bf16[2] %fusion.3)"
    stats { metadata_id: 4 uint64_value: 11 }
    stats { metadata_id: 2 str_value: "ragged-dot-none:" } } }
  event_metadata { key: 13 value { id: 13
    name: "%ragged-dot-none.2 = bf16[2] custom-call(bf16[2] %fusion.3)"
    stats { metadata_id: 4 uint64_value: 12 }
    stats { metadata_id: 2 str_value: "ragged-dot-none:" } } }
  stat_metadata { key: 2 value { id: 2 name: "tf_op" } }
  stat_metadata { key: 3 value { id: 3 name: "flops" } }
  stat_metadata { key: 4 value { id: 4 name: "program_id" } }
  stat_metadata { key: 9 value { id: 9 name: "jit(f)/cache_write/dus:" } } }
'''


def test_name_stacks_are_read_from_the_device_planes_metadata(tmp_path):
    from jax.profiler import ProfileData

    path = tmp_path / "t.xplane.pb"
    path.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    stacks, conflicts = D.op_stacks(str(path))
    # the stat without its ``:<type>``
    assert stacks["%fusion.3 = bf16[2] fusion()"] == \
        "jit(f)/ffn/experts/gather"
    assert "host" not in stacks and "%no_stack" not in stacks
    # a string given by reference, and two programs' metadata of one name
    assert stacks["%copy.1 = bf16[2] copy()"] in (
        "jit(f)/cache_write/dus", "jit(g)/ffn/copy")
    assert conflicts == {"%copy.1 = bf16[2] copy()"}
    # a compiler's custom call takes its first scoped operand's stack, in
    # its own program only
    assert stacks["%ragged-dot-none.1 = bf16[2] custom-call(s32[1] %gte.1, "
                  "bf16[2] %fusion.3)"] == "jit(f)/ffn/experts/gather"
    assert stacks["%ragged-dot-none.2 = bf16[2] custom-call(bf16[2] "
                  "%fusion.3)"] == "ragged-dot-none"


# ----- this PR's entries, by presence (PERF.md §4, step 5) ------------------

SERVED = ["large.gen-closed", "kanana2-30b-a3b.gen-chat",
          "minicpm-sala.gen-longdoc"]
STEPS = ["large.score-batch", "long8k.train", "large.train-dp2tp2"]
ADDED = {
    "engine.decode_attend_ms": ("engine", "itl_p50_s", SERVED, ["attend"]),
    "engine.decode_cache_write_ms": ("engine", "itl_p50_s", SERVED,
                                     ["cache_write"]),
    "engine.decode_weights_ms": ("engine", "itl_p50_s", SERVED,
                                 ["project", "ffn", "head"]),
    "engine.decode_sample_ms": ("engine", "itl_p50_s", SERVED, ["sample"]),
    "engine.prefill_attend_ms": ("engine", "itl_p95_s", SERVED, ["attend"]),
    "model.attend_share": ("model", "tok_s_chip", STEPS, ["attend"]),
    "device.unscoped_share": ("device", "tok_s_chip", STEPS, [D.UNSCOPED]),
    "device.unscoped_share.serve": ("device", "serve_tok_s_chip", SERVED,
                                    [D.UNSCOPED]),
}


@pytest.mark.parametrize("name", list(ADDED))
def test_what_this_pr_added_is_still_there(name):
    layer, moves, cells, classes = ADDED[name]
    man = json.loads((ROOT / "BENCHMARK.json").read_text())
    m = next(m for m in man["per_layer"] if m["name"] == name)
    assert (m["source"], m["layer"], m["moves"]) == (
        "device_trace", layer, moves)
    assert m["workloads"][:len(cells)] == cells
    spec = json.loads((ROOT / "benchmark" / "metrics" / f"{name}.json")
                      .read_text())
    assert spec["reader"] == "device_scopes" and spec["classes"] == classes
    assert manifest.check(ROOT) == []


def test_the_readers_vocabulary_is_the_programs():
    assert D.vocabulary() == CLASSES
