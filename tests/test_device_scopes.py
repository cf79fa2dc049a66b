"""Every device op is filed under a mechanism the program names
(``progen_tpu/telemetry/scopes.py``), and naming them changed no program.

* Each served program of the three families, the score step and two train
  steps, compiled here at the tests' tiny sizes: every instruction of the
  compiled text that XLA will run (the entry and the loop bodies, not the
  insides of a fusion) falls in a class through the benchmark reader's own
  ``classify``, but for a short, named allowlist.
* The StableHLO text of the same programs equals the parent's, as
  ``test_latent_moe.py::PROGEN_PROGRAMS`` holds for ProGen's two.
* No ``jax.named_scope`` in the package is outside the vocabulary, and a
  span labels no op.
"""

import ast
import contextlib
import functools
import hashlib
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax.core import meta

from benchmark.readers.device_scopes import UNSCOPED, classify
from progen_tpu.telemetry.scopes import CLASSES

REPO = Path(__file__).resolve().parents[1]


def _progen(**over):
    from progen_tpu.config import ProGenConfig
    from progen_tpu.models.progen import ProGen

    return ProGen(ProGenConfig(**{**dict(
        num_tokens=256, dim=32, seq_len=32, depth=3, window_size=8,
        global_mlp_depth=1, heads=2, dim_head=16, ff_mult=2,
        dtype="bfloat16"), **over}))


def _preset(name):
    from progen_tpu.config import load_toml_config
    from progen_tpu.models import build_model

    return build_model(load_toml_config(
        str(REPO / "configs" / "model" / f"{name}.toml")))


# family -> (model, the engine's max_len, the init sequence's length)
FAMILIES = {
    "progen": (_progen, 24, 32),
    "latent_moe": (lambda: _preset("latent-moe-small"), 256, 8),
    "linear_sparse": (lambda: _preset("linear-sparse-small"), 64, 8),
}
# the long8k recipe's switches at the tiny size
LONG = dict(use_pallas_attn=True, remat=True, scan_layers=True, depth=4,
            rotate_value=True, ff_glu=True)


@functools.lru_cache(maxsize=None)
def _served(family):
    from progen_tpu.serving import engine as E

    make, max_len, init_len = FAMILIES[family]
    model = make()
    params = meta.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, init_len), jnp.int32)))["params"]
    eng = E.ServeEngine(model, params, max_slots=3, max_len=max_len)
    row = jnp.zeros((max_len,), jnp.int32)
    return {
        "decode": E._decode_step.lower(eng.model, eng.served_params,
                                       eng.slots),
        "chunk": E._prefill_chunk.lower(
            eng.model, eng.served_params, eng.new_cache(), row, np.int32(0),
            np.int32(5)),
        "finish": E._prefill_finish.lower(
            eng.slots, eng.new_cache(), np.int32(0), row, np.int32(5),
            np.int32(max_len // 2), E.seed_key(1), np.float32(1.0),
            np.float32(1.0), np.int32(0), np.bool_(True), row,
            np.zeros((max_len,), bool), new_cache=eng._build_cache),
    }


@functools.lru_cache(maxsize=None)
def lowered(prog):
    """The program called ``prog``, lowered (not compiled)."""
    family, program = prog
    if program == "score":
        from progen_tpu.workloads.scoring import score_step

        model = _progen()
        params = meta.unbox(model.init(
            jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)))["params"]
        return score_step.lower(model, params, jnp.zeros((2, 33), jnp.int32))
    if program.startswith("train"):
        from progen_tpu.training.optimizer import make_optimizer
        from progen_tpu.training.step import init_train_state, make_train_step

        model = _progen(**(LONG if program == "train_long" else {}))
        opt = make_optimizer()
        state, _ = init_train_state(model, opt, jax.random.PRNGKey(0), 32)
        return jax.jit(make_train_step(model, opt)).lower(
            state, jnp.zeros((2, 2, 33), jnp.int32))
    return _served(family)[program]


# sha256 of ``Lowered.as_text()`` (jax 0.9.0), taken at the parent commit
# 7bade2d (PR 37), before any scope of PR 38 was added: scopes change only
# an op's location, which the text without debug info leaves out, so the
# programs XLA is handed are the parent's. ``finish`` is `_prefill_finish`;
# the served programs take ``engine.served_params``. ``latent_moe``'s decode
# and chunk were taken again when its row write became
# ``layers._update_at``'s (one update of the batch a leaf, the mask its own
# operand), which changes their text on purpose.
PARENT_PROGRAMS = {
    ("latent_moe", "decode"): "f763bbe112d4a9b666f6797a3b60d560fff274758ead1525ea6812dde0f95af2",
    ("latent_moe", "chunk"): "0ab8786c4148d13170a9f65ef0dce56cc89dc63be0454c0dd75ffd3c98b3e686",
    ("latent_moe", "finish"): "28c48698f051292e4086cc9d31a0cb8dff9b1900699f7c4fec700dd06ecf5deb",
    ("linear_sparse", "decode"): "169c030d1a1240a1d250ad2f648f87da4a6d06da264af70d40b1ceb6de13c0a2",
    ("linear_sparse", "chunk"): "eff0e512a7eb378c5e22484427090e4de8df0a0dd06628135488eea2e7872ae4",
    ("linear_sparse", "finish"): "49d10edf51c6f030c761da04ec319dd32e1acba487e76d8b805f5a5b9b2484e3",
    ("progen", "finish"): "636e5d5224b3c8ba79a78b5e2fef7f6f942d46323da10df4d7f0cda8a651f22b",
    ("progen", "score"): "b518d634ab9fb87e3781b3edfdf388aae07837df0d162be814bd00773e7c0888",
    ("progen", "train"): "517ddede2a0165afcf811ccd4d5388844d4219906ba7bb1ad3d0fdc297d240f3",
    ("progen", "train_long"): "de64008acbdf958c341e8eff592af3e0e94e8d4f20b3f5f57fa67b6909a68000",
}


@pytest.mark.parametrize("prog", list(PARENT_PROGRAMS),
                         ids=lambda k: "-".join(k))
def test_the_programs_lower_as_the_parents(prog):
    text = lowered(prog).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_PROGRAMS[prog]


# ----- every instruction XLA runs falls in a class --------------------------

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
_OPCODE = re.compile(r"\s([a-z][\w\-]*)\(")


def executed_instructions(hlo_text: str) -> list:
    """[(opcode, op_name)] of the instructions a device runs as ops of
    their own: those of the entry computation and of every computation a
    ``while``, ``conditional`` or ``call`` runs — not the insides of a
    fusion, a reduction or a comparator."""
    comps, cur, entry = {}, None, None
    for line in hlo_text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        else:
            m = _INSTRUCTION.match(line)
            if m:
                comps[cur].append(m.group(2))
    out, todo, done = [], [entry], set()
    while todo:
        c = todo.pop()
        if c in done or c not in comps:
            continue
        done.add(c)
        for rest in comps[c]:
            head = rest.split(" metadata=")[0]
            m = _OPCODE.search(" " + head)
            opcode = m.group(1) if m else "?"
            m = re.search(r'op_name="([^"]*)"', rest)
            out.append((opcode, m.group(1) if m else ""))
            keys = ["body", "condition", "true_computation",
                    "false_computation"] + (["to_apply"] if opcode == "call"
                                            else [])
            for k in keys:
                todo += re.findall(k + r"=%?([\w.\-]+)", head)
            m = re.search(r"branch_computations=\{([^}]*)\}", head)
            if m:
                todo += [x.strip().lstrip("%") for x in m.group(1).split(",")]
    return out


NOT_ARITHMETIC = {"parameter", "tuple", "get-tuple-element", "constant",
                  "bitcast"}
# what may stay unscoped, each with its reason
ALLOWED = {
    "XLA's own instruction (no name stack) or one named after an argument "
    "(a copy or re-layout of it)": r"^(?!jit\()",
    "the slots' bookkeeping at the top of _decode_step":
        r"^jit\(_decode_step\)/jit\((clip|take_along_axis)\)/",
    "a prefill feed loop's bounds":
        r"^jit\(_prefill_chunk\)/(\w+\.feed_tokens/)?(jit\(floor_divide\)/)?"
        r"jit\(_where\)/select_n$",
    "a loop itself, its condition and its counter":
        r"/while(/cond/lt|/body/add)?$",
    "a scan stacking what it carries out, its body's call and remat's "
    "copies": r"/while/body/(dynamic_update_slice|closed_call(/remat2)?)$",
    "the zeros a layer scan's stacked carries start from":
        r"\(ProGen\)\)?/broadcast_in_dim$",
}
PROGRAMS = [(f, p) for f in FAMILIES for p in ("decode", "chunk", "finish")] \
    + [("progen", "score"), ("progen", "train"), ("progen", "train_long")]


@pytest.mark.parametrize("prog", PROGRAMS, ids=lambda k: "-".join(k))
def test_every_instruction_falls_in_a_class(prog):
    text = lowered(prog).compile().as_text()
    ops = [(o, n) for o, n in executed_instructions(text)
           if o not in NOT_ARITHMETIC]
    classes = [classify(n, CLASSES) for _, n in ops]
    stray = sorted({
        (o, n) for (o, n), c in zip(ops, classes)
        if c == UNSCOPED and not any(re.search(rx, n)
                                     for rx in ALLOWED.values())
    })
    unscoped = classes.count(UNSCOPED)
    print(f"{'-'.join(prog)}: {unscoped} of {len(ops)} instructions "
          f"({100.0 * unscoped / len(ops):.1f}%) stay unscoped")
    assert len(ops) > 20 and not stray, stray
    # the mechanisms each program is made of are there
    want = {"decode": {"project", "attend", "cache_write", "ffn", "head",
                       "sample"},
            "chunk": {"project", "attend", "cache_write", "ffn", "head"},
            "finish": {"cache_write", "sample"},
            "score": {"project", "attend", "ffn", "head"},
            "train": {"project", "attend", "ffn", "head", "optimizer"},
            "train_long": {"project", "attend", "ffn", "head", "optimizer"}}
    assert want[prog[1]] <= set(classes)


# ----- the vocabulary and what labels nothing --------------------------------


def test_every_named_scope_in_the_package_is_in_the_vocabulary():
    """A scope is ``<class>`` or ``<class>/<detail>``; no Flax module may
    take a class's name."""
    scopes, modules = [], []
    for path in (REPO / "progen_tpu").rglob("*.py"):
        for node in ast.walk(ast.parse(path.read_text())):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (isinstance(f, ast.Attribute) and f.attr == "named_scope"
                    and node.args):
                arg = node.args[0]
                assert isinstance(arg, ast.Constant), (path, ast.dump(arg))
                scopes.append((path.name, arg.value))
            for kw in node.keywords:
                if kw.arg == "name" and isinstance(kw.value, ast.Constant):
                    modules.append(kw.value.value)
    assert len(scopes) > 30
    outside = [(p, s) for p, s in scopes if s.split("/")[0] not in CLASSES]
    assert not outside, outside
    assert not set(modules) & set(CLASSES)


def test_a_program_first_called_inside_a_span_carries_no_span_name():
    """A span is a host span: a jitted program first called (so traced
    and compiled) inside one compiles to the op names it has outside."""
    from progen_tpu.telemetry.spans import Telemetry

    tel = Telemetry()

    def op_names(region):
        def f(x):  # a fresh function each time: jit caches traces by it
            with jax.named_scope("ffn"):
                return jnp.tanh(x) * 2.0

        with region():
            text = jax.jit(f).lower(jnp.ones((4,))).compile().as_text()
        return sorted(re.findall(r'op_name="([^"]*)"', text))

    outside = op_names(contextlib.nullcontext)
    inside = op_names(lambda: tel.span("serve/prefill"))
    assert outside and inside == outside
    assert not any("serve/" in n for n in inside)
    assert {classify(n, CLASSES) for n in inside
            if n.startswith("jit(")} == {"ffn"}
