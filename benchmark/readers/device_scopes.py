"""Device time by mechanism class, read from the programs' own scopes.

Every program files its device work under ``jax.named_scope``s of one
vocabulary, ``progen_tpu/telemetry/scopes.py``'s ``CLASSES``. XLA keeps
an op's name stack as its ``op_name``; in a TPU trace the metadata of an
'XLA Ops' event carries it as the ``tf_op`` stat. ``classify`` files an
op under the LAST component of that stack that is a class name (the
innermost, as ``program_stages`` names idle gaps; a transform's wrapper,
``transpose(jvp(attend))``, is looked through); an op with none is
``(unscoped)``.

The spec's ``what`` picks the reduction, over device-0 self times (an
op's duration less that of the ops nested in it):

  ``per_execution``  median, over the whole executions of the program
                     whose 'XLA Modules' event matches ``match``, of the
                     self time of its ops in ``classes``, in ms
  ``share``          self time of the ops in ``classes`` over all op self
                     time, in percent: inside the executions of
                     ``match`` where one is given, else in the whole
                     traced window

``xplane.load`` keeps names and times only, so the trace file is read
once more here: the event metadata of the first TPU plane, decoded from
the protobuf's bytes (``ProfileData`` does not expose metadata stats);
each distinct instruction is classified once. The first read writes
``<out_dir>/program_scopes.json``: per program its executions and ms per
execution of each class and of ``(unscoped)`` (median of the whole
executions and total over executions counted by device time) and each
class's five largest instructions by short name; over the window, the
ten largest unscoped instructions with each one's share of it — so that
copies XLA inserts are named and not hidden. A program from before the
vocabulary (no ``telemetry.scopes``) gives every metric nothing to read.
"""

from __future__ import annotations

import bisect
import json
import re
import statistics
import time

from benchmark import xplane

UNSCOPED = "(unscoped)"
STACK_STAT = "tf_op"
_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def vocabulary():
    """The program's classes, or None for a program without them."""
    try:
        from progen_tpu.telemetry.scopes import CLASSES
    except ImportError:
        return None
    return tuple(CLASSES)


def classify(op_name: str, classes) -> str:
    """The last component of ``op_name`` that is a class, or UNSCOPED."""
    for part in reversed(op_name.split("/")):
        while True:
            m = _WRAPPED.match(part)
            if m is None:
                break
            part = m.group(1)
        if part in classes:
            return part
    return UNSCOPED


# ----- the trace file's event metadata --------------------------------------


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, value) of a message's fields: an int for a varint,
    (start, end) for a length-delimited field, raw bytes otherwise."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} in an XSpace")
        yield key >> 3, value


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, span):
    """The value (field 2) of a map entry."""
    return next((v for f, v in _fields(buf, *span) if f == 2), None)


_OPERAND = re.compile(r"%[\w.\-]+")


def _no_type(tf_op: str) -> str:
    """'jit(f)/ffn/dot_general:' -> 'jit(f)/ffn/dot_general'."""
    head, colon, tail = tf_op.rpartition(":")
    return head if colon and "/" not in tail else tf_op


def _plane_stacks(buf, lo, hi):
    """({instruction name: name stack}, names with two) of one XPlane: its
    event metadata's ``tf_op`` stat (a string, or a reference to a stat
    metadata's name) less its trailing ``:<type>``. A custom call the
    compiler made out of an op (``ragged_dot``'s grouped products) has no
    stack of its own: it takes that of its first operand that has one, in
    its program (``program_id``)."""
    stat_names, events = {}, []
    for f, v in _fields(buf, lo, hi):
        if f == 4:  # event_metadata map<int64, XEventMetadata>
            events.append(_map_values(buf, v))
        elif f == 5:  # stat_metadata map<int64, XStatMetadata>
            meta = _map_values(buf, v)
            if meta is not None:
                sid = name = None
                for g, w in _fields(buf, *meta):
                    if g == 1:
                        sid = w
                    elif g == 2:
                        name = _text(buf, w)
                stat_names[sid] = name
    stacks, conflicts, found = {}, set(), []
    for span in events:
        if span is None:
            continue
        name, stats, got = None, [], {}
        for g, w in _fields(buf, *span):
            if g == 2:
                name = _text(buf, w)
            elif g == 5:
                stats.append(w)
        for st in stats:
            sid, value = None, None
            for h, x in _fields(buf, *st):
                if h == 1:
                    sid = x
                elif h in (3, 4):
                    value = x
                elif h == 5:
                    value = _text(buf, x)
                elif h == 7:
                    value = stat_names.get(x, "")
            if stat_names.get(sid) in (STACK_STAT, "program_id"):
                got[stat_names[sid]] = value
        stack = _no_type(str(got.get(STACK_STAT, "")))
        found.append((name, stack, got.get("program_id")))
    own = {(pid, name.split(" = ", 1)[0]): stack
           for name, stack, pid in found if "/" in stack}
    for name, stack, pid in found:
        if "/" not in stack and " custom-call(" in name:
            args = name.split(" custom-call(", 1)[1]
            stack = next((own[(pid, a)] for a in _OPERAND.findall(args)
                          if (pid, a) in own), stack)
        if not stack:
            continue
        if name in stacks and stacks[name] != stack:
            conflicts.add(name)
        stacks[name] = stack
    return stacks, conflicts


def op_stacks(path: str) -> tuple:
    """({instruction name: name stack}, names whose metadata disagree)
    of the file's first TPU plane, the device the trace dict's first
    entry is."""
    with open(path, "rb") as fh:
        buf = fh.read()
    for f, v in _fields(buf, 0, len(buf)):
        if f != 1:  # XSpace.planes
            continue
        name = next((_text(buf, w) for g, w in _fields(buf, *v) if g == 2),
                    "")
        if re.fullmatch(r"/device:TPU:\d+", name):
            return _plane_stacks(buf, *v)
    return {}, set()


# ----- the reduction ---------------------------------------------------------


def self_ops(events) -> list:
    """[(name, start, self ns)], ordered by start: ``xplane.self_times``
    with each op's start kept."""
    out, stack = [], []  # stack of [end, index into out]
    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][2] -= min(d, stack[-1][0] - s)
        out.append([name, s, d])
        stack.append([s + d, len(out) - 1])
    return [(n, s, max(d, 0.0)) for n, s, d in out]


def program_of(module_name: str) -> str:
    """'jit__decode_step(1234)' -> 'jit__decode_step'."""
    return module_name.split("(", 1)[0]


def reduce(dev: dict, window, stacks: dict, classes) -> dict:
    """Device 0's ops by class: the window's totals, per program each
    execution's, and seconds by (program, class, instruction's short
    name). ``stacks`` maps an op's name to its name stack."""
    lo, hi = window
    execs = sorted((s, s + d, program_of(n)) for n, s, d in dev["modules"])
    starts = [e[0] for e in execs]
    per_exec = [dict() for _ in execs]
    window_s, by_op = {}, {}
    seen = {}
    for name, s, d in self_ops(dev["ops"]):
        got = seen.get(name)
        if got is None:  # once per distinct instruction
            got = seen[name] = (classify(stacks.get(name, ""), classes),
                                xplane.short_name(name))
        cls, short = got
        window_s[cls] = window_s.get(cls, 0.0) + d / 1e9
        k = bisect.bisect_right(starts, s) - 1
        inside = k >= 0 and s < execs[k][1]
        if inside:
            row = per_exec[k]
            row[cls] = row.get(cls, 0.0) + d / 1e9
        key = (execs[k][2] if inside else None, cls, short)
        by_op[key] = by_op.get(key, 0.0) + d / 1e9
    programs = {}
    for (s, e, prog), row in zip(execs, per_exec):
        p = programs.setdefault(prog, {"whole": [], "durations": [],
                                       "total_s": {}})
        p["durations"].append((e - s) / 1e9)
        for cls, v in row.items():
            p["total_s"][cls] = p["total_s"].get(cls, 0.0) + v
        if lo < s and e < hi:
            p["whole"].append(row)
    unscoped = {}
    for (q, c, n), v in by_op.items():
        if q is not None:
            programs[q].setdefault("ops", {}).setdefault(c, {})[n] = v
        if c == UNSCOPED:
            unscoped[n] = unscoped.get(n, 0.0) + v
    return {"window_s": window_s, "programs": programs, "unscoped": unscoped}


def _median_ms(rows, classes) -> float:
    return 1000.0 * statistics.median(
        sum(r.get(c, 0.0) for c in classes) for r in rows)


def _top(d: dict, n: int = 10) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]


def _summary(table: dict, classes) -> dict:
    names = list(classes) + [UNSCOPED]
    out = {}
    for prog, p in sorted(table["programs"].items()):
        steps = sum(p["durations"]) / max(p["durations"])
        out[prog] = {
            "executions": len(p["whole"]),
            "steps_by_time": steps,
            "module_ms_median": 1000.0 * statistics.median(p["durations"]),
            "ms_median": ({c: _median_ms(p["whole"], [c]) for c in names}
                          if p["whole"] else None),
            "ops_ms_median": (_median_ms(p["whole"], names)
                              if p["whole"] else None),
            "ms_by_time": {c: 1000.0 * p["total_s"].get(c, 0.0) / steps
                           for c in names},
            # per class its five largest instructions, ms per execution
            # counted by device time
            "top_ms": {c: [[n, 1000.0 * v / steps] for n, v in _top(ops, 5)]
                       for c, ops in sorted(p.get("ops", {}).items())},
        }
    return out


def collect(run):
    """The reduction of this run's trace, gathered once and kept on the
    run; None where there is nothing to read."""
    if hasattr(run, "_device_scopes"):
        return run._device_scopes
    run._device_scopes = data = None
    classes = vocabulary()
    trace_dir = getattr(run, "_trace_dir", None)
    if (classes is None or run.trace is None or not run.trace["devices"]
            or trace_dir is None):
        return None
    path = xplane.find_xplane(str(trace_dir))
    if path is None:
        return None
    t0 = time.perf_counter()
    stacks, conflicts = op_stacks(path)
    if not stacks:
        return None
    table = reduce(run.trace["devices"][0], run.trace_window, stacks, classes)
    run._device_scopes = data = {"classes": classes, "table": table}
    total = sum(table["window_s"].values())
    window = (run.trace_window[1] - run.trace_window[0]) / 1e9
    out = {
        "cell": run.cell["name"], "seed": run.seed,
        "classes": list(classes),
        "traced_window_s": window,
        "op_self_s": total,
        "window_s": {c: table["window_s"].get(c, 0.0)
                     for c in list(classes) + [UNSCOPED]},
        "instructions_with_stack": len(stacks),
        "instructions_with_two_stacks": sorted(conflicts)[:20],
        "programs": _summary(table, classes),
        # [short name, seconds, % of the traced window]
        "unscoped_top": [[n, v, 100.0 * v / window]
                         for n, v in _top(table["unscoped"])],
        "reader_s": time.perf_counter() - t0,
    }
    (run.out_dir / "program_scopes.json").write_text(json.dumps(out, indent=1))
    return data


def read(run, spec):
    what = spec["what"]
    if what not in ("per_execution", "share"):
        raise ValueError(f"device_scopes: unknown reduction {what!r}")
    data = collect(run)
    if data is None:
        return None
    table, want = data["table"], spec["classes"]
    rx = re.compile(spec["match"]) if "match" in spec else None
    progs = [p for name, p in table["programs"].items()
             if rx is not None and rx.search(name)]
    if what == "per_execution":
        rows = [r for p in progs for r in p["whole"]]
        return _median_ms(rows, want) if rows else None
    if rx is None:
        totals = table["window_s"]
    else:
        totals = {}
        for p in progs:
            for c, v in p["total_s"].items():
                totals[c] = totals.get(c, 0.0) + v
    all_s = sum(totals.values())
    if not all_s:
        return None
    return 100.0 * sum(totals.get(c, 0.0) for c in want) / all_s
