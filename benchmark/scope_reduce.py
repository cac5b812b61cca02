"""From a profiler trace to device seconds per op class per program, and the
program's own spans on the trace's clock.  One file, one clock, no wall shift.

What a v5e trace carries (looked at by hand, PR 25; ``trace_reduce.py`` has the
planes and lines): an "XLA Ops" event is named by its HLO line *without*
``metadata={...}``; the name stack (``op_name``) is not on the event.  It is
in two other places of the same ``.xplane.pb``: the event's *metadata* entry
has the stats ``tf_op`` (the instruction's own ``op_name``), ``hlo_category``,
``flops``, ``bytes_accessed`` and ``program_id`` -- ``jax.profiler.ProfileData``
does not show metadata stats, so they are read from the protobuf's wire format
here -- and the plane ``/host:metadata`` holds, per XLA module, the whole
optimised ``HloProto`` (stat "Hlo Proto"), every instruction of every fused
computation with its ``op_name``.  "XLA Modules" has one event per program run,
named ``jit_<function>(<fingerprint>)``, the name the HLO proto is filed under.
The host plane's "python" line has the program's ``gentun/<kind>`` annotations
(``models/evaluation.py::phase``) with their scalars as stats (``n_real``, ``fold``).

This file is the trace: the protobuf, matching events to modules, self time,
the fusion vote, ``per_individual``.  What is the model -- which programs to
read, the classes, and the rule from an ``op_name`` to a class -- is the
``rules`` every function here takes: one object of the run's model family
(``families/<family>/scope_rules.py``, whose docstring has the vocabulary),
handed over by that family's readers under ``layer_metrics/``.  It has
``CLASSES`` (``unattributed``, the class of an op no rule can place, among
them), ``PROGRAMS`` (base names of the jitted programs to read),
``classify(op_name) -> (class, detail)``, ``SCOPED_DETAILS``, ``SPAN_PROGRAMS``,
``SPAN_ATTR`` and ``CALL_ANNOTATION``.

A fusion goes to the class of the convolution it contains (XLA:TPU turns
dense layers' dots into convolutions too), else to the class most of its
instructions with an ``op_name`` have (parameters, constants, bitcasts and
tuples do not vote), else to its own ``op_name``'s, else ``unattributed``.
Time is self time (``trace_reduce.self_times``): a ``while`` spans its body's
events and keeps only what they leave.

``seconds_per_class`` is the pure arithmetic, checked on
``fixtures/scope_fixture.json``; ``read`` turns a trace file into its inputs;
``table`` is what the ``layer_metrics`` readers call: it finds the newest trace
of the run's cell, prints the tables on ``info op_class`` lines once, and
writes ``op_classes.json`` beside ``inventory.json``.
"""

from __future__ import annotations

import bisect
import functools
import glob
import importlib.util
import json
import os
import re
import struct
import traceback
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
UNATTRIBUTED = "unattributed"  # an op no rule can place: in every family's CLASSES
NO_VOTE = {"parameter", "constant", "bitcast", "tuple", "get-tuple-element"}
ANNOTATION = "gentun/"

Instruction = Dict[str, Any]  # {"opcode", "op_name", "body": [[opcode, op_name], ...]}

# -- callers older than the families ---------------------------------------------------------
#
# ``tests/test_tracing_scopes.py`` (PR 25) calls this module as it was when it
# held the Genetic-CNN's rules itself: ``scope_reduce.CLASSES``, ``.TRAIN``,
# ``.EVAL`` and every function without ``rules``.  A benchmark PR may not edit
# a test outside ``benchmark/``, so for those calls alone ``rules=None`` means
# the rules of ``LEGACY_FAMILY``, loaded by path.  Nothing under ``benchmark/``
# leans on it; it goes when that test hands the rules over (PERF.md §7).

LEGACY_FAMILY = "genetic_cnn"


@functools.lru_cache(maxsize=None)
def _legacy_rules():
    path = os.path.join(HERE, "families", LEGACY_FAMILY, "scope_rules.py")
    spec = importlib.util.spec_from_file_location(f"bench_scope_rules_{LEGACY_FAMILY}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def __getattr__(name: str):
    if name in ("CLASSES", "TRAIN", "EVAL"):
        return getattr(_legacy_rules(), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# -- classification: strings only ------------------------------------------------------


def classify(op_name: str, rules=None) -> Tuple[str, str]:
    """(class, detail) of one instruction from its ``op_name``, by the family's rule."""
    return (rules or _legacy_rules()).classify(op_name)


def classify_instruction(ins: Instruction, rules=None) -> Tuple[str, str]:
    """The fusion rule of the module docstring."""
    rules = rules or _legacy_rules()
    body = ins.get("body") or []
    conv = next((n for op, n in body if op == "convolution" and n), None)
    if conv:
        return rules.classify(conv)
    votes: Dict[Tuple[str, str], int] = {}
    for op, n in body:
        if n and op not in NO_VOTE:
            key = rules.classify(n)
            votes[key] = votes.get(key, 0) + 1
    if votes:
        per_class: Dict[str, int] = {}
        for (c, _), k in votes.items():
            per_class[c] = per_class.get(c, 0) + k
        best = max(per_class, key=lambda c: (per_class[c], -rules.CLASSES.index(c)))
        detail = max((k, d) for (c, d), k in votes.items() if c == best)[1]
        return best, detail
    return rules.classify(ins.get("op_name", ""))


def instruction_of(hlo_line: str) -> str:
    """``%fusion.512 = bf16[...] fusion(...)`` -> ``fusion.512``."""
    return hlo_line.split(" = ", 1)[0].strip().lstrip("%")


def base_name(module: str) -> str:
    """``jit_train_segment(108976...)`` -> ``jit_train_segment``."""
    return re.sub(r"\(\d+\)$", "", module)


# -- the arithmetic ----------------------------------------------------------------------


def seconds_per_class(ops: Dict[str, Sequence[Tuple[str, float, float]]],
                      programs: Dict[str, Dict[str, Instruction]],
                      fallback: Optional[Dict[str, str]] = None, rules=None) -> Dict[str, Dict[str, Any]]:
    """``ops``: program -> [(HLO line or instruction name, start, end)] of one
    device, seconds; ``programs``: program -> instruction name -> Instruction;
    ``fallback``: HLO line -> ``op_name`` for an op of a program without a
    table.  Returns program -> {"classes": {class: self seconds}, "details":
    {"class/detail": seconds}, "ops": {instruction: [class, seconds]}}."""
    out: Dict[str, Dict[str, Any]] = {}
    fallback = fallback or {}
    rules = rules or _legacy_rules()
    for program, intervals in ops.items():
        table = programs.get(program)
        classes = {c: 0.0 for c in rules.CLASSES}
        details: Dict[str, float] = {}
        per_op: Dict[str, List[Any]] = {}
        for line, seconds in trace_reduce.self_times(intervals):
            name = instruction_of(line)
            if table is not None:
                klass, detail = classify_instruction(table[name], rules) if name in table else (UNATTRIBUTED, "")
            else:
                klass, detail = rules.classify(fallback.get(line, ""))
            classes[klass] += seconds
            key = f"{klass}/{detail}" if detail else klass
            details[key] = details.get(key, 0.0) + seconds
            per_op.setdefault(name, [klass, 0.0])[1] += seconds
        out[program] = {"classes": classes, "details": details, "ops": per_op}
    return out


# -- the protobuf wire format: what ProfileData does not show ------------------------------


def _varint(b: bytes, i: int) -> Tuple[int, int]:
    r = s = 0
    while True:
        c = b[i]
        i += 1
        r |= (c & 0x7F) << s
        s += 7
        if not c & 0x80:
            return r, i


def _fields(b: bytes) -> Iterator[Tuple[int, int, Any]]:
    """(field number, wire type, value) of one message; nested messages come
    as bytes."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        f, w = key >> 3, key & 7
        if w == 0:
            v, i = _varint(b, i)
        elif w == 1:
            v, i = b[i:i + 8], i + 8
        elif w == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif w == 5:
            v, i = b[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {w}")
        yield f, w, v


def _planes(raw: bytes) -> Iterator[Tuple[str, Dict[int, str], List[bytes]]]:
    """(name, stat id -> stat name, event metadata messages) per XPlane.
    XSpace.planes=1; XPlane: name=2, event_metadata=4, stat_metadata=5 (maps:
    key=1, value=2); XStatMetadata.name=2."""
    for f, _, plane in _fields(raw):
        if f != 1:
            continue
        name, stat_names, metadata = "", {}, []
        for f2, _, v in _fields(plane):
            if f2 == 2:
                name = v.decode()
            elif f2 == 5:
                entry = {a: c for a, _, c in _fields(v)}
                stat_names[entry[1]] = next((c.decode() for a, _, c in _fields(entry[2]) if a == 2), "")
            elif f2 == 4:
                metadata.append(next(c for a, _, c in _fields(v) if a == 2))
        yield name, stat_names, metadata


def _event_metadata(message: bytes, stat_names: Dict[int, str]) -> Tuple[str, Dict[str, Any]]:
    """XEventMetadata: name=2, stats=5; XStat: metadata_id=1, double=2,
    uint64=3, int64=4, str=5, bytes=6."""
    name, stats = "", {}
    for f, _, v in _fields(message):
        if f == 2:
            name = v.decode(errors="replace")
        elif f == 5:
            stat = {a: c for a, _, c in _fields(v)}
            key = stat_names.get(stat.get(1))
            value = next((stat[k] for k in (3, 4, 5, 6) if k in stat), None)
            if 2 in stat:
                value = struct.unpack("<d", stat[2])[0]
            stats[key] = value
    return name, stats


def hlo_instructions(hlo_proto: bytes) -> Dict[str, Instruction]:
    """Instruction name -> Instruction of one ``HloProto``.  HloProto.hlo_module=1;
    HloModuleProto.computations=3; HloComputationProto: instructions=2, id=5;
    HloInstructionProto: name=1, opcode=2, metadata=7 (OpMetadata.op_name=2),
    called_computation_ids=38."""
    module = next(v for f, _, v in _fields(hlo_proto) if f == 1)
    computations: Dict[int, List[Dict[str, Any]]] = {}
    for f, _, comp in _fields(module):
        if f != 3:
            continue
        cid, instructions = None, []
        for f2, _, v in _fields(comp):
            if f2 == 5:
                cid = v
            elif f2 == 2:
                ins: Dict[str, Any] = {"name": "", "opcode": "", "op_name": "", "called": []}
                for f3, w3, v3 in _fields(v):
                    if f3 == 1:
                        ins["name"] = v3.decode()
                    elif f3 == 2:
                        ins["opcode"] = v3.decode()
                    elif f3 == 7:
                        ins["op_name"] = next((c.decode() for a, _, c in _fields(v3) if a == 2), "")
                    elif f3 == 38 and w3 == 0:
                        ins["called"].append(v3)
                    elif f3 == 38:  # packed
                        i = 0
                        while i < len(v3):
                            called, i = _varint(v3, i)
                            ins["called"].append(called)
                instructions.append(ins)
        computations[cid] = instructions

    def body(ins: Dict[str, Any], depth: int = 0) -> List[List[str]]:
        """A fusion's instructions; XLA:TPU nests fusions, those are opened too."""
        found: List[List[str]] = []
        for inner in computations.get(ins["called"][0], []):
            if inner["opcode"] == "fusion" and inner["called"] and depth < 4:
                found += body(inner, depth + 1)
            else:
                found.append([inner["opcode"], inner["op_name"]])
        return found

    out: Dict[str, Instruction] = {}
    for instructions in computations.values():
        for ins in instructions:
            entry: Instruction = {"opcode": ins["opcode"], "op_name": ins["op_name"]}
            if ins["opcode"] == "fusion" and ins["called"]:
                entry["body"] = body(ins)
            out[ins["name"]] = entry
    return out


# -- reading a trace ------------------------------------------------------------------------


def newest_trace(cell: str) -> Optional[str]:
    """The newest trace of any seed of ``cell`` under ``out/trace``."""
    found = [trace_reduce.newest_xplane(d) for d in glob.glob(os.path.join(HERE, "out", "trace", cell + ".*"))]
    return max(filter(None, found), key=os.path.getmtime, default=None)


def _entry(rules) -> Dict[str, Any]:
    zeros = lambda: {c: 0.0 for c in rules.CLASSES}
    return {"runs": 0, "device_s": 0.0, "classes": zeros(), "details": {}, "flops": zeros(), "bytes": zeros(),
            "ops": {}}


def _metadata(raw: bytes, rules) -> Tuple[Dict[str, Dict[str, Instruction]], Dict[str, Dict[str, Any]]]:
    """(program -> instruction table from its "Hlo Proto", HLO line -> the
    stats of its event metadata on a device plane)."""
    programs: Dict[str, Dict[str, Instruction]] = {}
    costs: Dict[str, Dict[str, Any]] = {}
    for plane, stat_names, metadata in _planes(raw):
        on_device = bool(trace_reduce.DEVICE_PLANE.match(plane))
        if plane != "/host:metadata" and not on_device:
            continue
        for message in metadata:
            name, stats = _event_metadata(message, stat_names)
            if on_device:
                if "tf_op" in stats or "flops" in stats:
                    costs.setdefault(name, stats)
            elif isinstance(stats.get("Hlo Proto"), bytes) and base_name(name) in rules.PROGRAMS:
                programs[name] = hlo_instructions(stats["Hlo Proto"])
    return programs, costs


def read(path: str, rules=None) -> Dict[str, Any]:
    """Everything the readers need of one trace file, times in seconds on the
    trace's own clock; sums over devices are divided by the devices traced."""
    import jax

    rules = rules or _legacy_rules()
    with open(path, "rb") as fh:
        raw = fh.read()
    programs, costs = _metadata(raw, rules)
    fallback = {line: (stats.get("tf_op") or b"").decode(errors="replace") for line, stats in costs.items()}
    data = jax.profiler.ProfileData.from_serialized_xspace(raw)
    devices, annotations, anchor = {}, [], None
    for plane in data.planes:
        for line in plane.lines:
            if trace_reduce.DEVICE_PLANE.match(plane.name):
                if line.name in (trace_reduce.OPS_LINE, trace_reduce.MODULES_LINE):
                    devices.setdefault(plane.name, {})[line.name] = [
                        (e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9) for e in line.events]
            elif plane.name.startswith("/host:"):
                for e in line.events:
                    if e.name.startswith(ANNOTATION):
                        annotations.append({"kind": e.name[len(ANNOTATION):], "start": e.start_ns / 1e9,
                                            "end": (e.start_ns + e.duration_ns) / 1e9, "stats": dict(e.stats)})
                    elif e.name == trace_reduce.ANCHOR and anchor is None:
                        anchor = e.start_ns / 1e9
    per_program: Dict[str, Dict[str, Any]] = {}
    runs: List[Tuple[str, float, float]] = []
    share = 1.0 / len(devices) if devices else 0.0
    for lines in devices.values():
        modules = sorted(lines.get(trace_reduce.MODULES_LINE, []), key=lambda m: m[1])
        runs += modules
        starts = [m[1] for m in modules]
        ops: Dict[str, List[Tuple[str, float, float]]] = {}
        for name, s, e in lines.get(trace_reduce.OPS_LINE, []):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < modules[i][2] and base_name(modules[i][0]) in rules.PROGRAMS:
                ops.setdefault(modules[i][0], []).append((name, s, e))
        for name, s, e in modules:
            if base_name(name) in rules.PROGRAMS:
                entry = per_program.setdefault(name, _entry(rules))
                entry["runs"] += 1
                entry["device_s"] += (e - s) * share
        for program, got in seconds_per_class(ops, programs, fallback, rules).items():
            entry = per_program[program]
            for c, t in got["classes"].items():
                entry["classes"][c] += t * share
            for d, t in got["details"].items():
                entry["details"][d] = entry["details"].get(d, 0.0) + t * share
            for name, (klass, t) in got["ops"].items():
                entry["ops"].setdefault(name, [klass, 0.0])[1] += t * share
            for line, _, _ in ops[program]:
                stats = costs.get(line) or {}
                # a while's flops are its body's, already counted op by op
                if stats.get("hlo_category") not in (b"while", b"call", b"conditional"):
                    klass = got["ops"][instruction_of(line)][0]
                    entry["flops"][klass] += float(stats.get("flops") or 0) * share
                    entry["bytes"][klass] += float(stats.get("bytes_accessed") or 0) * share
    scoped = any("/" + scope in detail for p in per_program.values()
                 for detail in p["details"] for scope in rules.SCOPED_DETAILS)
    return {"path": path, "programs": per_program, "annotations": sorted(annotations, key=lambda a: a["start"]),
            "anchor": anchor, "runs": sorted(runs, key=lambda m: m[1]), "devices": len(devices),
            "hlo_tables": sorted(programs), "names": "scopes" if scoped else "modules_only"}


# -- what the layer_metrics readers call -------------------------------------------------------


def individuals_traced(trace: Dict[str, Any], run: Dict[str, Any], rules=None) -> int:
    """Individuals of the evaluator calls the trace covers: the counting stat
    of the family's call annotation (the ``n_real`` of the ``gentun/cv_call``
    annotations); for a program that has no such annotation, the calls the
    harness counted that started inside the traced stretch."""
    kind, stat = (rules or _legacy_rules()).CALL_ANNOTATION
    calls = [a for a in trace["annotations"] if a["kind"] == kind]
    if calls:
        return int(sum(int(a["stats"].get(stat, 0)) for a in calls))
    if not run.get("trace"):
        return 0
    lo = run["window"][0]
    hi = lo + run["trace"]["window_s"]
    return int(sum(c[2] for u in run["units"] for c in u["calls"] if lo <= c[0] < hi))


def merged(trace: Dict[str, Any], program: str) -> Dict[str, Any]:
    """The per-program entries of one base name (the deep cell runs its train
    program 16 and 2 wide) added up."""
    out: Dict[str, Any] = {"runs": 0, "device_s": 0.0, "classes": {}}
    for name, entry in trace["programs"].items():
        if base_name(name) == program:
            out["runs"] += entry["runs"]
            out["device_s"] += entry["device_s"]
            for c, t in entry["classes"].items():
                out["classes"][c] = out["classes"].get(c, 0.0) + t
    return out


def table(run: Dict[str, Any], rules) -> Optional[Dict[str, Any]]:
    """The newest trace of the run's cell, read by the family's ``rules``; None
    if there is none.  The first call prints the tables and writes
    ``op_classes.json``; the result rides on ``run``, which every reader of a
    run is handed."""
    if "scope_table" in run:
        return run["scope_table"]
    path = newest_trace(run["cell"]["name"])
    try:
        run["scope_table"] = trace = read(path, rules) if path else None
    except Exception:  # a reader that cannot read leaves its metrics out; it does not end the run
        traceback.print_exc()
        run["scope_table"] = trace = None
    if trace is None:
        return None
    trace["individuals"] = n = individuals_traced(trace, run, rules)
    call = rules.CALL_ANNOTATION[0]
    print(f"info op_class trace {os.path.relpath(path, HERE)}: {trace['devices']} device(s), {n} individuals in "
          f"{sum(a['kind'] == call for a in trace['annotations'])} {call} annotations, names: "
          f"{trace['names']}, HLO tables for {len(trace['hlo_tables'])} of {len(trace['programs'])} programs")
    for name, p in sorted(trace["programs"].items()):
        busy = sum(p["classes"].values())
        print(f"info op_class {name}: {p['runs']} runs, {p['device_s']:.4f} s on XLA Modules, {busy:.4f} s in ops")
        for c in rules.CLASSES:
            print(f"info op_class {name} {c}: {p['classes'][c]:.4f} s ({100 * p['classes'][c] / busy if busy else 0:.1f}%), "
                  f"{p['flops'][c] / 1e12:.3f} TFLOP, {p['bytes'][c] / 1e9:.2f} GB accessed")
        for d, t in sorted(p["details"].items(), key=lambda kv: -kv[1])[:24]:
            print(f"info op_class {name} detail {d}: {t:.4f} s")
        for op, (klass, t) in sorted(p["ops"].items(), key=lambda kv: -kv[1][1])[:8]:
            print(f"info op_class {name} op {op} [{klass}]: {t:.4f} s")
    _print_clocks(trace, run, rules)
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(path)))),
                           "op_classes.json"), "w", encoding="utf-8") as fh:
        json.dump({k: trace[k] for k in ("path", "programs", "names", "individuals", "hlo_tables", "annotations")},
                  fh, indent=1, default=str)
    return trace


def _print_clocks(trace: Dict[str, Any], run: Dict[str, Any], rules) -> None:
    """``info`` lines: how far the annotations' clock and the harness's anchor
    shift disagree, and what the fenced span of each program (the family's
    ``SPAN_PROGRAMS``: ``train``/``eval``) holds beyond the program's own run
    on the device."""
    records = [r for r in run["records"] if r.get("type") == "span"]
    if trace["anchor"] is not None and trace["annotations"]:
        shift = run["window"][0] - trace["anchor"]  # the harness anchors as it opens the window
        worst, matched = 0.0, 0
        for kind in {a["kind"] for a in trace["annotations"]}:
            seen = [a for a in trace["annotations"] if a["kind"] == kind]
            spans = sorted((r for r in records if r["kind"] == kind and r["t_wall"] >= run["window"][0] - 0.5),
                           key=lambda r: r["t_wall"])
            for a, r in zip(seen, spans):
                worst, matched = max(worst, abs(a["start"] + shift - r["t_wall"])), matched + 1
        print(f"info clocks: {matched} gentun annotations against their span records through the anchor shift: "
              f"largest disagreement {1e3 * worst:.3f} ms")
    for kind, program in rules.SPAN_PROGRAMS:
        seen = [a for a in trace["annotations"] if a["kind"] == kind]
        if not seen:
            continue
        held = sum(a["end"] - a["start"] for a in seen)
        inside = sum(min(e, a["end"]) - max(s, a["start"]) for a in seen for n, s, e in trace["runs"]
                     if base_name(n) == program and s < a["end"] and e > a["start"])
        spans = [r for r in records if r["kind"] == kind and rules.SPAN_ATTR in (r.get("attrs") or {})
                 and r["t_wall"] >= run["window"][0]][:len(seen)]
        dispatch = sum(r["attrs"].get("dispatch_s", 0.0) for r in spans)
        print(f"info span_vs_device {kind}: {len(seen)} annotations hold {held:.4f} s; the program ran "
              f"{inside:.4f} s of it on XLA Modules; dispatch_s {dispatch:.4f} s; "
              f"{held - inside:.4f} s with no run of the program on the device")


def per_individual(run: Dict[str, Any], rules, program: str,
                   classes: Optional[Sequence[str]] = None) -> Optional[float]:
    """Device seconds of ``program`` (whole runs, or the self time of
    ``classes``) per individual of the calls traced."""
    trace = table(run, rules)
    if not trace or not trace.get("individuals"):
        return None
    entry = merged(trace, program)
    if not entry["runs"]:
        return None
    seconds = entry["device_s"] if classes is None else sum(entry["classes"][c] for c in classes)
    return seconds / trace["individuals"]
