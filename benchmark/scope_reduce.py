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
(``models/cnn.py::_phase``) with their scalars as stats (``n_real``, ``fold``).

Classes (``classify``; the vocabulary is docs/OBSERVABILITY.md's): an
instruction whose ``op_name`` passes through the model (``MaskedGeneticCnn``,
under ``jvp(`` forward, ``transpose(jvp(`` backward, bare in the eval program)
is ``conv_fwd``/``conv_bwd`` under a conv module (``stage*_entry|node*|exit``),
``head`` under ``head``/``Dense_*``/``Dropout_*``, else ``glue``: the
``stage{s}/mask_sum|gate|merge|pool`` scopes, and what has neither module nor
scope (relu, casts).  Outside the model it is ``rest`` (``loss``, ``optimizer``,
``gather``, ``score``, rng, loop bookkeeping).  Without ``op_name``:
``unattributed``.  Programs served from a compile-cache entry written before
the scopes existed carry the older names; the same rules then give the same
five classes, only the detail column cannot tell a mask sum from a pool.

A fusion goes to the class of the convolution it contains (XLA:TPU turns the
dense layers' dots into convolutions too, so that is ``head`` for them), else
to the class most of its instructions with an ``op_name`` have (parameters,
constants, bitcasts and tuples do not vote), else to its own ``op_name``'s,
else ``unattributed``.  Time is self time (``trace_reduce.self_times``): a
``while`` spans its body's events and keeps only what they leave.

``seconds_per_class`` is the pure arithmetic, checked on
``fixtures/scope_fixture.json``; ``read`` turns a trace file into its inputs;
``table`` is what the ``layer_metrics`` readers call: it finds the newest trace
of the run's cell, prints the tables on ``info op_class`` lines once, and
writes ``op_classes.json`` beside ``inventory.json``.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import struct
import traceback
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
CLASSES = ("conv_fwd", "conv_bwd", "glue", "head", "rest", "unattributed")
TRAIN, EVAL = "jit_train_segment", "jit_eval_fold"  # the cell's two programs
MODEL = "MaskedGeneticCnn"
CONV_MODULE = re.compile(r"^stage\d+_(entry|node\d+|exit)$")
HEAD_MODULE = re.compile(r"^(head|Dense_\d+|Dropout_\d+)$")
GLUE_SCOPES = ("mask_sum", "gate", "merge", "pool")
REST_SCOPES = re.compile(r"\b(loss|optimizer|gather|score)\b")
NO_VOTE = {"parameter", "constant", "bitcast", "tuple", "get-tuple-element"}
ANNOTATION = "gentun/"

Instruction = Dict[str, Any]  # {"opcode", "op_name", "body": [[opcode, op_name], ...]}


# -- classification: strings only ------------------------------------------------------


def classify(op_name: str) -> Tuple[str, str]:
    """(class, detail) of one instruction from its ``op_name``."""
    if not op_name:
        return "unattributed", ""
    parts = op_name.rstrip(":").split("/")
    at = next((i for i, p in enumerate(parts) if MODEL in p), None)
    if at is None:
        scope = REST_SCOPES.search(op_name)
        return "rest", scope.group(1) if scope else ("rng" if "threefry" in op_name else "other")
    backward = parts[at].startswith("transpose(")
    inside = parts[at + 1:]
    for i, part in enumerate(inside):
        if CONV_MODULE.match(part):
            return ("conv_bwd" if backward else "conv_fwd"), part
        if HEAD_MODULE.match(part):
            return "head", "head"
        if re.match(r"^stage\d+$", part) and inside[i + 1:i + 2] and inside[i + 1] in GLUE_SCOPES:
            return "glue", inside[i + 1]
    return "glue", "no_scope"


def classify_instruction(ins: Instruction) -> Tuple[str, str]:
    """The fusion rule of the module docstring."""
    body = ins.get("body") or []
    conv = next((n for op, n in body if op == "convolution" and n), None)
    if conv:
        return classify(conv)
    votes: Dict[Tuple[str, str], int] = {}
    for op, n in body:
        if n and op not in NO_VOTE:
            key = classify(n)
            votes[key] = votes.get(key, 0) + 1
    if votes:
        per_class: Dict[str, int] = {}
        for (c, _), k in votes.items():
            per_class[c] = per_class.get(c, 0) + k
        best = max(per_class, key=lambda c: (per_class[c], -CLASSES.index(c)))
        detail = max((k, d) for (c, d), k in votes.items() if c == best)[1]
        return best, detail
    return classify(ins.get("op_name", ""))


def instruction_of(hlo_line: str) -> str:
    """``%fusion.512 = bf16[...] fusion(...)`` -> ``fusion.512``."""
    return hlo_line.split(" = ", 1)[0].strip().lstrip("%")


def base_name(module: str) -> str:
    """``jit_train_segment(108976...)`` -> ``jit_train_segment``."""
    return re.sub(r"\(\d+\)$", "", module)


# -- the arithmetic ----------------------------------------------------------------------


def seconds_per_class(ops: Dict[str, Sequence[Tuple[str, float, float]]],
                      programs: Dict[str, Dict[str, Instruction]],
                      fallback: Optional[Dict[str, str]] = None) -> Dict[str, Dict[str, Any]]:
    """``ops``: program -> [(HLO line or instruction name, start, end)] of one
    device, seconds; ``programs``: program -> instruction name -> Instruction;
    ``fallback``: HLO line -> ``op_name`` for an op of a program without a
    table.  Returns program -> {"classes": {class: self seconds}, "details":
    {"class/detail": seconds}, "ops": {instruction: [class, seconds]}}."""
    out: Dict[str, Dict[str, Any]] = {}
    fallback = fallback or {}
    for program, intervals in ops.items():
        table = programs.get(program)
        classes = {c: 0.0 for c in CLASSES}
        details: Dict[str, float] = {}
        per_op: Dict[str, List[Any]] = {}
        for line, seconds in trace_reduce.self_times(intervals):
            name = instruction_of(line)
            if table is not None:
                klass, detail = classify_instruction(table[name]) if name in table else ("unattributed", "")
            else:
                klass, detail = classify(fallback.get(line, ""))
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


def _entry() -> Dict[str, Any]:
    zeros = lambda: {c: 0.0 for c in CLASSES}
    return {"runs": 0, "device_s": 0.0, "classes": zeros(), "details": {}, "flops": zeros(), "bytes": zeros(),
            "ops": {}}


def _metadata(raw: bytes) -> Tuple[Dict[str, Dict[str, Instruction]], Dict[str, Dict[str, Any]]]:
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
            elif isinstance(stats.get("Hlo Proto"), bytes) and base_name(name) in (TRAIN, EVAL):
                programs[name] = hlo_instructions(stats["Hlo Proto"])
    return programs, costs


def read(path: str) -> Dict[str, Any]:
    """Everything the readers need of one trace file, times in seconds on the
    trace's own clock; sums over devices are divided by the devices traced."""
    import jax

    with open(path, "rb") as fh:
        raw = fh.read()
    programs, costs = _metadata(raw)
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
            if i >= 0 and s < modules[i][2] and base_name(modules[i][0]) in (TRAIN, EVAL):
                ops.setdefault(modules[i][0], []).append((name, s, e))
        for name, s, e in modules:
            if base_name(name) in (TRAIN, EVAL):
                entry = per_program.setdefault(name, _entry())
                entry["runs"] += 1
                entry["device_s"] += (e - s) * share
        for program, got in seconds_per_class(ops, programs, fallback).items():
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
                 for detail in p["details"] for scope in GLUE_SCOPES)
    return {"path": path, "programs": per_program, "annotations": sorted(annotations, key=lambda a: a["start"]),
            "anchor": anchor, "runs": sorted(runs, key=lambda m: m[1]), "devices": len(devices),
            "hlo_tables": sorted(programs), "names": "scopes" if scoped else "modules_only"}


# -- what the layer_metrics readers call -------------------------------------------------------


def individuals_traced(trace: Dict[str, Any], run: Dict[str, Any]) -> int:
    """Individuals of the ``cv_call``s the trace covers: the ``n_real`` of the
    ``gentun/cv_call`` annotations; for a program that has no such annotation,
    the calls the harness counted that started inside the traced stretch."""
    calls = [a for a in trace["annotations"] if a["kind"] == "cv_call"]
    if calls:
        return int(sum(int(a["stats"].get("n_real", 0)) for a in calls))
    if not run.get("trace"):
        return 0
    lo = run["window"][0]
    hi = lo + run["trace"]["window_s"]
    return int(sum(c[2] for u in run["units"] for c in u["calls"] if lo <= c[0] < hi))


def merged(trace: Dict[str, Any], program: str) -> Dict[str, Any]:
    """The per-program entries of one base name (the deep cell runs its train
    program 16 and 2 wide) added up."""
    out: Dict[str, Any] = {"runs": 0, "device_s": 0.0, "classes": {c: 0.0 for c in CLASSES}}
    for name, entry in trace["programs"].items():
        if base_name(name) == program:
            out["runs"] += entry["runs"]
            out["device_s"] += entry["device_s"]
            for c, t in entry["classes"].items():
                out["classes"][c] += t
    return out


def table(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The newest trace of the run's cell, read; None if there is none.  The
    first call prints the tables and writes ``op_classes.json``; the result
    rides on ``run``, which every reader of a run is handed."""
    if "scope_table" in run:
        return run["scope_table"]
    path = newest_trace(run["cell"]["name"])
    try:
        run["scope_table"] = trace = read(path) if path else None
    except Exception:  # a reader that cannot read leaves its metrics out; it does not end the run
        traceback.print_exc()
        run["scope_table"] = trace = None
    if trace is None:
        return None
    trace["individuals"] = n = individuals_traced(trace, run)
    print(f"info op_class trace {os.path.relpath(path, HERE)}: {trace['devices']} device(s), {n} individuals in "
          f"{sum(a['kind'] == 'cv_call' for a in trace['annotations'])} cv_call annotations, names: "
          f"{trace['names']}, HLO tables for {len(trace['hlo_tables'])} of {len(trace['programs'])} programs")
    for name, p in sorted(trace["programs"].items()):
        busy = sum(p["classes"].values())
        print(f"info op_class {name}: {p['runs']} runs, {p['device_s']:.4f} s on XLA Modules, {busy:.4f} s in ops")
        for c in CLASSES:
            print(f"info op_class {name} {c}: {p['classes'][c]:.4f} s ({100 * p['classes'][c] / busy if busy else 0:.1f}%), "
                  f"{p['flops'][c] / 1e12:.3f} TFLOP, {p['bytes'][c] / 1e9:.2f} GB accessed")
        for d, t in sorted(p["details"].items(), key=lambda kv: -kv[1])[:24]:
            print(f"info op_class {name} detail {d}: {t:.4f} s")
        for op, (klass, t) in sorted(p["ops"].items(), key=lambda kv: -kv[1][1])[:8]:
            print(f"info op_class {name} op {op} [{klass}]: {t:.4f} s")
    _print_clocks(trace, run)
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(path)))),
                           "op_classes.json"), "w", encoding="utf-8") as fh:
        json.dump({k: trace[k] for k in ("path", "programs", "names", "individuals", "hlo_tables", "annotations")},
                  fh, indent=1, default=str)
    return trace


def _print_clocks(trace: Dict[str, Any], run: Dict[str, Any]) -> None:
    """Two ``info`` lines: how far the annotations' clock and the harness's
    anchor shift disagree, and what the fenced ``train``/``eval`` spans hold
    beyond the program's own run on the device."""
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
    for kind, program in (("train", TRAIN), ("eval", EVAL)):
        seen = [a for a in trace["annotations"] if a["kind"] == kind]
        if not seen:
            continue
        held = sum(a["end"] - a["start"] for a in seen)
        inside = sum(min(e, a["end"]) - max(s, a["start"]) for a in seen for n, s, e in trace["runs"]
                     if base_name(n) == program and s < a["end"] and e > a["start"])
        spans = [r for r in records if r["kind"] == kind and "fold" in (r.get("attrs") or {})
                 and r["t_wall"] >= run["window"][0]][:len(seen)]
        dispatch = sum(r["attrs"].get("dispatch_s", 0.0) for r in spans)
        print(f"info span_vs_device {kind}: {len(seen)} annotations hold {held:.4f} s; the program ran "
              f"{inside:.4f} s of it on XLA Modules; dispatch_s {dispatch:.4f} s; "
              f"{held - inside:.4f} s with no run of the program on the device")


def per_individual(run: Dict[str, Any], program: str, classes: Optional[Sequence[str]] = None) -> Optional[float]:
    """Device seconds of ``program`` (whole runs, or the self time of
    ``classes``) per individual of the calls traced."""
    trace = table(run)
    if not trace or not trace.get("individuals"):
        return None
    entry = merged(trace, program)
    if not entry["runs"]:
        return None
    seconds = entry["device_s"] if classes is None else sum(entry["classes"][c] for c in classes)
    return seconds / trace["individuals"]
