"""Check ``BENCHMARK.json`` before any chip call: ``python benchmark/check_manifest.py``.

Every name, unit and layer against the character rules; every ``moves``
names an end-to-end metric that every cell of the per-layer metric reports;
every file a cell needs exists and ``layer_metrics/`` holds no reader that no
entry names; every configuration states its model ``family``, whose directory
``families/<family>/`` lies under ``paths`` and holds the family's files and
the four functions the harness calls; and
``trace_reduce.reduce`` gives the fixture's expected busy time, idle share,
top ops and named gaps.  The last line counts cells, per-layer metrics and the
manifest's bytes (the contract's limits: 24, 128, 65,536).
"""

from __future__ import annotations

import ast
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
FAMILY_FILES = ("family.py", "reference.py")
FAMILY_FUNCTIONS = ("make_inputs", "program_side", "after_window", "window_checks")


def top_level_names(path: str) -> set:
    """Names a module binds at its top level, read without importing it (a
    family's files import jax and the program): defs, imports, assignments."""
    with open(path, encoding="utf-8") as fh:
        tree = ast.parse(fh.read())
    names = set()
    for node in tree.body:
        if isinstance(node, ast.FunctionDef):
            names.add(node.name)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            names |= {(a.asname or a.name).split(".")[0] for a in node.names}
        elif isinstance(node, ast.Assign):
            names |= {t.id for t in node.targets if isinstance(t, ast.Name)}
    return names


def check_family(config_name: str, config_file: str, under_paths) -> list:
    """The configuration's ``family``: stated (there is no default), one token,
    a directory under ``paths`` with the contract's files and functions."""
    with open(config_file, encoding="utf-8") as fh:
        family = json.load(fh).get("family")
    if not isinstance(family, str) or not NAME.match(family):
        return [f"config {config_name}: its file states no 'family' that is one token (got {family!r})"]
    folder = os.path.join(HERE, "families", family)
    relative = os.path.relpath(folder, ROOT).replace(os.sep, "/")
    if not os.path.isdir(folder):
        return [f"config {config_name}: family {family!r} has no directory {relative}/"]
    bad = []
    if not under_paths(relative + "/"):
        bad.append(f"config {config_name}: family directory {relative}/ is outside paths")
    missing = [f for f in FAMILY_FILES if not os.path.isfile(os.path.join(folder, f))]
    if missing:
        return bad + [f"config {config_name}: family {family!r} lacks {', '.join(missing)}"]
    lacking = [f for f in FAMILY_FUNCTIONS if f not in top_level_names(os.path.join(folder, "family.py"))]
    if lacking:
        bad.append(f"config {config_name}: {relative}/family.py lacks {', '.join(lacking)}")
    return bad


def check(manifest) -> list:
    bad = []
    need = lambda ok, msg: bad.append(msg) if not ok else None
    need(set(manifest) == {"command", "paths", "run_seconds", "configs", "workloads",
                           "end_to_end", "per_layer"}, f"top-level keys: {sorted(manifest)}")
    need(isinstance(manifest["run_seconds"], int) and 1 <= manifest["run_seconds"] <= 51,
         "run_seconds must be a whole number from 1 to 51")
    under_paths = lambda f: any(f.startswith(p.rstrip("/") + "/") for p in manifest["paths"])
    configs = {}
    for c in manifest["configs"]:
        need(set(c) == {"name", "source", "file", "reduced", "why"}, f"config keys: {sorted(c)}")
        need(NAME.match(c["name"]), f"config name {c['name']!r}")
        need(LINE.match(c["source"]) and LINE.match(c["why"]), f"config {c['name']}: source/why line")
        need(under_paths(c["file"]) and os.path.isfile(os.path.join(ROOT, c["file"])),
             f"config {c['name']}: file {c['file']} missing or outside paths")
        need(len(c["reduced"]) <= 16 and all(NAME.match(k) for k in c["reduced"]),
             f"config {c['name']}: reduced keys")
        if os.path.isfile(os.path.join(ROOT, c["file"])):
            bad += check_family(c["name"], os.path.join(ROOT, c["file"]), under_paths)
        configs[c["name"]] = c
    cells, pairs = {}, set()
    for w in manifest["workloads"]:
        need(set(w) == {"name", "config", "traffic", "chips", "why"}, f"cell keys: {sorted(w)}")
        for key in ("name", "config", "traffic"):
            need(NAME.match(w[key]), f"cell {w['name']}: {key} {w[key]!r} is not one token")
        need(LINE.match(w["why"]), f"cell {w['name']}: why must be 1-200 characters on one line")
        need(w["chips"] in (1, 4), f"cell {w['name']}: chips")
        need(w["config"] in configs, f"cell {w['name']}: unknown config {w['config']}")
        need((w["config"], w["traffic"]) not in pairs, f"cell {w['name']}: pair appears twice")
        pairs.add((w["config"], w["traffic"]))
        mix = os.path.join(HERE, "traffic", w["traffic"] + ".json")
        need(os.path.isfile(mix), f"cell {w['name']}: no traffic file {mix}")
        if os.path.isfile(mix):
            kind = json.load(open(mix, encoding="utf-8"))["kind"]
            need(os.path.isfile(os.path.join(HERE, "traffic_kinds", kind + ".py")),
                 f"cell {w['name']}: no traffic kind {kind}")
        cells[w["name"]] = w
    need(sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 4), "too many 4-chip cells")
    need({c for c in configs} == {w["config"] for w in cells.values()}, "a configuration no cell uses")
    reports = {}  # end-to-end metric -> cells that report it
    names = set()
    for m in manifest["end_to_end"]:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"},
             f"end_to_end keys: {sorted(m)}")
        need(NAME.match(m["name"]) and m["name"] not in names, f"metric name {m['name']!r}")
        names.add(m["name"])
        need(UNIT.match(m["unit"]), f"metric {m['name']}: unit {m['unit']!r}")
        need(m["better"] in ("lower", "higher"), f"metric {m['name']}: better")
        need(m["source"] in ("host_clock", "device_trace"), f"metric {m['name']}: source")
        need(0.01 <= m["bound"] <= 0.1, f"metric {m['name']}: bound {m['bound']}")
        listed = m.get("workloads", list(cells))
        need(all(c in cells for c in listed), f"metric {m['name']}: unknown cell")
        reports[m["name"]] = set(listed)
    need("setup_s" in reports and reports["setup_s"] == set(cells), "every cell reports setup_s")
    for cell in cells:
        need(sum(cell in v for k, v in reports.items() if k != "setup_s") >= 1,
             f"cell {cell}: no end-to-end metric besides setup_s")
    layered = set()
    for m in manifest["per_layer"]:
        need(set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"},
             f"per_layer keys: {sorted(m)}")
        need(NAME.match(m["name"]) and m["name"] not in names, f"metric name {m['name']!r}")
        names.add(m["name"])
        need(NAME.match(m["layer"]), f"metric {m['name']}: layer {m['layer']!r} is not one token")
        need(UNIT.match(m["unit"]), f"metric {m['name']}: unit {m['unit']!r}")
        need(m["better"] in ("lower", "higher") and m["source"] in SOURCES, f"metric {m['name']}")
        need(os.path.isfile(os.path.join(HERE, "layer_metrics", m["name"] + ".py")),
             f"metric {m['name']}: no reader layer_metrics/{m['name']}.py")
        need(m["moves"] in reports, f"metric {m['name']}: moves {m['moves']!r} is no end-to-end metric")
        listed = set(m.get("workloads", reports.get(m["moves"], ())))
        need(listed <= reports.get(m["moves"], set()),
             f"metric {m['name']}: a cell of it does not report {m['moves']}")
        layered |= listed
    need(layered == set(cells), "a cell without a per-layer metric")
    readers = {f[:-3] for f in os.listdir(os.path.join(HERE, "layer_metrics")) if f.endswith(".py")}
    need(readers <= names, f"layer_metrics/ holds readers no entry names: {sorted(readers - names)}")
    need(len(json.dumps(manifest)) < 64 * 1024, "manifest over 64 KiB")
    return bad


def check_trace_fixture() -> list:
    import trace_reduce

    f = json.load(open(os.path.join(HERE, "fixtures", "trace_fixture.json"), encoding="utf-8"))
    got = trace_reduce.reduce({d: [tuple(o) for o in ops] for d, ops in f["device_ops"].items()},
                              [tuple(s) for s in f["host_spans"]], tuple(f["window"]))
    bad = []
    for key, want in f["expect"].items():
        same = (got[key] == want if isinstance(want, list) else abs(got[key] - want) < 1e-9)
        if not same:
            bad.append(f"trace fixture: {key} is {got[key]}, expected {want}")
    return bad


def main() -> int:
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8"))
    bad = check(manifest) + check_trace_fixture()
    for line in bad:
        print("check_manifest:", line)
    print("check_manifest:", "FAILED" if bad else
          f"ok ({len(manifest['workloads'])} cells, {len(manifest['per_layer'])} per-layer metrics, "
          f"{len(json.dumps(manifest))} bytes)")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())
