"""The benchmark: one cell, one process, one chip.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name from ``BENCHMARK.json``: its
configuration (``configs/<config>.json``), the configuration's model family
(``families/<family>/``: inputs from the seed, the plain reference, the
comparison that decides ``correct``, the counts of operations), its traffic
mix (``traffic/<traffic>.json``, which names its kind), the kind's module
(``traffic_kinds/<kind>.py``) and, in a traced run, one reader per per-layer
metric (``layer_metrics/<metric>.py``).  The harness itself names no model,
no gene and no data shape.  See ``README.md`` beside this file.

Set-up has four parts, read off the clock between its statements, printed on
the ``set-up:`` line and handed to the readers as ``reading["setup"]``
(``layer_metrics/setup_warmup_s.py`` reads the third; the manifest has no room
for the others yet): the backend (``T_START`` to ``require_device`` returning),
the inputs (the compile cache's directory, the family's files,
``family.make_inputs`` from ``--seed``), the warm-up (``kind.setup``: every
program's first call and one pass over the pool) and the program's half of the
correctness check (``family.program_side`` up to the window's opening; in a
traced run ``jax.profiler.start_trace`` lies here).  They sum to ``setup_s``,
which ends where the window opens.  The window starts a new unit of work only
while less than ``--seconds`` have passed and closes when the unit in flight
returns; every rate divides by the time that really passed.  The reference's
half of the check runs after the window, outside ``setup_s`` and after the peak
memory has been read.  Each number compared is printed beside its limit on a
``check`` line; the last line of standard output is the result object and holds
the contract's keys only.

``--rehearsal`` runs the same code at the configuration's ``rehearsal`` sizes
on whatever jax comes up on; its result says so and is never ``correct``.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse
import importlib
import importlib.util
import json
import math
import os
import sys
import traceback
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
OUT_DIR = os.path.join(HERE, "out")
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)


def load_json(*parts: str) -> Any:
    with open(os.path.join(*parts), encoding="utf-8") as fh:
        return json.load(fh)


def load_module(folder: str, name: str):
    path = os.path.join(HERE, folder, name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_{folder}_{name}".replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_family(name: str):
    """The model family of the run's configuration: ``families/<name>/family.py``.

    One process runs one cell, so one family: its directory goes first on
    ``sys.path``, so that its files import each other by bare name (the
    comparison its copy of the reference) and the family's readers under
    ``layer_metrics/`` import its counts and rules the same way (``import
    flops``, ``import scope_rules``).  ``family.py`` there is what
    the harness calls: ``make_inputs``, ``program_side``, ``after_window``
    and ``window_checks`` (README.md, "A model family")."""
    folder = os.path.join(HERE, "families", name)
    if not os.path.isfile(os.path.join(folder, "family.py")):
        raise SystemExit(f"no model family {name!r}: {os.path.join(folder, 'family.py')} is missing")
    loaded = sys.modules.get("family")
    if loaded is not None and os.path.dirname(os.path.abspath(loaded.__file__)) != folder:
        raise SystemExit(f"this process has loaded the family at {loaded.__file__}; one process runs one family")
    if folder not in sys.path:
        sys.path.insert(0, folder)
    return importlib.import_module("family")


def merge(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


# -- the cell, found by name --------------------------------------------------


def load_cell(workload: str, rehearsal: bool = False):
    """(manifest, cell, configuration, mix) of one cell, found by name."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no cell {workload!r} in BENCHMARK.json")
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = load_json(ROOT, entry["file"])
    if rehearsal:
        config = merge(config, config.get("rehearsal", {}))
    return manifest, cell, config, load_json(HERE, "traffic", cell["traffic"] + ".json")


# -- what jax itself says about compiles ---------------------------------------------


class Monitor:
    """jax.monitoring listeners: programs asked for, found in the persistent
    cache, compiled by the backend; each with its wall time."""

    def __init__(self) -> None:
        import jax

        self.requests: List[float] = []
        self.hits: List[float] = []
        self.compiles: List[float] = []
        self.window = (math.inf, math.inf)
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests.append(time.time())
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits.append(time.time())

    def _duration(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append(time.time())

    def requests_in_window(self) -> int:
        return sum(1 for t in self.requests if self.window[0] <= t <= self.window[1])


class Records:
    """Telemetry run sink: keeps every span and event record."""

    def __init__(self) -> None:
        self.items: List[Dict[str, Any]] = []

    def record(self, rec: Dict[str, Any]) -> None:
        self.items.append(rec)


class Ctx:
    """What a traffic kind and the checks get to see."""

    def __init__(self, **kw: Any) -> None:
        self.__dict__.update(kw)


# -- the device -------------------------------------------------------------------


def require_device(chips: int, rehearsal: bool) -> Dict[str, Any]:
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if rehearsal:
        return device
    peaks = load_json(HERE, "peaks.json")["peaks"]
    if device["platform"] != "tpu" or device["kind"] not in peaks:
        raise SystemExit(f"no TPU with a published peak: jax came up on {device}; "
                         f"known kinds: {sorted(peaks)}")
    if device["count"] < chips:
        raise SystemExit(f"the cell asks for {chips} chip(s), jax sees {device['count']}")
    return device


class MemoryPeak:
    """Device memory taken on the fullest chip: buffers plus program scratch.

    This runtime's allocator counts buffers (``bytes_in_use``) apart from the
    scratch it sets aside for the temporaries of the programs that are loaded
    (``bytes_reserved``), and its ``peak_bytes_in_use`` leaves that scratch
    out although no buffer can have it: in the flagship 0.87 GB of buffers at
    most, beside 6.03 GB reserved -- the 6.03 GB of temporaries the 20-wide
    ``eval_pop`` compiles to -- and 10.1 GB left as the largest free block
    (PERF.md).  ``sample`` reads the two together at instants of the run:
    after the warm-up call, when only the window's programs are loaded, and
    after every unit of the window.  ``value`` is the largest such sum, or the
    allocator's own buffer peak where that is larger: a reading, never above
    what the chip held.  The allocator's numbers are printed on an ``info``
    line beside it.
    """

    def __init__(self) -> None:
        self.best = 0

    def sample(self) -> Dict[str, int]:
        import jax

        for d in jax.local_devices():
            stats = d.memory_stats() or {}
            self.best = max(self.best, int(stats.get("peak_bytes_in_use", 0)),
                            int(stats.get("bytes_in_use", 0)) + int(stats.get("bytes_reserved", 0)))
        return stats


# -- end-to-end metrics: all the work and all the time of the window --------------------


def end_to_end(units: List[Dict[str, Any]], elapsed: float, chips: int, setup_s: float) -> Dict[str, float]:
    return {
        "individuals_per_hour_per_chip": sum(u["scored"] for u in units) * 3600.0 / elapsed / chips,
        "setup_s": setup_s,
    }


# -- main ------------------------------------------------------------------------------


def run(args) -> Dict[str, Any]:
    """One run of one cell: the result object (``correct`` as the checks say)
    and, under ``checks``, each number compared: printed here on lines of
    their own, and kept out of the last line by ``main``."""
    manifest, cell, config, mix = load_cell(args.workload, args.rehearsal)
    kind = load_module("traffic_kinds", mix["kind"])

    device = require_device(cell["chips"], args.rehearsal)
    t_backend = time.monotonic()
    backend_s = t_backend - T_START
    import jax

    from gentun_tpu.telemetry import spans
    from gentun_tpu.utils.xla_cache import default_cache_dir, enable_compilation_cache

    cache_dir = default_cache_dir()
    if cache_dir and not args.rehearsal:
        enable_compilation_cache(cache_dir)  # before the first eager op compiles
    monitor, records = Monitor(), Records()
    if args.trace:
        spans.set_run_sink(records)
        spans.enable()

    family = load_family(config["family"])
    ctx = Ctx(config=config, mix=mix, cell=cell, seed=args.seed, monitor=monitor, records=records,
              trace=bool(args.trace), rehearsal=args.rehearsal, chips=cell["chips"],
              **family.make_inputs(config, mix, args.seed, args.rehearsal))
    t_inputs = time.monotonic()

    memory = MemoryPeak()
    state = kind.setup(ctx, mix)
    t_warm = time.monotonic()
    print("info memory_stats after the warm-up call, the window's programs loaded and no other:",
          json.dumps(memory.sample()))
    program = family.program_side(ctx)
    setup_requests, setup_hits, setup_compiles = len(monitor.requests), len(monitor.hits), len(monitor.compiles)

    # -- the window ------------------------------------------------------------
    trace_dir = os.path.join(OUT_DIR, "trace", f"{args.workload}.{args.seed}")
    tracing, anchor_wall, trace_window = False, None, None
    if args.trace:
        os.makedirs(trace_dir, exist_ok=True)
        jax.profiler.start_trace(trace_dir)
        tracing = True
    t_open, open_wall = time.monotonic(), time.time()
    setup_s = t_open - T_START
    setup_parts = {"backend_s": backend_s, "inputs_s": t_inputs - t_backend, "warmup_s": t_warm - t_inputs,
                   "program_check_s": t_open - t_warm}
    if tracing:
        anchor_wall = time.time()
        with jax.profiler.TraceAnnotation("bench_anchor"):
            pass
    units: List[Dict[str, Any]] = []
    raised = 0
    memory.sample()
    while time.monotonic() - t_open < args.seconds:
        t_wall, t0 = time.time(), time.monotonic()
        try:
            unit = kind.unit(ctx, mix, state)
        except Exception:  # counted as failed individuals; the run is not correct
            traceback.print_exc()
            raised += config["population"]
            break
        unit["t_wall"], unit["wall_s"] = t_wall, time.monotonic() - t0
        units.append(unit)
        memory.sample()
        if tracing and time.monotonic() - t_open >= float(mix.get("trace_seconds", 6)):
            trace_window = (anchor_wall, time.time())
            jax.profiler.stop_trace()
            tracing = False
    elapsed = time.monotonic() - t_open
    close_wall = time.time()
    if tracing:
        trace_window = (anchor_wall, close_wall)
        jax.profiler.stop_trace()
    monitor.window = (open_wall, close_wall)
    print("info memory_stats after the window, before the reference runs:", json.dumps(memory.sample()))
    device["memory_peak_bytes"] = memory_peak = memory.best

    # -- what was produced, and is it right ------------------------------------------
    scored = sum(u["scored"] for u in units)
    trained = sum(u["trained"] for u in units)
    failed = raised + sum(u["failed"] for u in units)
    checks = [{"name": "units_in_window", "value": len(units), "limit": ">=1",
               "ok": len(units) >= 1 and not raised}]
    checks += family.window_checks(ctx, units)
    checks += kind.checks(ctx, mix, state, units)
    t_ref = time.monotonic()
    checks += family.after_window(ctx, program)[0]
    reference_s = time.monotonic() - t_ref
    for c in checks:
        print(f"check {c['name']}: value={c['value']} limit={c['limit']} "
              f"{'ok' if c['ok'] else 'NOT OK'}")
    ok = all(c["ok"] for c in checks) and failed == 0

    print(f"window: {len(units)} units, {scored} individuals scored, {trained} trained, "
          f"elapsed {elapsed:.4f} s of {args.seconds} asked; unit walls "
          f"{[round(u['wall_s'], 3) for u in units]}")
    print(f"set-up: {setup_s:.3f} s (backend {backend_s:.3f} s, inputs {setup_parts['inputs_s']:.3f} s, warm-up "
          f"{setup_parts['warmup_s']:.3f} s, program's check {setup_parts['program_check_s']:.3f} s); jax asked for "
          f"{setup_requests} programs, {setup_hits} from the cache at {cache_dir}, {setup_compiles} backend compiles; "
          f"in the window: {monitor.requests_in_window()} asked for; reference {reference_s:.3f} s, "
          f"not in setup_s")

    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not args.trace:
        values = end_to_end(units, elapsed, cell["chips"], setup_s) if units else {}
        for m in manifest["end_to_end"]:
            if applies(m, cell["name"]) and m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        import trace_reduce

        reduction = None
        xplane = trace_reduce.newest_xplane(trace_dir)
        if xplane and trace_window:
            read = trace_reduce.read_xplane(xplane, anchor_wall)
            host = [(r["kind"], r["t_wall"], r["t_wall"] + r["dur_s"]) for r in records.items
                    if r.get("type") == "span"]
            host += [("unit", u["t_wall"], u["t_wall"] + u["wall_s"]) for u in units]
            reduction = trace_reduce.reduce(read["device_ops"], host, trace_window)
            reduction["anchor_found"] = read["anchor_found"]
            with open(os.path.join(trace_dir, "inventory.json"), "w", encoding="utf-8") as fh:
                json.dump({"inventory": read["inventory"], "reduction": reduction}, fh, indent=1)
            device["busy_s"], device["window_s"] = reduction["busy_s"], reduction["window_s"]
            breakdown = {"device_ops": reduction["device_ops"], "idle_gaps": reduction["idle_gaps"]}
        reading = {"config": config, "cell": cell, "chips": cell["chips"], "units": units,
                   "records": records.items, "window": (open_wall, close_wall), "elapsed": elapsed,
                   "monitor": monitor, "trace": reduction, "setup": setup_parts, "memory_peak_bytes": memory_peak,
                   "peak": None if args.rehearsal else load_json(HERE, "peaks.json")["peaks"][device["kind"]]}
        for m in manifest["per_layer"]:
            if applies(m, cell["name"]):
                value = load_module("layer_metrics", m["name"]).read(reading)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        with open(os.path.join(trace_dir, "records.jsonl"), "w", encoding="utf-8") as fh:
            for r in records.items:
                fh.write(json.dumps(r, default=str) + "\n")

    result: Dict[str, Any] = {"correct": ok, "attempted": scored + raised, "failed": failed,
                              "metrics": metrics, "device": device}
    if breakdown:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description="one run of one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the configuration's rehearsal sizes, any backend, never a passing result")
    args = ap.parse_args(argv)
    result = run(args)
    result.pop("checks")  # printed above, each on a line of its own
    if args.rehearsal:
        # Never a pass, and a CPU number is never written under a device metric's name.
        print("rehearsal (no measurement): checks say", result["correct"], json.dumps(result["metrics"]))
        result.update(rehearsal=True, correct=False, metrics={})
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
