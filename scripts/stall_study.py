"""PR 38's study of device stalls: one process, one routed cell, traced unit by unit.

    python scripts/stall_study.py --workload lfm2_24b_a2b_ep8.popeval --seed <n> --units 60 --plant sleep,stop

The process sets a cell up as ``benchmark/run.py`` does, then scores
``--units`` units in a row under the host sampler
(``gentun_tpu/telemetry/sampler.py``).  Each unit is traced on its own, reduced
at once by ``benchmark/stall_reduce.py`` (its ``info stall`` lines) and printed
as one ``study unit`` line: the unit's wall, the reader's numbers, its stall
records and the ``host_hiccup`` events of the unit; the trace is deleted.

``--plant`` puts a known pause into the first units, to show that each column
of the reader means what it says.  Nothing is planted in the program: the
pause hangs on the run sink, which hears of every span's end on the main thread.

- ``sleep``: ``time.sleep(PAUSE_S)`` when individual 0's last span ends, before
  individual 1's first call.  Expected: one stall of about the pause, between
  programs, host on time (the sampler's thread runs on).
- ``stop``: when individual 1's ``init_params`` span ends a child is started
  that stops this whole process (``kill -STOP``) in the middle of that
  individual's train steps and continues it ``PAUSE_S`` later.  Expected: a
  tick that woke about the pause late; whether the device ran on is the finding.

``--rehearsal`` runs the same code at the configuration's rehearsal sizes on
whatever jax comes up on (a CPU trace has no device plane to reduce).
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

PAUSE_S = 0.5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--units", type=int, default=60)
    ap.add_argument("--plant", default="", help="comma-separated: sleep, stop; one unit each, before the others")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()

    import run as bench

    class Sink(bench.Records):
        """The run sink, with the unit's plant (``armed``) hung on a span's end."""

        armed, train_s = None, 1.0

        def record(self, rec):
            super().record(rec)
            if self.armed is None or rec.get("type") != "span":
                return
            at = (rec["kind"], (rec.get("attrs") or {}).get("individual"))
            if self.armed == "sleep" and at == ("fetch", 0):
                time.sleep(PAUSE_S)
            elif self.armed == "stop" and at == ("init_params", 1):
                subprocess.Popen(["bash", "-c", f"sleep {0.5 * self.train_s:.2f}; kill -STOP {os.getpid()}; "
                                                f"sleep {PAUSE_S}; kill -CONT {os.getpid()}"])
            else:
                return
            self.armed = None

    manifest, cell, config, mix = bench.load_cell(args.workload, args.rehearsal)
    kind = bench.load_module("traffic_kinds", mix["kind"])
    device = bench.require_device(cell["chips"], args.rehearsal)
    import jax

    import stall_reduce
    from gentun_tpu.telemetry import spans
    from gentun_tpu.utils.xla_cache import default_cache_dir, enable_compilation_cache

    if default_cache_dir() and not args.rehearsal:
        enable_compilation_cache(default_cache_dir())
    records = Sink()
    spans.set_run_sink(records)
    spans.enable()
    family = bench.load_family(config["family"])
    ctx = bench.Ctx(config=config, mix=mix, cell=cell, seed=args.seed, monitor=bench.Monitor(), records=records,
                    trace=True, rehearsal=args.rehearsal, chips=cell["chips"],
                    **family.make_inputs(config, mix, args.seed, args.rehearsal))
    t0 = time.monotonic()
    state = kind.setup(ctx, mix)
    trains = sorted(r["dur_s"] for r in records.items if r.get("type") == "span" and r["kind"] == "train")
    records.train_s = trains[len(trains) // 2] if trains else 1.0
    print(f"study: {device}; set-up {time.monotonic() - t0:.1f} s; a train span takes {records.train_s:.3f} s", flush=True)

    plants = [p for p in args.plant.split(",") if p]
    for n in range(len(plants) + args.units):
        records.armed = planted = plants[n] if n < len(plants) else None
        del records.items[:]
        trace_dir = os.path.join(BENCH, "out", "trace", f"{args.workload}.study{n}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir)
        jax.profiler.start_trace(trace_dir)
        open_wall, t_open = time.time(), time.monotonic()
        with jax.profiler.TraceAnnotation("bench_anchor"):
            pass
        unit = kind.unit(ctx, mix, state)
        unit["t_wall"], unit["wall_s"] = open_wall, time.monotonic() - t_open
        close_wall = time.time()
        jax.profiler.stop_trace()
        run = {"cell": cell, "trace": {"window_s": close_wall - open_wall}, "window": (open_wall, close_wall),
               "records": list(records.items), "units": [unit]}
        got = stall_reduce.table(run)  # prints the unit's ``info stall`` lines
        train = [r for r in run["records"] if r.get("type") == "span" and r["kind"] == "train"]
        line = {"unit": n, "planted": planted, "wall_s": unit["wall_s"], "train_s": [r["dur_s"] for r in train],
                "wait_cpu_s": [r["attrs"].get("wait_cpu_s") for r in train],
                "hiccups": [r["data"] for r in run["records"] if r.get("type") == "event" and r["name"] == "host_hiccup"]}
        if got is not None:
            line.update({k: got[k] for k in stall_reduce.METRICS})
            line.update(stalls=got["stalls"], longest_gaps=got["longest_gaps"])
        print("study unit", json.dumps(line, default=str), flush=True)
        shutil.rmtree(trace_dir, ignore_errors=True)  # a routed unit's trace is large
    spans.disable()
    return 0


if __name__ == "__main__":
    sys.exit(main())
