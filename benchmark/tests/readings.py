"""Readings the limits in ``configs/*.json`` are set from (run on the chip).

    python benchmark/tests/readings.py --workload <cell> --seeds 1,2,3 [--controls 3] [--fit-rows n]

For each seed, in one process: the program's numbers against the reference
(what a sound run of ``run.py`` prints as ``check ...`` lines) and, for the
first ``--controls`` seeds, the control's -- the reference itself computed in
the next precision down (``--control``, fp8), put in the program's place.  A
limit goes above the largest sound reading and below the smallest control
reading (PERF.md lists both).  ``--rehearsal`` as in ``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import run as harness  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="fp8")
    ap.add_argument("--controls", type=int, default=3, help="how many of the seeds also read the control")
    ap.add_argument("--fit-rows", type=int, help="in place of the configuration's check.fit_rows")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    _, cell, config, mix = harness.load_cell(args.workload, args.rehearsal)
    if args.fit_rows:
        config["check"]["fit_rows"] = args.fit_rows
    device = harness.require_device(cell["chips"], args.rehearsal)
    from gentun_tpu.utils.xla_cache import default_cache_dir, enable_compilation_cache

    if default_cache_dir() and not args.rehearsal:
        enable_compilation_cache(default_cache_dir())
    family = harness.load_family(config["family"])

    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        ctx = harness.Ctx(config=config, seed=seed, **family.make_inputs(config, mix, seed, args.rehearsal))
        program = family.program_side(ctx)
        t1 = time.monotonic()
        sound, control = family.after_window(ctx, program, args.control if i < args.controls else None)
        print(json.dumps({"cell": args.workload, "seed": seed, "device": device["kind"],
                          "fit_rows": config["check"]["fit_rows"],
                          "sound": {c["name"]: c["value"] for c in sound},
                          "control": args.control, "control_values": control,
                          "program_s": t1 - t0, "after_s": time.monotonic() - t1}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
