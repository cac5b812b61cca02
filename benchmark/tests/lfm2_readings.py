"""Readings the limits of an ``lfm2_moe`` configuration are set from (run on the chip).

    python benchmark/tests/lfm2_readings.py --workload lfm2_24b_a2b_ep8.popeval --seeds 1,2,3

``readings.py``'s pattern for this family: for each seed, in one process, the
program's numbers against the reference (what a sound run of ``run.py`` prints
as ``check ...`` lines) and the control's -- the reference itself computed in
fp8, put in the program's place.  A limit goes above the largest sound reading
and below the smallest control reading of the number that tells them apart
(PERF.md lists both).  ``--rehearsal`` as in ``run.py``.  ``--fault NAME`` breaks
the timed path underneath first, as ``test_lfm2_correct.py`` plants it on the
CPU: what a limit makes of a fault at the published widths.
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
    ap.add_argument("--control", default="fp8", help="a lower precision, or 'none'")
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--fault", default="")
    args = ap.parse_args()
    if args.fault:
        from test_lfm2_correct import _plant

        _plant(args.fault)
    _, cell, config, mix = harness.load_cell(args.workload, args.rehearsal)
    device = harness.require_device(cell["chips"], args.rehearsal)
    from gentun_tpu.utils.xla_cache import default_cache_dir, enable_compilation_cache

    if default_cache_dir() and not args.rehearsal:
        enable_compilation_cache(default_cache_dir())
    family = harness.load_family(config["family"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        ctx = harness.Ctx(config=config, seed=seed, rehearsal=args.rehearsal,
                          **family.make_inputs(config, mix, seed, args.rehearsal))
        program = family.program_side(ctx)
        t1 = time.monotonic()
        sound, control = family.after_window(ctx, program, None if args.control == "none" else args.control)
        print(json.dumps({"cell": args.workload, "seed": seed, "device": device["kind"], "fault": args.fault,
                          "sound": {c["name"]: c["value"] for c in sound},
                          "not_ok": [c["name"] for c in sound if not c["ok"]], "control": args.control,
                          "control_values": control, "losses": program["losses"],
                          "program_s": t1 - t0, "after_s": time.monotonic() - t1}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
