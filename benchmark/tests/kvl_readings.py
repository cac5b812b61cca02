"""Readings the limits of a ``keye_vl2`` configuration are set from (run on the chip).

    python benchmark/tests/kvl_readings.py --workload keye_vl2_30b_a3b_ep8.popeval --seeds 1,2,3
    python benchmark/tests/kvl_readings.py --workload keye_vl2_30b_a3b_ep8.popeval --seeds 4 --control none --faults all

``lag_readings.py``'s pattern for this family: for each seed, in one process,
the program's numbers against the reference (what a sound run of ``run.py``
prints as ``check ...`` lines) and the control's -- the reference itself
computed in fp8, put in the program's place.  A limit goes between the largest
sound reading and the smallest control reading of the number that tells them
apart (PERF.md lists both).  ``--faults a,b`` (or ``all``: those of the
published widths) then breaks the timed path underneath, one fault after
another as ``test_kvl_correct.py`` plants them on the CPU, and compares each
broken program with the seed's one reference: what the limits make of a fault
at the published widths.  ``--rehearsal`` as in ``run.py``.
"""

from __future__ import annotations

import argparse
import gc
import importlib
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
    ap.add_argument("--faults", default="", help="comma-separated faults of test_kvl_correct.py, or 'all'")
    ap.add_argument("--skip-sound", action="store_true", help="no sound program: the faults alone against the reference")
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args()
    from test_kvl_correct import PUBLISHED_FAULTS, plant

    faults = PUBLISHED_FAULTS if args.faults == "all" else tuple(f for f in args.faults.split(",") if f)
    _, cell, config, mix = harness.load_cell(args.workload, args.rehearsal)
    device = harness.require_device(cell["chips"], args.rehearsal)
    import jax

    from gentun_tpu.models import lfm2_moe as model
    from gentun_tpu.utils.xla_cache import default_cache_dir, enable_compilation_cache

    if default_cache_dir() and not args.rehearsal:
        enable_compilation_cache(default_cache_dir())
    family = harness.load_family(config["family"])
    correct = importlib.import_module("correct")
    limits = correct.flat_limits(config["check"]["limits"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        ctx = harness.Ctx(config=config, seed=seed, rehearsal=args.rehearsal,
                          **family.make_inputs(config, mix, seed, args.rehearsal))
        program = family.program_side(ctx) if not args.skip_sound else {"inputs": correct.check_inputs(ctx)}
        jax.clear_caches()  # as ``correct.after_window``: the loaded programs' scratch would lie beside the reference
        t1 = time.monotonic()
        reference = correct.reference_side(ctx, program["inputs"])
        compare = lambda side: correct.compare(side, reference)
        sound = compare(program) if not args.skip_sound else {}
        control = compare(correct.reference_side(ctx, program["inputs"], args.control)) if args.control != "none" else None
        print(json.dumps({"cell": args.workload, "seed": seed, "device": device["kind"], "sound": sound,
                          "not_ok": [k for k, v in sound.items() if v > limits[k]], "control": args.control,
                          "control_values": control, "losses": program.get("losses"),
                          "reference_losses": reference["losses"], "program_s": t1 - t0,
                          "after_s": time.monotonic() - t1}), flush=True)
        for fault in faults:
            t2 = time.monotonic()
            model._programs.cache_clear()  # the next programs are built from what is planted now
            undo = plant(fault)
            try:
                broken = compare(family.program_side(ctx))
            finally:
                undo()
                model._programs.cache_clear()
                jax.clear_caches()  # the broken programs go, or the host holds every fault's executables: nine of them
                gc.collect()        # and their trees met the machine's 40 GiB at the seventh (PR 34)
            print(json.dumps({"cell": args.workload, "seed": seed, "fault": fault, "values": broken,
                              "not_ok": [k for k, v in broken.items() if v > limits[k]],
                              "seconds": time.monotonic() - t2}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
