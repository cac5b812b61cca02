"""Tail-generation throughput study (VERDICT r4 weak #2 / item 4).

DISTRIBUTED.md's read-out: warm steady-state generations run at 12-13k
individuals/hour/chip vs the 22.4k bench figure, because late generations
evaluate 1-3 individuals and amortize the program+dispatch cost poorly.
This study measures the mitigation: the same 50-generation proxy search
(the `distributed_tpu_run.py` 50-gen workload) run back-to-back with
speculative bucket filling off vs on, comparing per-generation
steady-state throughput and total search wall.

Speculation changes which architectures are pre-measured, not the search
itself: both runs use identical seeds, so the GA's trajectory (selection,
children) is identical; only the cache warm-up differs.  The comparison
is therefore apples-to-apples on the exact same 51-barrier schedule.

One command, owns the chip for its duration (runs master+worker pairs
sequentially per variant):

    python scripts/tailgen_study.py --out scripts/tailgen_study.json
    python scripts/tailgen_study.py --tiny ...   # CPU rehearsal
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_variant(name: str, spec_flag: str, args, port: int) -> dict:
    out = os.path.join(REPO, "scripts", f"tailgen_{name}.json")
    master_cmd = [
        sys.executable, os.path.join(REPO, "scripts", "distributed_tpu_run.py"),
        "master", "--port", str(port), "--generations", str(args.generations),
        "--out", out,
    ]
    if spec_flag:
        master_cmd += ["--speculative-fill", spec_flag]
    if args.tiny:
        master_cmd += ["--tiny"]
    worker_cmd = [
        sys.executable, "-m", "gentun_tpu.distributed.worker",
        "--port", str(port), "--species", "genetic-cnn",
        "--dataset", "cifar10", "--n", str(96 if args.tiny else 10_000),
        "--capacity", "20",
    ]
    env = dict(os.environ)
    if args.tiny:
        env["JAX_PLATFORMS"] = "cpu"
    log_dir = os.path.join(REPO, "scripts", "logs")
    os.makedirs(log_dir, exist_ok=True)  # git-ignored, so absent in a fresh copy
    master_log = open(os.path.join(log_dir, f"tailgen_{name}_master.log"), "w")
    worker_log = open(os.path.join(log_dir, f"tailgen_{name}_worker.log"), "w")
    t0 = time.monotonic()
    master = subprocess.Popen(master_cmd, cwd=REPO, env=env,
                              stdout=master_log, stderr=subprocess.STDOUT)
    worker = None
    try:
        time.sleep(3)
        worker = subprocess.Popen(worker_cmd, cwd=REPO, env=env,
                                  stdout=worker_log, stderr=subprocess.STDOUT)
        rc = master.wait(timeout=args.timeout)
    finally:
        # A hung variant must not leak the master/worker pair: they own the
        # TPU (one-TPU-process rule) and would block every later run.
        for proc in (master, worker):
            if proc is not None and proc.poll() is None:
                proc.terminate()
                try:
                    proc.wait(timeout=30)
                except subprocess.TimeoutExpired:
                    proc.kill()
        master_log.close(); worker_log.close()
    if rc != 0:
        raise RuntimeError(f"variant {name}: master rc={rc} (see scripts/logs/tailgen_{name}_master.log)")
    with open(out) as f:
        rec = json.load(f)
    rec["orchestrator_wall_s"] = round(time.monotonic() - t0, 2)
    return rec


def steady_state_stats(history: list) -> dict:
    """Per-generation throughput for generations that actually trained
    something, split by batch size (the tail = small batches)."""
    small = [h for h in history if 0 < h["evaluated"] <= 4]
    large = [h for h in history if h["evaluated"] > 4]
    zero = [h for h in history if h["evaluated"] == 0]
    agg = lambda hs: {
        "generations": len(hs),
        "trained_total": sum(h["evaluated"] for h in hs),
        "wall_total_s": round(sum(h["eval_wall_s"] for h in hs), 3),
        "individuals_per_hour_per_chip": round(
            sum(h["evaluated"] for h in hs)
            / max(sum(h["eval_wall_s"] for h in hs), 1e-9) * 3600.0, 1),
    }
    return {
        "small_batches_1_to_4": agg(small),
        "large_batches_gt4": agg(large),
        "zero_train_generations": {"generations": len(zero),
                                   "wall_total_s": round(sum(h["eval_wall_s"] for h in zero), 3)},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--generations", type=int, default=50)
    ap.add_argument("--variants", nargs="+", default=["off", "16"],
                    help="speculative-fill settings to compare (''/'off', 'bucket', or an int)")
    ap.add_argument("--timeout", type=float, default=3600.0)
    ap.add_argument("--tiny", action="store_true", help="CPU rehearsal")
    ap.add_argument("--out", default=os.path.join(REPO, "scripts", "tailgen_study.json"))
    args = ap.parse_args(argv)

    os.makedirs(os.path.join(REPO, "scripts", "logs"), exist_ok=True)
    record = {"workload": f"distributed 50-gen proxy search (pop=20), "
                          f"generations={args.generations}, tiny={args.tiny}",
              "variants": {}}
    base_port = 56750
    for i, v in enumerate(args.variants):
        name = "off" if v in ("", "off") else f"spec{v}"
        if name in record["variants"]:
            name = f"{name}_{i}"  # e.g. off,16,off — rerun 'off' on a warm cache
        flag = "" if v in ("", "off") else v
        rec = run_variant(name, flag, args, base_port + i)
        hist = rec["proxy"]["history"]
        record["variants"][name] = {
            "speculative_fill": rec.get("speculative_fill", "off"),
            "proxy_total_wall_s": rec["proxy"]["wall_s"],
            "evaluated_total": rec["proxy"]["evaluated_total"],
            "best_fitness": rec["proxy"]["best_fitness"],
            "search_level_individuals_per_hour_per_chip":
                rec["proxy"]["individuals_per_hour_per_chip"],
            "steady_state": steady_state_stats(hist),
        }
        with open(args.out, "w") as f:  # incremental: variants are chip-minutes
            json.dump(record, f, indent=1)
        print(f"[{name}] wall={rec['proxy']['wall_s']}s "
              f"evaluated={rec['proxy']['evaluated_total']} "
              f"best={rec['proxy']['best_fitness']:.4f} "
              f"small-batch rate="
              f"{record['variants'][name]['steady_state']['small_batches_1_to_4']['individuals_per_hour_per_chip']}",
              flush=True)

    names = list(record["variants"])
    if len(names) >= 2:
        fits = {record["variants"][n]["best_fitness"] for n in names}
        if len(fits) > 1:
            spread = max(fits) - min(fits)
            # Content-hash PRNG keys (models/evaluation.genome_hashes) remove all
            # systematic divergence; what can remain on TPU is a rare
            # validation-sample flip when speculation moves an architecture
            # to a different program SHAPE (XLA rounds differently).  A
            # spread at or below a few validation samples is that; anything
            # larger means a protocol bug.
            kind = ("cross-program-shape rounding (expected, sample-level)"
                    if spread < 1e-3 else "PROTOCOL-LEVEL — investigate")
            print(f"NOTE: best fitness differs between variants by {spread:.6f}: "
                  f"{kind}", flush=True)
            record["best_fitness_spread"] = round(spread, 6)
        # Compare each later variant against the LAST plain-off run (the
        # warmest apples-to-apples baseline when 'off' appears twice).
        offs = [n for n in names if n.startswith("off")]
        specs = [n for n in names if not n.startswith("off")]
        if offs and specs:
            a = record["variants"][offs[-1]]
            record["comparison"] = {"baseline": offs[-1]}
            for n in specs:
                b = record["variants"][n]
                record["comparison"][n] = {
                    "wall_ratio": round(b["proxy_total_wall_s"] / a["proxy_total_wall_s"], 4),
                    "small_batch_rate_ratio": round(
                        b["steady_state"]["small_batches_1_to_4"]["individuals_per_hour_per_chip"]
                        / max(a["steady_state"]["small_batches_1_to_4"]["individuals_per_hour_per_chip"], 1e-9), 4),
                }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
