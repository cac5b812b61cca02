"""Chip smoke: the Genetic-CNN search's main path, once, on the TPU.

    python chip_smoke.py               # the check: needs a TPU, fails without
    python chip_smoke.py --rehearsal   # tiny shapes, CPU allowed, never a pass

One command, run from the root of a checkout (which need not be a git
repository and needs no network).  It drives the search through the entry
points a user calls, at the full width of the CIFAR-10 flagship — S=(3,4,5),
kernels (32,64,128), dense 256, batch 256, bfloat16, 10,000 synthetic
images, population 20: ``bench.PROXY`` — with the depth cut to the proxy
schedule (kfold=2, one epoch, 19 train steps a fold) and two generations.

A chip belongs to one process at a time, so this parent never initializes a
jax backend; every phase is a child that owns the chip alone, is joined with
a timeout, and is gone before the next starts:

- *local*: ``Population`` + ``GeneticAlgorithm.run`` in one process driving
  every local device through ``auto_mesh``, then one more population of the
  same size forwards and backwards, which must compile nothing and agree;
- *distributed*: this process hosts ``DistributedPopulation`` +
  ``GeneticAlgorithm.run``; the child is the stock worker CLI;
- *dryrun* (more than one device only): ``__graft_entry__.dryrun_multichip``
  on the real devices, so the data axis and its all-reduce run too.

Each phase prints one JSON line.  Checked: the platform is ``tpu`` and its
``device_kind`` has a published peak (``bench.PEAK_BF16_FLOPS``); every
fitness is finite and in [0, 1] and the population mean is above chance;
the OOM healer never split a population at this width; the train carries
have shards on every device and every device holds memory; no program
compiled twice; a population scores the same, genome by genome, when its
slot order is reversed; the distributed search saw zero failed jobs, zero retries
and the worker's device count as ``n_chips`` in every generation; and the
later phase found the earlier phase's entries in the compile cache.  Any
failed check, child timeout or nonzero child exit gives a nonzero exit.

The last line of stdout is the verdict: on a pass
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``;
otherwise a ``chip_smoke: FAIL`` line and no result object.  A rehearsal
marks every line ``"rehearsal": true`` and never prints ``"ok"``.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time
import warnings
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

#: The whole command must end inside the driver's 1200 s; phases share this.
BUDGET_S = 1140.0
PHASE_CAP_S = {"local": 660.0, "distributed": 540.0, "dryrun": 240.0}

CHANCE = 0.1  # ten classes
#: The same genome in another slot of the same program: a few validation
#: samples of rounding at most (5,000 a fold), never a different answer.
SLOT_ORDER_TOL = 0.01


class PhaseFailed(Exception):
    """A phase did not pass; the message says which check and why."""


def workload(rehearsal: bool) -> Dict[str, Any]:
    """Population, generations, dataset size and model config of the run."""
    import bench

    if rehearsal:
        # Plumbing only: every width cut, same code paths.
        params = dict(bench.PROXY, kernels_per_layer=(4, 4, 4), dense_units=8,
                      batch_size=16, compute_dtype="float32")
        return {"pop": 4, "generations": 1, "n_data": 96, "params": params}
    return {"pop": bench.POP, "generations": 2, "n_data": bench.N_DATA,
            "params": dict(bench.PROXY)}


def versions() -> Dict[str, Optional[str]]:
    out: Dict[str, Optional[str]] = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = importlib.metadata.version(pkg)
        except importlib.metadata.PackageNotFoundError:
            out[pkg] = None
    return out


def emit(line: Dict[str, Any], rehearsal: bool) -> None:
    if rehearsal:
        line = {"rehearsal": True, **line}
    print(json.dumps(line), flush=True)


class Records:
    """A telemetry run sink that keeps every span/event/lineage record."""

    def __init__(self) -> None:
        self.items: List[Dict[str, Any]] = []
        self._lock = threading.Lock()

    def record(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self.items.append(rec)

    def snapshot(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self.items)

    def dump(self, path: str) -> None:
        with open(path, "w", encoding="utf-8") as fh:
            for rec in self.snapshot():
                fh.write(json.dumps(rec, default=str) + "\n")


def device_spans(records: List[Dict[str, Any]]) -> List[Dict[str, Any]]:
    """The model-level device spans (``models/cnn.py``): ``compile``, ``train``
    and ``eval`` with a fold (the host phase ``fold_slice`` has one too)."""
    return [r for r in records if r.get("type") == "span"
            and r["kind"] in ("compile", "train", "eval")
            and "fold" in (r.get("attrs") or {})]


def summarize_spans(records: List[Dict[str, Any]], n_devices: int, pop: int,
                    failed: List[str]) -> Dict[str, Any]:
    """What both phases read off the device spans, with its checks."""
    from gentun_tpu.parallel.mesh import mesh_factor

    spans = device_spans(records)
    first = [s for s in spans if s["kind"] == "compile"]
    train = [s for s in spans if s["attrs"].get("phase", s["kind"]) == "train"]
    # The mesh follows the batch: report the full population's, which is the
    # one auto_mesh is specified for; small tail batches may factor otherwise.
    full = sorted({tuple(s["attrs"]["mesh"]) for s in train if s["attrs"]["pop"] >= pop})
    carry = [s["attrs"]["carry_devices"] for s in train]
    oom = [r for r in records if r.get("type") == "event"
           and r.get("name") == "oom_split"]
    if not full:
        failed.append("no full-population train span came back: telemetry is "
                      "not reaching the phase")
    elif full != [mesh_factor(n_devices, pop)]:
        failed.append(f"population of {pop} on {n_devices} device(s) ran on mesh "
                      f"{full}, mesh_factor says {mesh_factor(n_devices, pop)}")
    if carry and min(carry) != n_devices:
        failed.append(f"train carries span {min(carry)} device(s), not all {n_devices}")
    if oom:
        failed.append(f"OOM healer split a population at a width that fits: {oom[0].get('data')}")
    return {
        "mesh": list(full[0]) if full else None,
        "meshes": sorted({tuple(s["attrs"]["mesh"]) for s in train}),
        "carry_devices_min": min(carry) if carry else None,
        "first_calls": len(first),
        "first_call_train_programs": sum(1 for s in first if s["attrs"].get("phase") == "train"),
        "first_call_s": round(sum(s["dur_s"] for s in first), 2),
        "oom_splits": len(oom),
    }


def check_fitness(values: List[float], population: List[float], rehearsal: bool,
                  failed: List[str]) -> Dict[str, Any]:
    bad = [v for v in values if not (math.isfinite(v) and 0.0 <= v <= 1.0)]
    if bad:
        failed.append(f"{len(bad)} fitness value(s) not finite in [0, 1]: {bad[:3]}")
    mean = sum(population) / len(population)
    # At rehearsal shapes nothing is learnt; chance is a claim about the width.
    if not rehearsal and not mean > CHANCE:
        failed.append(f"population mean fitness {mean:.3f} is not above chance {CHANCE}")
    return {"fitness_mean": round(mean, 4), "fitness_best": round(max(population), 4),
            "measured": len(values)}


def check_history(history: List[Dict[str, Any]], n_devices: int,
                  failed: List[str]) -> Dict[str, Any]:
    n_chips = [h["n_chips"] for h in history]
    if any(n != n_devices for n in n_chips):
        failed.append(f"generations logged n_chips {n_chips}, device count is {n_devices}")
    return {"evaluated": [h["evaluated"] for h in history], "n_chips": n_chips}


# ---------------------------------------------------------------------------
# Children: each is this script again, and owns the chip alone
# ---------------------------------------------------------------------------


def require_device(rehearsal: bool) -> Dict[str, Any]:
    """What jax runs on; anything but a known TPU ends a real run here."""
    import bench

    return bench.jax_device() if rehearsal else bench.require_tpu()


def child_local(rehearsal: bool) -> int:
    t_start = time.monotonic()
    device = require_device(rehearsal)
    backend_init_s = time.monotonic() - t_start
    import jax

    from gentun_tpu import GeneticAlgorithm, GeneticCnnIndividual, Population
    from gentun_tpu.models import cnn
    from gentun_tpu.telemetry import spans
    from gentun_tpu.utils.datasets import load_cifar10
    from gentun_tpu.utils.xla_cache import list_cache_entries

    # jax's own account of compiles and of the persistent cache.
    compile_times: List[float] = []
    cache = {"requests": 0, "hits": 0}

    def on_duration(event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compile_times.append(time.time())

    def on_event(event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            cache["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            cache["hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    records = Records()
    spans.set_run_sink(records)
    spans.enable()

    work = workload(rehearsal)
    x, y, _meta = load_cifar10(n=work["n_data"])
    failed: List[str] = []
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        t_run = time.monotonic()
        pop = Population(GeneticCnnIndividual, x_train=x, y_train=y, size=work["pop"],
                         seed=0, additional_parameters=work["params"])
        ga = GeneticAlgorithm(pop, seed=0)
        ga.run(work["generations"])
        run_wall = time.monotonic() - t_run

        # A second, fresh population through the batched entry point, then
        # the same genomes in reverse order.  Both are an already-seen
        # shape: they must trace, lower and compile nothing at all.  And
        # fitness is a function of the genome, not of the slot it trains in
        # (the repo's own oracle, TestBatchCompositionPurity), so the two
        # orders must agree genome by genome.
        n_compiles = len(compile_times)
        t_repeat = time.monotonic()
        fresh = Population(GeneticCnnIndividual, x_train=x, y_train=y, size=work["pop"],
                           seed=1, additional_parameters=work["params"])
        genomes = [ind.get_genes() for ind in fresh]
        forward = cnn.GeneticCnnModel.cross_validate_population(
            x, y, genomes, **work["params"])
        backward = cnn.GeneticCnnModel.cross_validate_population(
            x, y, genomes[::-1], **work["params"])[::-1]
        slot_diff = [abs(float(a) - float(b)) for a, b in zip(forward, backward)]
        repeat = {"evaluated": 2 * len(genomes),
                  "xla_compiles": len(compile_times) - n_compiles,
                  "wall_s": round(time.monotonic() - t_repeat, 2),
                  "fitness_mean": round(float(sum(forward)) / len(genomes), 4),
                  "slot_order_max_diff": round(max(slot_diff), 5)}
    if repeat["xla_compiles"]:
        failed.append(f"a repeated population shape compiled {repeat['xla_compiles']} program(s)")
    if max(slot_diff) > SLOT_ORDER_TOL:
        worst = slot_diff.index(max(slot_diff))
        failed.append(f"fitness depends on the slot: genome {worst} scores {forward[worst]:.4f} "
                      f"in slot {worst} and {backward[worst]:.4f} in slot {len(genomes) - 1 - worst}")
    donation = [str(w.message) for w in caught if "donat" in str(w.message).lower()]
    if donation:
        failed.append(f"donated buffers were not usable: {donation[0]}")

    recs = records.snapshot()
    line: Dict[str, Any] = {
        "phase": "local", "platform": device["platform"],
        "device_kind": device["kind"], "device_count": device["count"],
        "versions": versions(),
        "cache_dir": jax.config.jax_compilation_cache_dir,
        "backend_init_s": round(backend_init_s, 2),
        "wall_s": round(run_wall, 2),
    }
    line.update(summarize_spans(recs, device["count"], work["pop"], failed))
    line["rest_s"] = round(run_wall - line["first_call_s"], 2)
    line["cache_entries"] = len(list_cache_entries())
    line["xla_compiles"] = len(compile_times)
    line["cache_requests"], line["cache_hits"] = cache["requests"], cache["hits"]

    # A compile that lands inside a call already labelled train/eval is a
    # second compile of a program shape this process had run before.
    again_compiled = 0
    for s in device_spans(recs):
        if s["kind"] != "compile":
            t0 = s["t_wall"]
            again_compiled += sum(1 for t in compile_times if t0 <= t <= t0 + s["dur_s"])
    line["recompiles_on_seen_shape"] = again_compiled
    if again_compiled:
        failed.append(f"{again_compiled} compile(s) inside calls of an already-run program shape")

    line.update(check_history(ga.history, device["count"], failed))
    line.update(check_fitness(list(ga.population.fitness_cache.values()),
                              [ind.get_fitness() for ind in ga.population],
                              rehearsal, failed))
    line["oom_cap_set"] = bool(cnn._POP_PROGRAM_CAP)
    if line["oom_cap_set"]:
        failed.append(f"_POP_PROGRAM_CAP was set: {dict(cnn._POP_PROGRAM_CAP)}")
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in jax.local_devices()]
    line["bytes_in_use"] = in_use
    # The CPU allocator reports nothing; on the chip every device must hold data.
    if device["platform"] == "tpu" and not all(in_use):
        failed.append(f"a device holds no memory after the run: bytes_in_use={in_use}")
    line["repeat"] = repeat
    line["donation_warnings"] = len(donation)
    line["failed"] = failed
    os.makedirs(OUT_DIR, exist_ok=True)
    records.dump(os.path.join(OUT_DIR, "local.records.jsonl"))
    emit(line, rehearsal)
    return 1 if failed else 0


def child_dryrun(rehearsal: bool) -> int:
    device = require_device(rehearsal)
    import __graft_entry__

    t0 = time.monotonic()
    __graft_entry__.dryrun_multichip(device["count"])
    emit({"phase": "dryrun", "platform": device["platform"],
          "device_kind": device["kind"], "device_count": device["count"],
          "wall_s": round(time.monotonic() - t0, 2), "failed": []}, rehearsal)
    return 0


# ---------------------------------------------------------------------------
# Parent: starts, joins and reaps the children; never touches a device
# ---------------------------------------------------------------------------


def reap(proc: subprocess.Popen) -> None:
    """SIGTERM the child's whole group, wait, SIGKILL what is left."""
    for sig, wait_s in ((signal.SIGTERM, 30.0), (signal.SIGKILL, 10.0)):
        if proc.poll() is not None:
            break
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            break
        try:
            proc.wait(timeout=wait_s)
        except subprocess.TimeoutExpired:
            continue
    try:  # stragglers of an already-dead leader
        os.killpg(proc.pid, signal.SIGKILL)
    except (ProcessLookupError, PermissionError):
        pass


def run_phase_child(phase: str, rehearsal: bool, timeout: float) -> Dict[str, Any]:
    """Run ``chip_smoke.py --phase <phase>`` to its end; its JSON line."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    if rehearsal:
        cmd.append("--rehearsal")
    proc = subprocess.Popen(cmd, cwd=REPO, stdout=subprocess.PIPE, text=True,
                            start_new_session=True)
    timed_out = False
    try:
        out, _ = proc.communicate(timeout=timeout)
    except subprocess.TimeoutExpired:
        timed_out = True
    finally:
        reap(proc)
    if timed_out:
        out, _ = proc.communicate()  # what it wrote before it was stopped
    sys.stdout.write(out)
    sys.stdout.flush()
    if timed_out:
        raise PhaseFailed(f"{phase}: child timed out after {timeout:.0f} s")
    line: Optional[Dict[str, Any]] = None
    for text in out.splitlines():
        if text.startswith("{"):
            line = json.loads(text)
    if proc.returncode != 0:
        why = "; ".join(line["failed"]) if line and line.get("failed") else "see its stderr"
        raise PhaseFailed(f"{phase}: child exited {proc.returncode}: {why}")
    if line is None or line.get("failed"):
        raise PhaseFailed(f"{phase}: child printed no passing line")
    return line


def phase_distributed(rehearsal: bool, timeout: float, local: Dict[str, Any],
                      cache_dir: str) -> Dict[str, Any]:
    from gentun_tpu import DistributedPopulation, GeneticAlgorithm, GeneticCnnIndividual
    from gentun_tpu.telemetry import lineage, spans
    from gentun_tpu.utils.xla_cache import list_cache_entries

    work = workload(rehearsal)
    records = Records()
    spans.set_run_sink(records)
    spans.enable()
    lineage.enable()  # the broker's requeue events are how a retried job shows
    os.makedirs(OUT_DIR, exist_ok=True)
    worker_log = os.path.join(OUT_DIR, "worker.log")
    entries_before = set(list_cache_entries(cache_dir))
    failed: List[str] = []
    outcome: Dict[str, Any] = {}
    pop = DistributedPopulation(
        GeneticCnnIndividual, size=work["pop"], seed=0, port=0,
        additional_parameters=work["params"], job_timeout=timeout)
    worker: Optional[subprocess.Popen] = None
    t0 = time.monotonic()
    try:
        port = pop.broker_address[1]
        with open(worker_log, "w", encoding="utf-8") as log:
            worker = subprocess.Popen(
                [sys.executable, "-m", "gentun_tpu.distributed.worker",
                 "--port", str(port), "--species", "genetic-cnn",
                 "--dataset", "cifar10", "--n", str(work["n_data"]),
                 "--capacity", str(work["pop"]), "--telemetry"],
                cwd=REPO, stdout=log, stderr=subprocess.STDOUT, start_new_session=True)
        ga = GeneticAlgorithm(pop, seed=0)

        def master() -> None:
            try:
                ga.run(work["generations"])
            except BaseException as e:  # noqa: BLE001 - reported by the parent below
                outcome["error"] = repr(e)

        thread = threading.Thread(target=master, name="chip-smoke-master", daemon=True)
        thread.start()
        deadline = t0 + timeout
        while thread.is_alive():
            thread.join(1.0)
            if worker.poll() is not None:
                raise PhaseFailed(f"distributed: the worker exited {worker.returncode} "
                                  f"mid-search (log: {worker_log})")
            if time.monotonic() > deadline:
                raise PhaseFailed(f"distributed: search not done after {timeout:.0f} s")
        if "error" in outcome:
            raise PhaseFailed(f"distributed: the search raised {outcome['error']}")
        wall = time.monotonic() - t0
        devices = pop.broker.fleet_devices()  # what the master saw in hello
        outstanding = ga.population.broker.outstanding()
    finally:
        if worker is not None:
            reap(worker)
        pop.close()
        spans.set_run_sink(None)
        spans.disable()
        lineage.disable()
        records.dump(os.path.join(OUT_DIR, "distributed.records.jsonl"))

    if len(devices) != 1:
        raise PhaseFailed(f"distributed: expected one device advert in hello, master saw {devices}")
    device = devices[0]
    recs = records.snapshot()
    line: Dict[str, Any] = {
        "phase": "distributed", "platform": device["platform"],
        "device_kind": device["kind"], "device_count": device["count"],
        "versions": versions(), "cache_dir": cache_dir, "wall_s": round(wall, 2),
    }
    line.update(summarize_spans(recs, device["count"], work["pop"], failed))
    line["rest_s"] = round(wall - line["first_call_s"], 2)
    if [device["platform"], device["kind"], device["count"]] != \
            [local["platform"], local["device_kind"], local["device_count"]]:
        failed.append(f"worker reported {device}, the local phase ran on "
                      f"{local['platform']}/{local['device_kind']}/{local['device_count']}")
    line.update(check_history(ga.history, device["count"], failed))
    line.update(check_fitness(list(ga.population.fitness_cache.values()),
                              [ind.get_fitness() for ind in ga.population],
                              rehearsal, failed))

    requeued = [r for r in recs if r.get("type") == "lineage" and r.get("event") == "requeued"]
    retries = sum(h.get("evaluate_retries", 0) for h in ga.history)
    penalized = sum(h.get("penalized", 0) for h in ga.history)
    line["jobs_requeued"], line["evaluate_retries"], line["penalized"] = \
        len(requeued), retries, penalized
    if requeued or retries or penalized:
        failed.append(f"{len(requeued)} job(s) requeued, {retries} sweep retries, "
                      f"{penalized} penalized: the fleet did not evaluate cleanly")
    if any(outstanding.values()):
        failed.append(f"broker not quiescent after the search: {outstanding}")

    # Entries this phase had to write are cache misses; the train programs
    # it ran for the first time without writing one, it found.
    new = set(list_cache_entries(cache_dir)) - entries_before
    new_train = sum(1 for name in new if name.startswith("jit_train_segment-"))
    line["cache_entries_written"] = len(new)
    line["train_programs_found_in_cache"] = line["first_call_train_programs"] - new_train
    if line["train_programs_found_in_cache"] < 1:
        failed.append(f"the worker ran {line['first_call_train_programs']} train program(s) "
                      f"for the first time and wrote {new_train} cache entries for them: "
                      "it found none of the local phase's")
    line["failed"] = failed
    emit(line, rehearsal)
    if failed:
        raise PhaseFailed("distributed: " + "; ".join(failed))
    return line


def parent(rehearsal: bool) -> int:
    t_start = time.monotonic()

    def remaining(phase: str) -> float:
        return max(30.0, min(PHASE_CAP_S[phase], BUDGET_S - (time.monotonic() - t_start)))

    # SIGTERM must unwind through the finally blocks that reap the children.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    try:
        try:
            import jax
            from jax._src import xla_bridge

            from gentun_tpu.utils import jax_state
            from gentun_tpu.utils.xla_cache import default_cache_dir, list_cache_entries
        except ImportError as e:
            raise PhaseFailed(f"not a checkout of the repository: {e}") from None
        cache_dir = default_cache_dir()
        if cache_dir is None:
            raise PhaseFailed("the compile cache is switched off (GENTUN_TPU_CACHE_DIR)")
        entries_before = len(list_cache_entries(cache_dir))
        local = run_phase_child("local", rehearsal, remaining("local"))
        if os.path.abspath(local["cache_dir"] or "") != os.path.abspath(cache_dir):
            raise PhaseFailed(f"local: cache is at {local['cache_dir']}, "
                              f"the rule says {cache_dir}")
        written = len(list_cache_entries(cache_dir)) - entries_before
        print(f"chip_smoke: local phase wrote {written} cache entries to {cache_dir}",
              file=sys.stderr)
        phase_distributed(rehearsal, remaining("distributed"), local, cache_dir)
        if local["device_count"] > 1:
            run_phase_child("dryrun", rehearsal, remaining("dryrun"))
        if jax_state.backend_used() or xla_bridge.backends_are_initialized():
            raise PhaseFailed("the parent initialized a jax backend")
    except PhaseFailed as e:
        print(f"chip_smoke: FAIL: {e}", flush=True)
        return 1
    print(f"chip_smoke: all phases passed in {time.monotonic() - t_start:.0f} s",
          file=sys.stderr)
    device = {"platform": local["platform"], "kind": local["device_kind"],
              "count": local["device_count"]}
    if rehearsal:
        print(json.dumps({"rehearsal": True, "rehearsal_passed": True, "device": device}),
              flush=True)
    else:
        print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny shapes, CPU allowed; marks every line, never prints a pass")
    ap.add_argument("--phase", choices=("local", "dryrun"), help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    if args.phase == "local":
        return child_local(args.rehearsal)
    if args.phase == "dryrun":
        return child_dryrun(args.rehearsal)
    return parent(args.rehearsal)


if __name__ == "__main__":
    sys.exit(main())
