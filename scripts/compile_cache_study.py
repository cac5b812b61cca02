"""Acceptance study for the fleet-wide compile cache (DISTRIBUTED.md
"Fleet-wide compile cache").

Three measured acts, written to ``scripts/compile_cache_study.json``:

1. **Cold join** (real jax): the time a freshly-joined host pays before
   its first result, before vs after the service.  Before = a full XLA
   compile.  After = network fetch of the artifact + a persistent-cache
   *load* of the same program.  Both sides are micro-timed compile/fetch
   costs (``time.perf_counter`` around the exact call), NOT a wall-clock
   A/B of whole runs — this box has one core and ±10-20% run-to-run
   noise, so whole-run timing cannot resolve the effect; the structural
   proof is byte-level: the warm host's cache dir gains ZERO new entries
   when it "compiles", i.e. no true recompile happened.

2. **Recompile storm** (real jax): one host compiles three distinct
   programs and publishes; three late joiners prefetch, then compile the
   same three programs after ``jax.clear_caches()``.  True compiles are
   counted as NEW files in each host's cache dir (a persistent-cache hit
   loads without writing).  Asserted: late joiners perform ZERO true
   compiles — fleet-wide, each program shape is compiled at most once.

3. **Service killed mid-search** (jax-free, seeded): a distributed
   OneMax search with the compile service killed after the first
   generation must finish bit-identical to a service-free single-process
   run, with exactly ONE ``compile_service_degraded`` event — cache
   downtime costs recompiles, never correctness.

CPU-only, self-contained: ``python scripts/compile_cache_study.py``.
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from gentun_tpu import GeneticAlgorithm, Individual, Population, genetic_cnn_genome  # noqa: E402
from gentun_tpu.distributed import DistributedPopulation, GentunClient  # noqa: E402
from gentun_tpu.distributed.compile_service import (  # noqa: E402
    CompileService,
    CompileServiceClient,
    platform_fingerprint,
)
from gentun_tpu.telemetry import spans as spans_mod  # noqa: E402
from gentun_tpu.utils.xla_cache import enable_compilation_cache, list_cache_entries  # noqa: E402


# -- act 1 + 2 scaffolding: tiny distinct XLA programs -----------------------

def _compile_program(width: int) -> float:
    """jit-compile a ``width``-wide program; returns the compile seconds.

    The returned time covers exactly ``lower().compile()`` — the step the
    persistent cache short-circuits — so cold (true compile) and warm
    (cache load) calls are directly comparable micro-timings.
    """
    import jax
    import jax.numpy as jnp

    def f(x):
        for _ in range(3):
            x = jnp.tanh(x @ jnp.ones((width, width), x.dtype))
        return x.sum()

    x = jnp.zeros((4, width), jnp.float32)
    lowered = jax.jit(f).lower(x)
    t0 = time.perf_counter()
    lowered.compile()
    return time.perf_counter() - t0


def run_cold_join_study() -> dict:
    """Micro-timed cold-join cost, before vs after the compile service."""
    import jax

    root = tempfile.mkdtemp(prefix="compile-study-")
    svc = CompileService(port=0).start()
    try:
        # BEFORE: a cold host pays the full XLA compile.
        dir_a = os.path.join(root, "host_a")
        assert enable_compilation_cache(dir_a) == dir_a
        t_compile = _compile_program(16)
        entries_a = list_cache_entries(dir_a)
        assert entries_a, "compile wrote no persistent-cache entries"

        # Host A publishes its artifacts to the fleet.
        client_a = CompileServiceClient(svc.url, cache_dir=dir_a)
        client_a.scan_publish()
        assert client_a.flush(10.0), "publish queue failed to drain"
        client_a.close()

        # AFTER: host B joins cold — prefetch (micro-timed) ...
        dir_b = os.path.join(root, "host_b")
        client_b = CompileServiceClient(svc.url, cache_dir=dir_b)
        t0 = time.perf_counter()
        fetched = client_b.prefetch()
        t_fetch = time.perf_counter() - t0
        client_b.close()
        assert fetched == len(entries_a), (
            f"prefetch pulled {fetched}/{len(entries_a)} entries")

        # ... then "compiles": the persistent cache must serve a LOAD.
        jax.clear_caches()
        assert enable_compilation_cache(dir_b) == dir_b
        before = set(list_cache_entries(dir_b))
        t_load = _compile_program(16)
        after = set(list_cache_entries(dir_b))
        assert after == before, (
            "warm host wrote new cache entries — it truly recompiled")
    finally:
        svc.stop()
        shutil.rmtree(root, ignore_errors=True)

    before_s = t_compile
    after_s = t_fetch + t_load
    return {
        "program_entries": len(entries_a),
        "cold_join_before_s": round(before_s, 4),
        "cold_join_after_s": round(after_s, 4),
        "compile_s": round(t_compile, 4),
        "fetch_s": round(t_fetch, 4),
        "cache_load_s": round(t_load, 4),
        "speedup_x": round(before_s / after_s, 2) if after_s > 0 else None,
        "warm_host_wrote_new_entries": False,
    }


def run_recompile_storm_jax() -> dict:
    """Real-jax storm: late joiners must perform ZERO true compiles."""
    import jax

    widths = (9, 13, 17)  # three distinct program shapes
    root = tempfile.mkdtemp(prefix="compile-storm-")
    svc = CompileService(port=0).start()
    compiles_per_host = {}
    try:
        # Host 0 pays the compiles and publishes.
        jax.clear_caches()
        dir_0 = os.path.join(root, "host0")
        assert enable_compilation_cache(dir_0) == dir_0
        for w in widths:
            _compile_program(w)
        n_artifacts = len(list_cache_entries(dir_0))
        compiles_per_host["host0"] = n_artifacts
        client_0 = CompileServiceClient(svc.url, cache_dir=dir_0)
        client_0.scan_publish()
        assert client_0.flush(10.0)
        client_0.close()

        # Hosts 1-3 join in a storm: prefetch, then need every shape.
        for h in (1, 2, 3):
            d = os.path.join(root, f"host{h}")
            client = CompileServiceClient(svc.url, cache_dir=d)
            fetched = client.prefetch()
            client.close()
            assert fetched == n_artifacts
            jax.clear_caches()
            assert enable_compilation_cache(d) == d
            prefetched = set(list_cache_entries(d))
            for w in widths:
                _compile_program(w)
            new_files = set(list_cache_entries(d)) - prefetched
            compiles_per_host[f"host{h}"] = len(new_files)
            assert not new_files, (
                f"host{h} truly recompiled {sorted(new_files)}")
    finally:
        svc.stop()
        shutil.rmtree(root, ignore_errors=True)

    total = sum(compiles_per_host.values())
    assert total == n_artifacts, "a shape was compiled more than once"
    return {
        "program_shapes": len(widths),
        "artifacts": n_artifacts,
        "compiles_per_host": compiles_per_host,
        "fleet_wide_true_compiles": total,
        "max_compiles_per_shape_fleet_wide": 1,
        "late_joiner_true_compiles": 0,
    }


# -- act 3: service killed mid-search ----------------------------------------

DATA = (np.zeros(1, np.float32), np.zeros(1, np.float32))


class OneMax(Individual):
    """Deterministic jax-free fitness: local and distributed runs are
    comparable bit-for-bit (same pattern as scripts/chaos_run.py)."""

    def build_spec(self, **params):
        return genetic_cnn_genome(tuple(params.get("nodes", (4, 4))))

    def evaluate(self):
        return float(sum(sum(g) for g in self.genes.values()))


class _ListSink:
    def __init__(self):
        self.records = []

    def record(self, rec):
        self.records.append(rec)


def _snapshot(ga):
    return {
        "history": [r["best_fitness"] for r in ga.history],
        "final": [
            {"genes": {k: list(v) for k, v in ind.get_genes().items()},
             "fitness": ind.get_fitness()}
            for ind in ga.population
        ],
    }


def run_service_killed_study() -> dict:
    """Kill the compile service mid-search: bit-identical, ONE event."""
    generations, pop_size, pop_seed, ga_seed = 4, 8, 42, 7

    ref = GeneticAlgorithm(
        Population(OneMax, *DATA, size=pop_size, seed=pop_seed), seed=ga_seed)
    ref.run(generations)

    root = tempfile.mkdtemp(prefix="compile-kill-")
    cache_dir = os.path.join(root, "xla")
    saved_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    sink = _ListSink()
    spans_mod.enable()
    spans_mod.set_run_sink(sink)

    svc = CompileService(port=0).start()
    # Pre-seed one artifact under the worker's fingerprint (OneMax never
    # probes devices) so the join-time prefetch is exercised too.
    svc.publish(platform_fingerprint(probe_devices=False),
                [("entry_warm", b"warm-artifact")])

    stop = threading.Event()
    try:
        with DistributedPopulation(OneMax, size=pop_size, seed=pop_seed,
                                   port=0, job_timeout=60.0) as pop:
            _, port = pop.broker_address
            worker = GentunClient(
                OneMax, *DATA, port=port, capacity=4,
                heartbeat_interval=0.2, reconnect_delay=0.05,
                compile_cache_url=svc.url)
            t = threading.Thread(
                target=lambda: worker.work(stop_event=stop), daemon=True)
            t.start()
            ga = GeneticAlgorithm(pop, seed=ga_seed)

            def _kill_then_dirty():
                # Pull the plug mid-search, then dirty the local cache so
                # the next publish scan must talk to the dead service.
                while not ga.history:
                    time.sleep(0.005)
                svc.stop()
                with open(os.path.join(cache_dir, "entry_fresh"), "wb") as fh:
                    fh.write(b"freshly-compiled")

            killer = threading.Thread(target=_kill_then_dirty, daemon=True)
            killer.start()
            ga.run(generations)
            killer.join(timeout=10)
            stats = worker._compile_client.stats()

        identical = _snapshot(ga) == _snapshot(ref)
        assert identical, "compile-service kill perturbed the search"
        assert stats["fetched"] == 1, "join-time prefetch did not run"

        # Stop the worker: its close() runs the final publish scan, which
        # finds entry_fresh and must hit the dead service → degraded path.
        stop.set()
        t.join(timeout=10)
        deadline = time.monotonic() + 5.0
        evs = []
        while time.monotonic() < deadline:
            evs = [r for r in sink.records
                   if r.get("type") == "event"
                   and r["name"] == "compile_service_degraded"]
            if evs:
                break
            time.sleep(0.02)  # flusher may still be timing out on the POST
        assert len(evs) == 1, f"expected ONE degraded event, got {len(evs)}"
    finally:
        stop.set()
        try:
            svc.stop()
        except Exception:
            pass
        spans_mod.disable()
        spans_mod.set_run_sink(None)
        if saved_env is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = saved_env
        shutil.rmtree(root, ignore_errors=True)

    return {
        "generations": generations,
        "bit_identical_to_service_free_run": True,
        "prefetched_artifacts": stats["fetched"],
        "degraded_events": len(evs),
        "worker_compile_cache": {k: stats[k] for k in
                                 ("fetched", "published", "degraded")},
    }


if __name__ == "__main__":
    out = {
        "cold_join": run_cold_join_study(),
        "recompile_storm_jax": run_recompile_storm_jax(),
        "service_killed": run_service_killed_study(),
    }
    print(json.dumps(out, indent=2))
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "compile_cache_study.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=2)
        f.write("\n")
    print(f"wrote {path}")
