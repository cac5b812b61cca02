"""Multi-fidelity evolution: the ASHA promotion ladder, its checkpoint and
wire surfaces, and the fidelity-fingerprinted fitness store.

Covers the PR's acceptance gates not already exercised by
``scripts/fidelity_study.py``: promotion × cancel × straggler-requeue on a
real fleet (a speculatively requeued rung-k job must not double-promote;
a cancelled stale promotion must not leak ``jobs_in_flight``), the
schema-v3 checkpoint round-trip of in-flight and QUEUED promotions, the
per-rung fitness-cache/telemetry counters, and the worker-side rejection
of unknown fidelity tags with back-compat for tagless masters.
"""

import json
import threading
import time

import numpy as np
import pytest

from gentun_tpu import AsyncEvolution, Individual, Population, genetic_cnn_genome
from gentun_tpu.distributed import (
    DistributedPopulation,
    FaultInjector,
    FaultPlan,
    FaultSpec,
    GentunClient,
)
from gentun_tpu.distributed.faults import MasterKilled
from gentun_tpu.telemetry import spans as spans_mod
from gentun_tpu.telemetry.registry import get_registry
from gentun_tpu.utils import Checkpointer, fidelity_fingerprint
from gentun_tpu.utils.fitness_store import (
    STORE_VERSION,
    load_fitness_cache,
    save_fitness_cache,
)


class OneMax(Individual):
    def build_spec(self, **params):
        return genetic_cnn_genome(tuple(params.get("nodes", (4, 4))))

    def evaluate(self):
        return float(sum(sum(g) for g in self.genes.values()))


class SlowOneMax(OneMax):
    def evaluate(self):
        time.sleep(0.15)
        return super().evaluate()


DATA = (np.zeros(1, np.float32), np.zeros(1, np.float32))
#: Fidelity knobs chosen from FIDELITY_KNOBS so each rung fingerprints —
#: and therefore cache-keys — differently.
LADDER = [{"kfold": 2, "epochs": (1,)}, {"kfold": 5, "epochs": (4,)}]


@pytest.fixture(autouse=True)
def _pristine_telemetry():
    spans_mod.disable()
    spans_mod.set_run_sink(None)
    get_registry().reset()
    yield
    spans_mod.disable()
    spans_mod.set_run_sink(None)
    get_registry().reset()


def _pop(size=8, seed=11, **kw):
    return Population(OneMax, DATA, size=size, seed=seed, maximize=True, **kw)


def _engine(pop=None, ladder=LADDER, **kw):
    kw.setdefault("tournament_size", 3)
    kw.setdefault("max_in_flight", 1)
    kw.setdefault("seed", 5)
    return AsyncEvolution(pop or _pop(), fidelity_ladder=ladder, eta=3, **kw)


def _sig(eng):
    return [(h["fitness"], h.get("rung")) for h in eng.history]


class TestLadderEngine:
    def test_everything_starts_at_rung_zero_and_climbs(self):
        eng = _engine()
        best = eng.run(max_evaluations=60)
        rungs = [h["rung"] for h in eng.history]
        assert set(rungs) <= {0, 1}
        # The founding cohort and every bred child measured at rung 0 first.
        first_by = {}
        for h in eng.history:
            first_by.setdefault(h["completed"], h["rung"])
        assert rungs[0] == 0
        # Something actually promoted, and the reported best is top-rung.
        assert any(h.get("promotion") for h in eng.history)
        assert getattr(best, "_rung", None) == 1

    def test_promotion_rate_bounded_by_eta(self):
        eng = _engine()
        eng.run(max_evaluations=90)
        r0, r1 = (len(v) for v in eng._rung_completions)
        assert r1 > 0
        # The ASHA invariant the quota fix enforces: rung sizes stay
        # geometric — promotions from rung 0 never exceed completions//eta.
        assert r1 <= r0 // eng.eta

    def test_same_seed_same_trajectory(self):
        runs = []
        for _ in range(2):
            eng = _engine()
            best = eng.run(max_evaluations=60)
            runs.append((best.get_genes(), _sig(eng)))
        assert runs[0] == runs[1]

    def test_ladderless_history_shape_unchanged(self):
        # fidelity_ladder=None keeps the legacy engine bit-identical —
        # including the absence of ladder keys in history entries.
        eng = AsyncEvolution(_pop(), tournament_size=3, max_in_flight=1, seed=5)
        eng.run(max_evaluations=20)
        assert all("rung" not in h and "promotion" not in h for h in eng.history)

    def test_rung_overlays_key_cache_disjointly(self):
        pop = _pop(size=4, seed=3, additional_parameters={"nodes": (2,)})
        eng = _engine(pop=pop)
        eng.run(max_evaluations=40)
        # A 2-genome space at 2 rungs → at most 4 distinct cache keys, and
        # the same genes appear under BOTH rung overlays (disjoint keys).
        keys = list(pop.fitness_cache)
        params = {k[-1] for k in keys}
        assert len(params) == 2, params

    def test_statusz_rung_snapshot(self):
        eng = _engine()
        eng.run(max_evaluations=40)
        status = eng._ops_status()
        assert [r["rung"] for r in status["rungs"]] == [0, 1]
        assert status["rungs"][0]["completions"] == len(eng._rung_completions[0])
        assert status["rungs"][1]["best_fitness"] == eng.best.get_fitness()

    def test_cache_hit_and_miss_counters_per_rung(self):
        spans_mod.enable()
        pop = _pop(size=4, seed=3, additional_parameters={"nodes": (2,)})
        eng = _engine(pop=pop)
        eng.run(max_evaluations=40)
        reg = get_registry()
        hits = sum(reg.counter("fitness_cache_hits_total", rung=str(r)).value
                   for r in (0, 1))
        misses = sum(reg.counter("fitness_cache_misses_total", rung=str(r)).value
                     for r in (0, 1))
        assert hits > 0 and misses > 0
        # With 2 genomes and 2 rungs there are exactly 4 unique measurements.
        assert misses == 4
        assert reg.counter("promotions_total", rung="1").value > 0


class TestLadderCheckpoint:
    def test_schema_round_trip_with_inflight_promotion(self, tmp_path):
        ref = _engine(checkpoint_every=2)
        ref.run(max_evaluations=60)

        path = str(tmp_path / "ladder-ckpt.json")
        promotion_seen = False
        for at in range(2, 14):
            p = str(tmp_path / f"probe-{at}.json")
            eng = _engine(checkpoint_every=2)
            eng.set_fault_injector(FaultInjector(FaultPlan([
                FaultSpec(hook="master_boundary", kind="kill_master", at=at)])))
            with pytest.raises(MasterKilled):
                eng.run(max_evaluations=60, checkpointer=Checkpointer(p))
            state = json.load(open(p))
            assert state["schema_version"] == 4
            entries = state["in_flight"] + state.get("queued", [])
            if any(e.get("kind") == "promotion" for e in entries):
                promotion_seen, path = True, p
                break
        assert promotion_seen, "no kill boundary caught a promotion in flight"

        resumed = _engine(checkpoint_every=2)
        best = resumed.run(max_evaluations=60, checkpointer=Checkpointer(path))
        assert _sig(resumed) == _sig(ref)
        assert best.get_genes() == ref.best.get_genes()

    def test_laddered_state_carries_rung_fields(self, tmp_path):
        path = str(tmp_path / "ck.json")
        eng = _engine(checkpoint_every=2)
        eng.run(max_evaluations=40, checkpointer=Checkpointer(path))
        state = json.load(open(path))
        assert state["ladder"] == LADDER or state["ladder"] == [
            {**r, "epochs": list(r["epochs"])} for r in LADDER]
        assert state["eta"] == 3
        assert len(state["rung_completions"]) == 2
        assert all("rung" in m for m in state["population"]["individuals"])
        assert [b["rung"] for b in state["best_by_rung"]] == sorted(
            b["rung"] for b in state["best_by_rung"])

    def test_v2_shaped_checkpoint_resumes_into_ladder(self, tmp_path):
        # A pre-ladder (v2) checkpoint — in_flight as bare genes, no ladder
        # keys — must resume under a ladder ctor: entries become rung-0
        # children, members rung 0.
        state = path = None
        for at in range(1, 8):
            path = str(tmp_path / f"ck-{at}.json")
            legacy = AsyncEvolution(_pop(), tournament_size=3, max_in_flight=1,
                                    seed=5, checkpoint_every=2)
            legacy.set_fault_injector(FaultInjector(FaultPlan([
                FaultSpec(hook="master_boundary", kind="kill_master", at=at)])))
            with pytest.raises(MasterKilled):
                legacy.run(max_evaluations=40, checkpointer=Checkpointer(path))
            state = json.load(open(path))
            if state["in_flight"]:
                break
        # v2 entries are bare genes dicts — no "kind"/"rung" envelope.
        assert state["in_flight"] and "kind" not in state["in_flight"][0]
        assert "ladder" not in state

        eng = _engine(checkpoint_every=2)
        eng.run(max_evaluations=40, checkpointer=Checkpointer(path))
        assert eng.completed == 40
        # The ladder applies from the resume on: later completions climb.
        assert any(h.get("rung") == 1 for h in eng.history[state["completed"]:])

    def test_ladderless_checkpoint_keeps_v2_shape(self, tmp_path):
        path = str(tmp_path / "ck.json")
        eng = AsyncEvolution(_pop(), tournament_size=3, max_in_flight=1,
                             seed=5, checkpoint_every=2)
        eng.set_fault_injector(FaultInjector(FaultPlan([
            FaultSpec(hook="master_boundary", kind="kill_master", at=1)])))
        with pytest.raises(MasterKilled):
            eng.run(max_evaluations=40, checkpointer=Checkpointer(path))
        state = json.load(open(path))
        assert "ladder" not in state and "queued" not in state
        assert state["dispatched"] == state["completed"] + len(state["in_flight"])


class TestPromotionCancel:
    def test_eviction_cancels_pending_promotion_and_run_stays_consistent(self):
        # Small ring + long budget → heavy aging eviction while promotions
        # are pending.  The engine must finish with every accounting
        # invariant intact: budget reached, no member left marked pending,
        # dispatched == completed once the queue drained.
        eng = _engine(pop=_pop(size=4), checkpoint_every=4)
        eng.run(max_evaluations=80)
        assert eng.completed == 80
        assert not any(getattr(m, "_promo_pending", False)
                       for m in eng.population)
        assert eng.dispatched == eng.completed

    def test_promotion_failure_marks_member_and_refunds_slot(self):
        class FlakyPromo(OneMax):
            def evaluate(self):
                if self.additional_parameters.get("kfold") == 5:
                    raise RuntimeError("full schedule OOM")
                return super().evaluate()

        pop = Population(FlakyPromo, DATA, size=6, seed=11, maximize=True)
        eng = AsyncEvolution(pop, tournament_size=3, max_in_flight=1, seed=5,
                             fidelity_ladder=LADDER, eta=3)
        eng.run(max_evaluations=60)
        assert eng.completed == 60
        # Every promotion attempt failed; members stay at rung 0 with their
        # proxy fitness intact and are marked no-retry.
        failed = [h for h in eng.history if h.get("failed")]
        assert failed and all(h["rung"] == 1 for h in failed)
        assert all(getattr(m, "_rung", 0) == 0 for m in eng.population)
        assert any(getattr(m, "_promo_failed_rung", None) == 1
                   for m in eng.population)
        # Refunded slots let later candidates keep trying: more attempts
        # than a single quota's worth of members.
        assert len(failed) >= 2


@pytest.mark.slow
class TestLadderFleet:
    def test_ladder_on_fleet_with_straggler_requeue_no_double_promote(self):
        """E2E: 2-worker fleet, straggler requeue armed and aggressive.  A
        requeued rung-k promotion redelivers to the other worker; result
        dedup on the broker means the engine sees ONE completion — so
        promotions stay within the eta quota and nothing leaks."""
        spans_mod.enable()
        reg = get_registry()
        pop = DistributedPopulation(
            SlowOneMax, size=6, seed=7, port=0, job_timeout=60, maximize=True,
            straggler_floor_s=0.05, straggler_k=1.1, straggler_requeue=True)
        stops = []
        try:
            _, port = pop.broker_address
            for i in range(2):
                stop = threading.Event()
                client = GentunClient(
                    SlowOneMax, *DATA, host="127.0.0.1", port=port,
                    capacity=1, worker_id=f"fid-w{i}",
                    heartbeat_interval=0.2, reconnect_delay=0.05)
                threading.Thread(
                    target=lambda c=client, s=stop: c.work(stop_event=s),
                    daemon=True).start()
                stops.append(stop)
            eng = AsyncEvolution(pop, tournament_size=3, seed=5,
                                 fidelity_ladder=LADDER, eta=3, job_timeout=60)
            eng.run(max_evaluations=24)
            assert eng.completed == 24
            r0, r1 = (len(v) for v in eng._rung_completions)
            assert r1 <= r0 // eng.eta
            # No duplicated completions: each history step is distinct.
            assert [h["completed"] for h in eng.history] == list(range(1, 25))
            # The broker went quiescent — a stale promotion cancel or a
            # requeue race would leave outstanding counts behind.
            out = pop.broker.outstanding()
            assert all(v == 0 for v in out.values()), out
            assert reg.gauge("jobs_in_flight").value == 0
        finally:
            for s in stops:
                s.set()
            pop.close()


class TestFidelityTagWire:
    def test_tagless_job_accepted(self):
        assert GentunClient._check_fidelity({"job_id": "j1", "genes": {}}) is None

    def test_matching_tag_accepted(self):
        params = {"nodes": (2,), "kfold": 2, "epochs": (1,)}
        job = {"job_id": "j1", "genes": {}, "additional_parameters": params,
               "fidelity": {"v": 1, "rung": 0,
                            "fingerprint": fidelity_fingerprint(params)}}
        assert GentunClient._check_fidelity(job) is None

    def test_unknown_tag_version_rejected(self):
        job = {"job_id": "j1", "genes": {},
               "fidelity": {"v": 2, "rung": 0, "fingerprint": "ab"}}
        reason = GentunClient._check_fidelity(job)
        assert reason is not None and "version" in reason

    def test_mislabeled_fingerprint_rejected(self):
        params = {"kfold": 2, "epochs": (1,)}
        other = fidelity_fingerprint({"kfold": 5, "epochs": (4,)})
        job = {"job_id": "j1", "genes": {}, "additional_parameters": params,
               "fidelity": {"v": 1, "rung": 0, "fingerprint": other}}
        reason = GentunClient._check_fidelity(job)
        assert reason is not None and "fingerprint" in reason

    def test_ladder_master_tags_jobs_and_fleet_accepts(self):
        # End-to-end: a laddered master stamps every dispatched job with a
        # fidelity tag; a current worker validates and evaluates normally.
        pop = DistributedPopulation(OneMax, size=4, seed=7, port=0,
                                    job_timeout=30, maximize=True)
        stop = threading.Event()
        try:
            _, port = pop.broker_address
            client = GentunClient(OneMax, *DATA, host="127.0.0.1", port=port,
                                  capacity=1, worker_id="tag-w0",
                                  heartbeat_interval=0.2, reconnect_delay=0.05)
            threading.Thread(target=lambda: client.work(stop_event=stop),
                             daemon=True).start()
            eng = AsyncEvolution(pop, tournament_size=3, seed=5,
                                 fidelity_ladder=LADDER, eta=3, job_timeout=30)
            eng.run(max_evaluations=12)
            assert eng.completed == 12
            assert any(h.get("rung") == 1 for h in eng.history)
        finally:
            stop.set()
            pop.close()

    def test_tagless_old_master_back_compat(self):
        # A ladderless master (= an old master on the wire: no fidelity
        # field is ever attached) against the CURRENT worker: everything
        # evaluates unchanged.
        pop = DistributedPopulation(OneMax, size=4, seed=7, port=0,
                                    job_timeout=30, maximize=True)
        stop = threading.Event()
        try:
            _, port = pop.broker_address
            client = GentunClient(OneMax, *DATA, host="127.0.0.1", port=port,
                                  capacity=1, worker_id="old-w0",
                                  heartbeat_interval=0.2, reconnect_delay=0.05)
            threading.Thread(target=lambda: client.work(stop_event=stop),
                             daemon=True).start()
            eng = AsyncEvolution(pop, tournament_size=3, seed=5, job_timeout=30)
            eng.run(max_evaluations=12)
            assert eng.completed == 12
        finally:
            stop.set()
            pop.close()


class TestFidelityFingerprintStore:
    def test_fingerprint_reads_only_fidelity_knobs(self):
        a = fidelity_fingerprint({"kfold": 2, "epochs": (1,), "nodes": (4, 4)})
        b = fidelity_fingerprint({"kfold": 2, "epochs": (1,), "nodes": (9, 9)})
        c = fidelity_fingerprint({"kfold": 5, "epochs": (1,), "nodes": (4, 4)})
        assert a == b != c

    def test_fingerprint_accepts_frozen_params(self):
        params = {"kfold": 2, "epochs": (1,)}
        frozen = tuple(sorted(params.items()))
        assert fidelity_fingerprint(params) == fidelity_fingerprint(frozen)

    def test_store_v3_round_trip_keeps_fidelity_keys(self, tmp_path):
        path = str(tmp_path / "store.json")
        cache = {
            ("OneMax", (("S_1", (1, 0, 1)),), (("epochs", (1,)), ("kfold", 2))): 3.0,
            ("OneMax", (("S_1", (1, 0, 1)),), (("epochs", (4,)), ("kfold", 5))): 2.5,
        }
        assert save_fitness_cache(cache, path) == 2
        data = json.load(open(path))
        assert data["version"] == STORE_VERSION == 3
        assert all(len(e) == 3 for e in data["entries"])
        assert load_fitness_cache(path) == cache

    def test_tampered_fingerprint_dropped_on_load(self, tmp_path):
        path = str(tmp_path / "store.json")
        cache = {
            ("OneMax", (("S_1", (1, 0)),), (("kfold", 2),)): 1.0,
            ("OneMax", (("S_1", (0, 1)),), (("kfold", 5),)): 2.0,
        }
        save_fitness_cache(cache, path)
        data = json.load(open(path))
        data["entries"][0][2] = "deadbeefdead"  # fidelity config renamed
        json.dump(data, open(path, "w"))
        loaded = load_fitness_cache(path)
        assert len(loaded) == 1
        assert list(loaded.values()) == [2.0]


class TestWarmStartBank:
    def _cfg(self, **kw):
        cfg = dict(nodes=(3,), kernels_per_layer=(4,), kfold=2, epochs=(1,),
                   learning_rate=(1e-2,), batch_size=8, dense_units=8,
                   seed=3, compute_dtype="float32", mesh=None)
        cfg.update(kw)
        return cfg

    def _data(self):
        rng = np.random.default_rng(0)
        x = rng.normal(size=(32, 8, 8, 1)).astype(np.float32)
        y = rng.integers(0, 2, size=32).astype(np.int32)
        return x, y

    def test_warm_start_off_by_default_and_bank_untouched(self):
        from gentun_tpu.models import cnn as cnn_mod
        from gentun_tpu.models.cnn import GeneticCnnModel

        cnn_mod._WARM_BANK.clear()
        x, y = self._data()
        GeneticCnnModel.cross_validate_population(
            x, y, [{"S_1": np.array([1, 0, 1])}], **self._cfg())
        assert not cnn_mod._WARM_BANK

    def test_deposit_then_inherit_across_rungs(self, monkeypatch):
        import jax

        from gentun_tpu.models import cnn as cnn_mod
        from gentun_tpu.models.cnn import GeneticCnnModel

        overlays = []  # per call: (fresh params per fold, params the run started from, slots warmed)
        real = cnn_mod._warm_start_overlay

        def spy(carries, hashes):
            started, warmed = real(carries, hashes)
            # host copies: the train program is donated its carries
            overlays.append(([jax.device_get(p) for p, _ in carries],
                             [jax.device_get(p) for p, _ in started], warmed, started is carries))
            return started, warmed

        monkeypatch.setattr(cnn_mod, "_warm_start_overlay", spy)
        cnn_mod._WARM_BANK.clear()
        x, y = self._data()
        genomes = [{"S_1": np.array([1, 0, 1])}, {"S_1": np.array([0, 1, 1])}]
        GeneticCnnModel.cross_validate_population(
            x, y, genomes, **self._cfg(warm_start=True))
        assert len(cnn_mod._WARM_BANK) == 2 and overlays[-1][2:] == (0, True)
        banked = list(cnn_mod._WARM_BANK.values())  # the warm run deposits anew
        # Promotion: same genomes at a longer schedule.  Every fold of the
        # warm run starts from the lower rung's trained params, bit for bit,
        # and those are not a fresh init (a masked-out node's leaves never
        # got a gradient, hence `any`).  Two accuracies quantised to 1/32
        # can tie, so the fitness is not what tells warm from cold.
        GeneticCnnModel.cross_validate_population(
            x, y, genomes, **self._cfg(warm_start=True, epochs=(2,)))
        fresh, started, warmed, untouched = overlays[-1]
        assert warmed == 2 and len(started) == 2 and not untouched
        for fresh_p, started_p in zip(fresh, started):
            for slot, bank in enumerate(banked):
                leaves = list(zip(*(jax.tree.leaves(t) for t in (fresh_p, started_p, bank))))
                assert all(np.array_equal(s[slot], b) for _, s, b in leaves)
                assert any(not np.array_equal(f[slot], b) for f, _, b in leaves)
        # With the bank empty the identical call starts cold.
        cnn_mod._WARM_BANK.clear()
        GeneticCnnModel.cross_validate_population(
            x, y, genomes, **self._cfg(warm_start=True, epochs=(2,)))
        assert overlays[-1][2:] == (0, True)

    def test_overlay_skips_shape_mismatch(self):
        from gentun_tpu.models import cnn as cnn_mod
        from gentun_tpu.models.cnn import GeneticCnnModel

        cnn_mod._WARM_BANK.clear()
        x, y = self._data()
        genomes = [{"S_1": np.array([1, 0, 1])}]
        GeneticCnnModel.cross_validate_population(
            x, y, genomes, **self._cfg(warm_start=True))
        assert len(cnn_mod._WARM_BANK) == 1
        # Same genome under a WIDER config: every banked leaf mismatches,
        # the evaluation must still succeed from fresh inits.
        accs = GeneticCnnModel.cross_validate_population(
            x, y, genomes,
            **self._cfg(warm_start=True, kernels_per_layer=(8,), dense_units=16))
        assert accs.shape == (1,)

    def test_warm_start_does_not_change_compiled_program_key(self):
        from gentun_tpu.models.cnn import _normalize_config, _static_key

        x, y = self._data()
        on = _normalize_config(x, y, self._cfg(warm_start=True))
        off = _normalize_config(x, y, self._cfg(warm_start=False))
        assert _static_key(on, 8, 16, 16, 8) == _static_key(off, 8, 16, 16, 8)

    def test_bank_lru_bound(self):
        from gentun_tpu.models import cnn as cnn_mod

        cnn_mod._WARM_BANK.clear()
        for i in range(cnn_mod._WARM_BANK_CAP + 10):
            cnn_mod._WARM_BANK.pop((i, i), None)
            cnn_mod._WARM_BANK[(i, i)] = {"w": np.zeros(1)}
            while len(cnn_mod._WARM_BANK) > cnn_mod._WARM_BANK_CAP:
                del cnn_mod._WARM_BANK[next(iter(cnn_mod._WARM_BANK))]
        assert len(cnn_mod._WARM_BANK) == cnn_mod._WARM_BANK_CAP
        assert (0, 0) not in cnn_mod._WARM_BANK
