"""Host-level mesh workers: derived capacity, mesh-aligned re-chunking.

One worker per host drives every local device through the ``(pop, data)``
mesh; its dispatch window is DERIVED from the mesh
(``parallel/mesh.host_worker_capacity``) and advertised to the broker in
the hello/advertise ``mesh`` field (DISTRIBUTED.md "Host-level mesh
workers").  These tests cover the derivation knob (``capacity="auto"``),
the dispatch plane's mesh-awareness (capacity-sized re-chunking must land
prefetched frames on mesh-pop-multiple boundaries — no recompiles, no
padding waste), and the broker-side bookkeeping the master's fill target
reads (``fleet_mesh_pop``).
"""

import threading
import time

import numpy as np
import pytest

from gentun_tpu import Individual, genetic_cnn_genome
from gentun_tpu.distributed import DistributedPopulation, GentunClient
from gentun_tpu.individuals import GeneticCnnIndividual
from gentun_tpu.parallel.mesh import host_worker_capacity
from gentun_tpu.telemetry import spans as spans_mod
from gentun_tpu.telemetry.registry import get_registry


class OneMax(Individual):
    def build_spec(self, **params):
        return genetic_cnn_genome(tuple(params.get("nodes", (4, 4))))

    def evaluate(self):
        return float(sum(sum(g) for g in self.genes.values()))


DATA = (np.zeros(1, np.float32), np.zeros(1, np.float32))


@pytest.fixture(autouse=True)
def _pristine_telemetry():
    spans_mod.disable()
    get_registry().reset()
    yield
    spans_mod.disable()
    get_registry().reset()


def _client(**kw):
    kw.setdefault("heartbeat_interval", 0.2)
    kw.setdefault("reconnect_delay", 0.05)
    return GentunClient(OneMax, *DATA, host="127.0.0.1", **kw)


def _wait(predicate, timeout=10.0, interval=0.01):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return True
        time.sleep(interval)
    return False


class TestDerivedCapacity:
    def test_auto_with_explicit_device_count(self):
        c = _client(capacity="auto", mesh_devices=8)
        assert c.capacity == 16
        assert c._mesh_shape == (8, 1)
        # derived window follows the derivation table exactly
        assert (c.capacity, *c._mesh_shape) == host_worker_capacity(8)

    def test_auto_probes_jax_for_jax_species(self):
        # conftest forces 8 virtual CPU devices; a jax species derives
        # from jax.device_count() without being told.
        c = GentunClient(GeneticCnnIndividual, *DATA, host="127.0.0.1",
                         capacity="auto")
        assert c.capacity == 16
        assert c._mesh_shape == (8, 1)

    def test_auto_requires_devices_for_non_jax_species(self):
        # OneMax never initialises jax: probing would advertise a mesh the
        # evaluator won't use — the caller must say what it meant.
        with pytest.raises(ValueError, match="mesh_devices"):
            _client(capacity="auto")

    def test_bad_capacity_string_is_loud(self):
        with pytest.raises(ValueError, match="auto"):
            _client(capacity="lots")

    def test_remesh_requires_auto_mode(self):
        c = _client(capacity=4)
        with pytest.raises(ValueError, match="auto"):
            c.remesh(n_devices=2)


class TestMeshAlignedChunking:
    """PR-4's capacity-sized re-chunking, made mesh-aware: every full
    prefetched frame must be a mesh-pop multiple so the evaluator never
    pads (``eval_pad_waste_total`` stays 0) and never meets a new
    compile shape mid-schedule."""

    def test_derived_capacity_chunks_are_pop_multiples(self):
        c = _client(capacity="auto", mesh_devices=8)  # capacity 16, pop 8
        jobs = [f"j{i}" for i in range(35)]
        chunks = c._chunk_jobs(jobs)
        assert [len(ch) for ch in chunks] == [16, 16, 3]
        assert [j for ch in chunks for j in ch] == jobs  # order preserved

    def test_misaligned_capacity_aligns_down(self):
        # An operator-typed capacity that isn't a pop multiple steps DOWN
        # to one (never exceeding the advertised window): 6 on a pop-4
        # mesh chunks by 4.
        c = _client(capacity=6)
        c._mesh_shape = (4, 1)
        assert [len(ch) for ch in c._chunk_jobs(list(range(10)))] == [4, 4, 2]

    def test_per_chip_worker_chunking_unchanged(self):
        # No mesh known (hand-set capacity): historical behavior, bit for
        # bit — chunks of exactly `capacity`.
        c = _client(capacity=3)
        assert [len(ch) for ch in c._chunk_jobs(list(range(8)))] == [3, 3, 2]

    def test_mixed_class_frame_never_mixes(self):
        """Big-genome regime: a frame mixing small and big jobs is
        partitioned by size class — small windows first, then each big
        job as a singleton (its program is 1-wide on a (1, n) mesh), so
        no chunk ever mixes mesh shapes and the shape flips at most once
        per frame."""
        from gentun_tpu.parallel.mesh import (
            SIZE_SMALL, cnn_genome_cost, job_size_class)

        c = _client(capacity="auto", mesh_devices=8)  # capacity 16, pop 8
        cost = cnn_genome_cost((3,), (8,), (8, 8, 1), 32, 4, "float32")
        big_params = dict(
            nodes=(3,), kernels_per_layer=(8,), input_shape=(8, 8, 1),
            dense_units=32, n_classes=4, compute_dtype="float32",
            batch_size=32,
            device_budget=cost.param_bytes + cost.act_bytes_per_example * 8)
        jobs = [{"job_id": f"j{i}",
                 "additional_parameters": big_params if i % 5 == 0 else {}}
                for i in range(20)]  # 4 big interleaved among 16 small
        chunks = c._chunk_jobs(jobs)
        assert [len(ch) for ch in chunks] == [16, 1, 1, 1, 1]
        for ch in chunks:
            classes = {job_size_class(j["additional_parameters"], 8) for j in ch}
            assert len(classes) == 1  # never a mixed frame
        assert all(job_size_class(j["additional_parameters"], 8) != SIZE_SMALL
                   for ch in chunks[1:] for j in ch)
        # every job routed exactly once, order preserved within each class
        assert sorted(j["job_id"] for ch in chunks for j in ch) == \
            sorted(j["job_id"] for j in jobs)
        assert [j["job_id"] for j in chunks[0]] == \
            [f"j{i}" for i in range(20) if i % 5]

    def test_budget_free_jobs_keep_historical_chunking(self):
        """Feature off (no device_budget on any wire config): the
        partitioning is a no-op and chunking stays bit-for-bit the
        PR-10 mesh-aligned behavior."""
        c = _client(capacity="auto", mesh_devices=8)
        jobs = [{"job_id": f"j{i}", "additional_parameters": {}}
                for i in range(35)]
        assert [len(ch) for ch in c._chunk_jobs(jobs)] == [16, 16, 3]


class TestMeshOverride:
    """Satellite: the worker-level ``--mesh POPxDATA`` override — loud on
    anything malformed or non-factoring, re-validated whenever the device
    count changes (``remesh``), never riding the wire config."""

    @pytest.fixture(autouse=True)
    def _clear_override(self):
        from gentun_tpu.parallel.mesh import set_mesh_override
        yield
        set_mesh_override(None)

    def test_cli_rejects_malformed_mesh(self):
        from gentun_tpu.distributed.worker import main as worker_main

        for bad in ("8", "axb", "0x8", "2x2x2"):
            with pytest.raises(SystemExit, match="--mesh"):
                worker_main(["--mesh", bad])

    def test_override_shapes_capacity_and_advert(self):
        from gentun_tpu.parallel.mesh import get_mesh_override

        c = _client(capacity="auto", mesh_devices=8, mesh_override="4x2")
        assert c._mesh_shape == (4, 2)
        assert c.capacity == 8  # 2 slots x pop 4
        # installed process-wide so the evaluator's auto_mesh sees it
        assert get_mesh_override() == (4, 2)

    def test_non_factoring_override_is_loud(self):
        with pytest.raises(ValueError, match="factor"):
            _client(capacity="auto", mesh_devices=8, mesh_override="3x2")

    def test_remesh_revalidates_override(self):
        # (4, 2) factors 8 devices; after losing 2 devices it factors
        # nothing — the remesh must refuse rather than advertise a mesh
        # the evaluator cannot build.
        c = _client(capacity="auto", mesh_devices=8, mesh_override=(4, 2))
        with pytest.raises(ValueError, match="factor"):
            c.remesh(n_devices=6)
        # the pre-remesh advert state is untouched by the failed attempt
        assert c._mesh_shape == (4, 2)


class TestHostMeshEndToEnd:
    def test_host_worker_advertises_mesh_and_evaluates(self):
        pop = DistributedPopulation(OneMax, size=6, seed=3, port=0,
                                    maximize=True, job_timeout=30)
        stop = threading.Event()
        try:
            _, port = pop.broker_address
            client = _client(capacity="auto", mesh_devices=8, port=port,
                             worker_id="mesh-w0")
            t = threading.Thread(target=lambda: client.work(stop_event=stop),
                                 daemon=True)
            t.start()
            assert _wait(lambda: pop.fleet_capacity() == 16)
            # the broker learned the mesh shape from the hello frame ...
            assert pop.broker.fleet_mesh_pop() == 8
            w = next(iter(pop.broker._workers.values()))
            assert w.mesh == {"pop": 8, "data": 1, "devices": 8}
            # ... and both ops planes expose it
            st = pop.broker._ops_status()
            assert st["mesh_pop_multiple"] == 8
            assert st["workers"][0]["mesh"]["pop"] == 8
            cst = client._ops_status()
            assert cst["mesh"] == {"pop": 8, "data": 1, "devices": 8,
                                   "derived_capacity": True}
            # master's speculative fill target rounds to the fleet's mesh
            assert pop._fill_target(9) % 8 == 0
            pop.evaluate()
            assert all(i.fitness_evaluated for i in pop)
            for ind in pop:
                assert ind.get_fitness() == float(
                    sum(sum(g) for g in ind.get_genes().values()))
        finally:
            stop.set()
            pop.close()


class JaxOneMax(OneMax):
    uses_jax = True  # the device advert is for species whose fitness runs on jax


class TestDeviceAdvert:
    """The OPTIONAL ``device`` hello field (protocol.py "Device field")."""

    def _join(self, species):
        pop = DistributedPopulation(species, size=2, seed=0, port=0, job_timeout=30)
        stop = threading.Event()
        client = GentunClient(species, *DATA, host="127.0.0.1",
                              port=pop.broker_address[1], heartbeat_interval=0.2,
                              reconnect_delay=0.05)
        threading.Thread(target=lambda: client.work(stop_event=stop), daemon=True).start()
        assert _wait(lambda: pop.broker.fleet_members() == 1)
        return pop, stop

    def test_jax_species_tells_the_master_what_it_runs_on(self):
        pop, stop = self._join(JaxOneMax)
        try:
            # conftest: 8 virtual CPU devices
            want = {"platform": "cpu", "kind": "cpu", "count": 8}
            assert pop.broker.fleet_devices() == [want]
            assert pop.broker._ops_status()["workers"][0]["device"] == want
            assert pop.broker.fleet_chips() == 8
        finally:
            stop.set()
            pop.close()

    def test_other_species_send_no_device_field(self):
        pop, stop = self._join(OneMax)
        try:
            assert pop.broker.fleet_devices() == []
            assert pop.broker._ops_status()["workers"][0]["device"] is None
        finally:
            stop.set()
            pop.close()

    def test_malformed_adverts_degrade_to_none(self):
        from gentun_tpu.distributed.broker import JobBroker

        parse = JobBroker._parse_device
        good = {"platform": "tpu", "kind": "TPU v5 lite", "count": 4}
        assert parse({"device": good}) == good
        for bad in (None, "tpu", {"platform": "tpu"}, dict(good, count=0),
                    dict(good, count="many"), dict(good, kind=5)):
            assert parse({"device": bad}) is None
