"""Fleet-wide compile cache service (distributed/compile_service.py).

``utils/xla_cache.py`` already persists compiled executables on disk; the
service promotes that directory to a network cache shared by an elastic
fleet.  These tests cover the wire contract (platform-fingerprint
namespacing, version skew → 409, fingerprint mismatch → 409, byte-budget
LRU, idempotent concurrent publish), the client's read-through prefetch /
write-behind publish scans, the degradation boundary (a dead service must
cost recompiles, never exceptions, with exactly ONE degraded event), the
worker/CLI guards, and the end-to-end invariant: a search with the
service killed mid-run is bit-identical to a service-free run.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from gentun_tpu import GeneticAlgorithm, Individual, Population, genetic_cnn_genome
from gentun_tpu.distributed import DistributedPopulation, GentunClient
from gentun_tpu.distributed.compile_service import (
    COMPILE_PROTOCOL,
    CompileService,
    CompileServiceClient,
    _safe_name,
    platform_components,
    platform_fingerprint,
)
from gentun_tpu.telemetry import spans as spans_mod
from gentun_tpu.telemetry.registry import get_registry
from gentun_tpu.utils import xla_cache


class _ListSink:
    def __init__(self):
        self.records = []

    def record(self, rec):
        self.records.append(rec)


@pytest.fixture(autouse=True)
def _pristine_telemetry():
    spans_mod.disable()
    spans_mod.set_run_sink(None)
    get_registry().reset()
    yield
    spans_mod.disable()
    spans_mod.set_run_sink(None)
    get_registry().reset()


@pytest.fixture
def service():
    svc = CompileService(port=0, max_bytes=1024 * 1024)
    svc.start()
    yield svc
    svc.stop()


FP = "aa" * 8  # a fixed platform fingerprint for wire tests


def _client(service, tmp_path, name="c", fp=FP, **kw):
    d = tmp_path / name
    d.mkdir(exist_ok=True)
    return CompileServiceClient(service.url, cache_dir=str(d),
                                fingerprint=fp, **kw)


def _write_entry(client, name, data=b"x" * 64):
    with open(os.path.join(client.cache_dir, name), "wb") as fh:
        fh.write(data)


def _post_raw(url, endpoint, body):
    req = urllib.request.Request(
        url + endpoint, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=5) as resp:
        return json.loads(resp.read().decode())


class TestPlatformFingerprint:
    def test_components_name_the_compat_facts(self):
        comps = platform_components(probe_devices=False)
        for field in ("jax", "jaxlib", "platform", "device_kind", "xla_flags"):
            assert field in comps

    def test_fingerprint_is_64_bit_hex_and_stable(self):
        fp = platform_fingerprint(probe_devices=False)
        assert len(fp) == 16
        int(fp, 16)
        assert fp == platform_fingerprint(probe_devices=False)

    def test_xla_flags_change_the_fingerprint(self, monkeypatch):
        # An env knob that changes codegen must change the namespace: a
        # binary built under different XLA flags is a different binary.
        base = platform_fingerprint(probe_devices=False)
        monkeypatch.setenv("XLA_FLAGS", "--xla_something_else=1")
        assert platform_fingerprint(probe_devices=False) != base

    def test_safe_name_charset_is_the_path_guard(self):
        assert _safe_name("a1b2_c3.d-e")
        assert not _safe_name("../etc/passwd")
        assert not _safe_name("a/b")
        assert not _safe_name(".hidden")
        assert not _safe_name("")
        assert not _safe_name(42)


class TestServiceWire:
    def test_publish_prefetch_roundtrip(self, service, tmp_path):
        a = _client(service, tmp_path, "a")
        b = _client(service, tmp_path, "b")
        _write_entry(a, "entry_one", b"artifact-bytes")
        assert a.scan_publish() == 1
        assert a.flush(5.0)
        assert b.prefetch() == 1
        with open(os.path.join(b.cache_dir, "entry_one"), "rb") as fh:
            assert fh.read() == b"artifact-bytes"
        a.close(), b.close()

    def test_scan_is_noop_when_dir_unchanged(self, service, tmp_path):
        c = _client(service, tmp_path)
        _write_entry(c, "entry_one")
        assert c.scan_publish() == 1
        # Steady state: one os.stat, nothing queued, no HTTP.
        assert c.scan_publish() == 0
        assert c.scan_publish() == 0
        c.close()

    def test_prefetch_skips_entries_already_local(self, service, tmp_path):
        a = _client(service, tmp_path, "a")
        _write_entry(a, "entry_one")
        a.scan_publish()
        assert a.flush(5.0)
        # A's own entry is local already — nothing to fetch.
        assert a.prefetch() == 0
        a.close()

    def test_idempotent_republish_keeps_byte_accounting(self, service, tmp_path):
        a = _client(service, tmp_path, "a")
        b = _client(service, tmp_path, "b")
        data = b"z" * 100
        _write_entry(a, "entry_one", data)
        _write_entry(b, "entry_one", data)  # both workers compiled the shape
        a.scan_publish(), b.scan_publish()
        assert a.flush(5.0) and b.flush(5.0)
        st = service.stats()
        assert st["entries"] == 1  # content-addressed: one blob, not two
        assert st["bytes"] == len(data)
        a.close(), b.close()

    def test_concurrent_publish_of_same_blob_is_idempotent(self, service, tmp_path):
        # N threads racing the same artifact through the threading server:
        # the store must end with exactly one entry and exact byte totals.
        data = b"q" * 256
        clients = [_client(service, tmp_path, f"w{i}") for i in range(6)]
        for c in clients:
            _write_entry(c, "entry_shared", data)
        threads = [threading.Thread(target=c.scan_publish) for c in clients]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for c in clients:
            assert c.flush(5.0)
        st = service.stats()
        assert st["entries"] == 1
        assert st["bytes"] == len(data)
        assert st["puts"] == 6  # all six re-publishes accepted, no error
        for c in clients:
            c.close()

    def test_byte_budget_lru_eviction(self, tmp_path):
        svc = CompileService(port=0, max_bytes=250).start()
        try:
            c = _client(svc, tmp_path)
            for i, name in enumerate(["entry_a", "entry_b", "entry_c"]):
                _write_entry(c, name, bytes([65 + i]) * 100)
                c.scan_publish()
                assert c.flush(5.0)
            st = svc.stats()
            assert st["entries"] == 2  # 300 bytes > 250: coldest evicted
            assert st["evictions"] == 1
            assert "entry_a" not in svc.list_names(FP)
            assert "entry_c" in svc.list_names(FP)
            c.close()
        finally:
            svc.stop()

    def test_fetch_refreshes_lru_position(self, tmp_path):
        svc = CompileService(port=0, max_bytes=250).start()
        try:
            a = _client(svc, tmp_path, "a")
            for name in ("entry_a", "entry_b"):
                _write_entry(a, name, b"x" * 100)
            a.scan_publish()
            assert a.flush(5.0)
            # Touch entry_a via a fetch, then push a third blob: entry_b
            # (now coldest) evicts, not entry_a.
            assert svc.fetch(FP, ["entry_a"])
            b = _client(svc, tmp_path, "b")
            _write_entry(b, "entry_c", b"x" * 100)
            b.scan_publish()
            assert b.flush(5.0)
            names = svc.list_names(FP)
            assert "entry_a" in names and "entry_b" not in names
            a.close(), b.close()
        finally:
            svc.stop()

    def test_statusz_serves_cache_block(self, service, tmp_path):
        c = _client(service, tmp_path)
        _write_entry(c, "entry_one")
        c.scan_publish()
        assert c.flush(5.0)
        with urllib.request.urlopen(service.url + "/statusz", timeout=5) as r:
            st = json.loads(r.read().decode())
        assert st["entries"] == 1 and st["puts"] == 1
        assert st["protocol"] == COMPILE_PROTOCOL
        assert st["fingerprints"] == 1
        c.close()

    def test_unsafe_names_never_stored(self, service):
        out = _post_raw(service.url, "/v1/publish", {
            "v": 1, "protocol": COMPILE_PROTOCOL, "fingerprint": FP,
            "entries": [["../escape", "eHg="], ["ok_name", "not base64!!"]]})
        assert out["stored"] == 0
        assert service.stats()["entries"] == 0


class TestConflicts:
    def test_protocol_skew_is_409(self, service):
        body = {"v": 1, "protocol": COMPILE_PROTOCOL + 1, "fingerprint": FP,
                "names": ["entry_one"]}
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_raw(service.url, "/v1/fetch", body)
        assert ei.value.code == 409
        refusal = json.loads(ei.value.read().decode())
        assert refusal["protocol"] == COMPILE_PROTOCOL
        assert refusal["client_protocol"] == COMPILE_PROTOCOL + 1

    def test_fingerprint_mismatch_fetch_is_409(self, service, tmp_path):
        a = _client(service, tmp_path, "a")
        _write_entry(a, "entry_one")
        a.scan_publish()
        assert a.flush(5.0)
        # A different platform asking for the same name: refused with both
        # sides' fingerprints, never served an incompatible binary.
        body = {"v": 1, "protocol": COMPILE_PROTOCOL, "fingerprint": "bb" * 8,
                "names": ["entry_one"]}
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_raw(service.url, "/v1/fetch", body)
        assert ei.value.code == 409
        refusal = json.loads(ei.value.read().decode())
        assert refusal["error"] == "platform fingerprint mismatch"
        assert refusal["stored_fingerprint"] == FP
        assert refusal["client_fingerprint"] == "bb" * 8
        assert service.stats()["conflicts"] == 1
        a.close()

    def test_fingerprint_mismatch_publish_is_409(self, service, tmp_path):
        a = _client(service, tmp_path, "a")
        _write_entry(a, "entry_one")
        a.scan_publish()
        assert a.flush(5.0)
        body = {"v": 1, "protocol": COMPILE_PROTOCOL, "fingerprint": "bb" * 8,
                "entries": [["entry_one", "eHg="]]}
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post_raw(service.url, "/v1/publish", body)
        assert ei.value.code == 409
        a.close()

    def test_mismatched_client_degrades_not_raises(self, service, tmp_path):
        a = _client(service, tmp_path, "a")
        _write_entry(a, "entry_one")
        a.scan_publish()
        assert a.flush(5.0)
        skewed = _client(service, tmp_path, "skewed", fp="bb" * 8,
                         timeout=2.0, cooldown=30.0)
        _write_entry(skewed, "entry_one")
        skewed.scan_publish()  # must not raise
        assert not skewed.flush(2.0)  # 409 → degraded, entries stay local
        assert skewed.degraded
        a.close(), skewed.close(flush_timeout=0.1)

    def test_disjoint_fingerprints_coexist(self, service, tmp_path):
        a = _client(service, tmp_path, "a", fp="aa" * 8)
        b = _client(service, tmp_path, "b", fp="bb" * 8)
        _write_entry(a, "entry_a")
        _write_entry(b, "entry_b")
        a.scan_publish(), b.scan_publish()
        assert a.flush(5.0) and b.flush(5.0)
        assert service.list_names("aa" * 8) == ["entry_a"]
        assert service.list_names("bb" * 8) == ["entry_b"]
        assert service.stats()["fingerprints"] == 2
        a.close(), b.close()


class TestDegradation:
    def test_dead_service_costs_recompiles_never_exceptions(self, tmp_path):
        sink = _ListSink()
        spans_mod.enable()
        spans_mod.set_run_sink(sink)
        d = tmp_path / "cache"
        d.mkdir()
        c = CompileServiceClient("http://127.0.0.1:1", cache_dir=str(d),
                                 fingerprint=FP, timeout=0.2, cooldown=30.0)
        assert c.prefetch() == 0  # miss, not exception
        _write_entry(c, "entry_one")
        assert c.scan_publish() == 1  # queues locally
        assert not c.flush(1.0)  # can't drain to a dead service
        assert c.degraded
        evs = [r for r in sink.records
               if r.get("type") == "event"
               and r["name"] == "compile_service_degraded"]
        assert len(evs) == 1  # ONE event per transition
        assert evs[0]["data"]["url"] == "http://127.0.0.1:1"
        assert get_registry().counter("compile_service_degraded_total").value == 1
        c.close(flush_timeout=0.1)

    def test_cooldown_prevents_per_batch_timeouts(self, tmp_path):
        d = tmp_path / "cache"
        d.mkdir()
        c = CompileServiceClient("http://127.0.0.1:1", cache_dir=str(d),
                                 fingerprint=FP, timeout=0.2, cooldown=60.0)
        c.prefetch()  # pays the one connect failure
        t0 = time.monotonic()
        for _ in range(50):
            c.prefetch()  # inside the cooldown: no socket touch
        assert time.monotonic() - t0 < 0.5
        c.close(flush_timeout=0.1)

    def test_recovery_after_cooldown(self, tmp_path):
        svc = CompileService(port=0).start()
        host, port = svc.address
        a = _client(svc, tmp_path, "a")
        _write_entry(a, "entry_one")
        svc.stop()
        a.cooldown = 0.1
        a.scan_publish()
        assert not a.flush(0.5)
        assert a.degraded
        svc2 = CompileService(host=host, port=port).start()
        try:
            time.sleep(0.15)  # cooldown expires; flusher retries and heals
            assert a.flush(5.0)
            assert not a.degraded
            assert svc2.stats()["entries"] == 1
        finally:
            svc2.stop()
        a.close(flush_timeout=0.1)


class TestPublishHooks:
    def test_hook_registry_drives_publish(self, service, tmp_path):
        c = _client(service, tmp_path)
        xla_cache.register_publish_hook(c.publish_hook)
        try:
            _write_entry(c, "entry_one")
            xla_cache.run_publish_hooks()  # what _prepare_population_setup calls
            assert c.flush(5.0)
            assert service.stats()["entries"] == 1
        finally:
            c.close()  # close() unregisters
        assert c.publish_hook not in xla_cache._publish_hooks

    def test_failing_hook_never_raises(self):
        def _boom():
            raise RuntimeError("hook boom")

        xla_cache.register_publish_hook(_boom)
        try:
            xla_cache.run_publish_hooks()  # must not raise
        finally:
            xla_cache.unregister_publish_hook(_boom)


class OneMax(Individual):
    """Cheap deterministic fitness (count of set bits): distributed and
    local runs are comparable bit-for-bit, and no jax backend is touched."""

    def build_spec(self, **params):
        return genetic_cnn_genome(tuple(params.get("nodes", (4, 4))))

    def evaluate(self):
        return float(sum(sum(g) for g in self.genes.values()))


DATA = (np.zeros(1, np.float32), np.zeros(1, np.float32))


class TestClientGuards:
    def test_gentun_client_rejects_malformed_url(self):
        with pytest.raises(ValueError, match="scheme"):
            GentunClient(OneMax, *DATA, compile_cache_url="not-a-url")

    def test_gentun_client_refuses_multihost(self):
        with pytest.raises(ValueError, match="multihost"):
            GentunClient(OneMax, *DATA, multihost=True,
                         compile_cache_url="http://127.0.0.1:9737")

    def test_worker_cli_malformed_url_is_systemexit(self):
        from gentun_tpu.distributed.worker import main as worker_main

        with pytest.raises(SystemExit, match="--compile-cache-url"):
            worker_main(["--dataset", "uci-wine",
                         "--compile-cache-url", "definitely-not-a-url"])

    def test_worker_cli_refuses_multihost(self):
        from gentun_tpu.distributed.worker import main as worker_main

        with pytest.raises(SystemExit, match="--compile-cache-url"):
            worker_main(["--dataset", "uci-wine",
                         "--compile-cache-url", "http://127.0.0.1:9737",
                         "--coordinator", "127.0.0.1:8476"])


class TestEndToEnd:
    def test_service_killed_mid_search_is_bit_identical(self, tmp_path, monkeypatch):
        """The acceptance invariant: kill the compile service mid-search →
        the search completes bit-identical to a service-free run, with
        exactly ONE ``compile_service_degraded`` event."""
        generations, pop_size, pop_seed, ga_seed = 4, 8, 42, 7

        def _snapshot(ga):
            return {
                "history": [r["best_fitness"] for r in ga.history],
                "final": [
                    {"genes": {k: list(v) for k, v in ind.get_genes().items()},
                     "fitness": ind.get_fitness()}
                    for ind in ga.population
                ],
            }

        # Service-free reference (single-process, telemetry-free).
        ref = GeneticAlgorithm(
            Population(OneMax, *DATA, size=pop_size, seed=pop_seed),
            seed=ga_seed)
        ref.run(generations)

        # The worker's compile client resolves its cache dir from the env
        # (conftest's kill switch would leave it with none).
        cache_dir = tmp_path / "xla"
        monkeypatch.delenv("GENTUN_TPU_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(cache_dir))
        sink = _ListSink()
        spans_mod.enable()
        spans_mod.set_run_sink(sink)

        svc = CompileService(port=0).start()
        # Pre-seed one artifact under the worker's fingerprint (OneMax
        # never probes devices) so the join-time prefetch has work to do.
        wfp = platform_fingerprint(probe_devices=False)
        svc.publish(wfp, [("entry_warm", b"warm-artifact")])

        stop = threading.Event()
        try:
            with DistributedPopulation(
                    OneMax, size=pop_size, seed=pop_seed, port=0,
                    job_timeout=60.0) as pop:
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
                    # Pull the plug mid-search, then write a fresh "compile
                    # artifact" so the next batch's publish scan has to talk
                    # to the dead service → the degraded path fires.
                    while not ga.history:
                        time.sleep(0.005)
                    svc.stop()
                    with open(cache_dir / "entry_fresh", "wb") as fh:
                        fh.write(b"freshly-compiled")

                killer = threading.Thread(target=_kill_then_dirty, daemon=True)
                killer.start()
                ga.run(generations)
                killer.join(timeout=10)
                stats = worker._compile_client.stats()
        finally:
            stop.set()
            try:
                svc.stop()
            except Exception:
                pass

        assert _snapshot(ga) == _snapshot(ref), (
            "compile-service kill perturbed the search")
        assert len(ga.history) == generations
        # The join-time prefetch pulled the pre-seeded artifact down.
        assert (cache_dir / "entry_warm").read_bytes() == b"warm-artifact"
        assert stats["fetched"] == 1
        # ONE degraded event for the kill.
        t0 = time.monotonic()
        while time.monotonic() - t0 < 5.0:
            evs = [r for r in sink.records
                   if r.get("type") == "event"
                   and r["name"] == "compile_service_degraded"]
            if evs:
                break
            time.sleep(0.02)  # flusher may still be timing out on the POST
        assert len(evs) == 1, f"expected ONE degraded event, got {len(evs)}"
