"""Black-box canary plane (telemetry/canary.py + the fitness_corrupt
fault + broker session tagging / TTFD plumbing).

The canary is the fleet's synthetic monitor: golden-genome probe
sessions through the REAL serving path, decomposed into golden-signal
SLIs, with a zero-tolerance bit-equality check on every returned
fitness.  These tests pin the pieces separately — golden sealing, the
fault kind, the no_memo dedup bypass, tenant invisibility of tagged
sessions, the TTFD stamps — and then the whole loop end to end against
a live broker + worker, including drift detection and the error SLIs.
"""

import contextlib
import json
import struct
import threading
import time
import urllib.request

import numpy as np
import pytest

from gentun_tpu import Individual, Population, genetic_cnn_genome
from gentun_tpu.distributed import GentunClient, JobBroker, SessionClient
from gentun_tpu.distributed.faults import FaultInjector, FaultPlan, FaultSpec
from gentun_tpu.distributed.sessions import SessionRegistry
from gentun_tpu.telemetry import spans as spans_mod
from gentun_tpu.telemetry.canary import CANARY_TAG, CanaryDaemon, GoldenSet
from gentun_tpu.telemetry.registry import get_registry
from gentun_tpu.telemetry.slo import default_rules


class OneMax(Individual):
    evaluations = 0  # class-level: counts REAL evaluations across jobs

    def build_spec(self, **params):
        return genetic_cnn_genome(tuple(params.get("nodes", (4, 4))))

    def evaluate(self):
        type(self).evaluations += 1
        return float(sum(sum(g) for g in self.genes.values()))


DATA = (np.zeros(1, np.float32), np.zeros(1, np.float32))


@pytest.fixture(autouse=True)
def _pristine_telemetry():
    spans_mod.disable()
    spans_mod.set_run_sink(None)
    get_registry().reset()
    yield
    spans_mod.disable()
    spans_mod.set_run_sink(None)
    get_registry().reset()


def _spawn_worker(species, port, worker_id, fault_injector=None, **kw):
    stop = threading.Event()
    client = GentunClient(
        species, *DATA, host="127.0.0.1", port=port, capacity=1,
        worker_id=worker_id, heartbeat_interval=0.2, reconnect_delay=0.05,
        fault_injector=fault_injector, **kw)
    t = threading.Thread(target=lambda: client.work(stop_event=stop),
                         daemon=True)
    t.start()
    return client, stop, t


def _probes(n=2, seed=0):
    pop = Population(OneMax, DATA, size=n, seed=seed, maximize=True)
    return [{"genes": ind.get_genes()} for ind in pop]


@contextlib.contextmanager
def _broker(**kw):
    b = JobBroker(port=0, **kw).start()
    try:
        yield b
    finally:
        b.stop()


def _counter_total(name, **labels):
    snap = get_registry().snapshot()
    total = 0.0
    for c in snap["counters"]:
        if c["name"] != name:
            continue
        if labels and any((c.get("labels") or {}).get(k) != v
                          for k, v in labels.items()):
            continue
        total += c["value"]
    return total


# ---------------------------------------------------------------------------
# GoldenSet: content-addressed, sealed at first evaluation
# ---------------------------------------------------------------------------


class TestGoldenSet:
    def test_first_seal_wins(self):
        g = GoldenSet()
        key = GoldenSet.key("space", "fp", "gk")
        sealed, newly = g.seal(key, 3.5)
        assert (sealed, newly) == (3.5, True)
        # A later (possibly corrupt) value never overwrites the truth.
        sealed, newly = g.seal(key, 99.0)
        assert (sealed, newly) == (3.5, False)
        assert g.get(key) == 3.5 and len(g) == 1

    def test_key_is_the_identity_triple(self):
        assert GoldenSet.key("s", "f", "g") == "s:f:g"
        assert GoldenSet.key("s2", "f", "g") != GoldenSet.key("s", "f", "g")

    def test_persistence_roundtrip(self, tmp_path):
        path = str(tmp_path / "golden.json")
        g = GoldenSet(path)
        g.seal("a:b:c", 1.25)
        g.seal("a:b:d", -0.0)
        g2 = GoldenSet(path)
        assert g2.get("a:b:c") == 1.25
        # Bit-level survival: -0.0 must come back as -0.0, not 0.0.
        assert struct.pack("<d", g2.get("a:b:d")) == struct.pack("<d", -0.0)

    def test_unreadable_file_starts_empty(self, tmp_path):
        path = tmp_path / "golden.json"
        path.write_text("{not json")
        g = GoldenSet(str(path))
        assert len(g) == 0


# ---------------------------------------------------------------------------
# fitness_corrupt fault kind (faults.py)
# ---------------------------------------------------------------------------


class TestFitnessCorruptFault:
    def test_spec_valid_only_at_worker_pre_eval(self):
        FaultSpec(hook="worker_pre_eval", kind="fitness_corrupt")  # ok
        with pytest.raises(ValueError):
            FaultSpec(hook="broker_send", kind="fitness_corrupt")

    def test_mark_is_consumed_once(self):
        inj = FaultInjector(FaultPlan([FaultSpec(
            hook="worker_pre_eval", kind="fitness_corrupt", at=0)]))
        inj.worker_pre_eval(None, {"job_id": "j1"})
        assert inj.take_fitness_corrupt("j1") is True
        assert inj.take_fitness_corrupt("j1") is False  # consumed
        assert inj.take_fitness_corrupt("j2") is False  # never marked
        assert inj.fired and inj.fired[0]["kind"] == "fitness_corrupt"

    def test_corrupt_fitness_is_deterministic_and_finite(self):
        assert FaultInjector.corrupt_fitness(6.0) == 7.0
        assert FaultInjector.corrupt_fitness(6.0) == 7.0  # same in, same out
        assert FaultInjector.corrupt_fitness(float("nan")) == 1.0
        assert FaultInjector.corrupt_fitness(float("inf")) == 1.0
        assert FaultInjector.corrupt_fitness("junk") == 1.0
        # Never bit-equal to the input.
        for v in (0.0, -1.5, 1e300):
            assert struct.pack("<d", FaultInjector.corrupt_fitness(v)) != \
                struct.pack("<d", v)


# ---------------------------------------------------------------------------
# Session tag + TTFD plumbing (sessions.py / broker.py)
# ---------------------------------------------------------------------------


class TestSessionTag:
    def test_registry_tag_roundtrip_and_snapshot(self):
        reg = SessionRegistry()
        sess = reg.open("probe", tag=CANARY_TAG)
        assert sess.tag == CANARY_TAG
        assert reg.open("tenant").tag is None
        snap = sess.snapshot()
        assert snap["tag"] == CANARY_TAG
        # Untagged snapshots keep the pre-tag schema (no new key).
        assert "tag" not in reg.open("tenant").snapshot()

    def test_reopen_updates_tag(self):
        reg = SessionRegistry()
        reg.open("s1")
        assert reg.open("s1", tag=CANARY_TAG).tag == CANARY_TAG

    def test_canary_sessions_excluded_from_flow_gauges(self):
        spans_mod.enable()
        with _broker() as broker:
            port = broker.address[1]
            broker.open_session("tenant-a")
            broker.open_session("probe-1", weight=1e-6, max_in_flight=1,
                                tag=CANARY_TAG)
            _, stop, _ = _spawn_worker(OneMax, port, "tg-w0")
            try:
                genes = _probes(1)[0]["genes"]
                broker.submit({"t-j0": {"genes": genes}}, session="tenant-a")
                broker.submit({"p-j0": {"genes": genes}}, session="probe-1")
                broker.gather(["t-j0", "p-j0"], timeout=30)
                snap = get_registry().snapshot()
                tagged = {(g["name"], (g.get("labels") or {}).get("session"))
                          for g in snap["gauges"]
                          if "session" in (g.get("labels") or {})}
                assert ("session_in_flight", "tenant-a") in tagged
                assert not any(s == "probe-1" for _, s in tagged), tagged
                # Nor any canary-labeled queue_wait_s series.
                qw = [(h.get("labels") or {}).get("session")
                      for h in snap["histograms"]
                      if h["name"] == "queue_wait_s"]
                assert "probe-1" not in qw
            finally:
                stop.set()

    def test_ttfd_stamped_and_cleared_on_close(self):
        with _broker() as broker:
            port = broker.address[1]
            broker.open_session("s-ttfd")
            assert broker.session_ttfd("s-ttfd") is None  # nothing submitted
            _, stop, _ = _spawn_worker(OneMax, port, "tt-w0")
            try:
                genes = _probes(1)[0]["genes"]
                broker.submit({"j0": {"genes": genes}}, session="s-ttfd")
                broker.gather(["j0"], timeout=30)
                ttfd = broker.session_ttfd("s-ttfd")
                assert ttfd is not None and ttfd >= 0.0
                broker.close_session("s-ttfd")
                deadline = time.monotonic() + 5
                while (broker.session_ttfd("s-ttfd") is not None
                       and time.monotonic() < deadline):
                    time.sleep(0.01)
                assert broker.session_ttfd("s-ttfd") is None
            finally:
                stop.set()

    def test_wire_session_stats_carries_ttfd(self):
        with _broker() as broker:
            port = broker.address[1]
            _, stop, _ = _spawn_worker(OneMax, port, "ws-w0")
            client = SessionClient("127.0.0.1", port)
            try:
                sid = client.open_session("s-wire", tag=CANARY_TAG)
                stats = client.session_stats(sid)
                assert "ttfd_s" not in stats  # pre-dispatch: old byte layout
                genes = _probes(1)[0]["genes"]
                [jid] = client.submit(sid, {"wj0": {"genes": genes}})
                r, f = client.wait_any([jid], timeout=30)
                assert r and not f
                stats = client.session_stats(sid)
                assert stats["ttfd_s"] >= 0.0
            finally:
                client.close()
                stop.set()


# ---------------------------------------------------------------------------
# no_memo: the canary's fitness-cache dedup bypass (client.py)
# ---------------------------------------------------------------------------


class TestNoMemo:
    def test_no_memo_jobs_always_really_evaluate(self):
        with _broker() as broker:
            port = broker.address[1]
            _, stop, _ = _spawn_worker(OneMax, port, "nm-w0")
            try:
                genes = _probes(1)[0]["genes"]
                OneMax.evaluations = 0
                # Two no_memo submits of the SAME genome: the worker's
                # per-group cache must not dedup the second into a hit.
                broker.submit({"n-j0": {"genes": genes, "no_memo": True}})
                broker.gather(["n-j0"], timeout=30)
                broker.submit({"n-j1": {"genes": genes, "no_memo": True}})
                broker.gather(["n-j1"], timeout=30)
                assert OneMax.evaluations == 2
            finally:
                stop.set()

    def test_memoizing_jobs_unaffected(self):
        with _broker() as broker:
            port = broker.address[1]
            _, stop, _ = _spawn_worker(OneMax, port, "nm-w1")
            try:
                genes = _probes(1, seed=3)[0]["genes"]
                res = broker.evaluate({"m-j0": {"genes": genes}}, timeout=30)
                assert res["m-j0"] == float(
                    sum(sum(g) for g in genes.values()))
            finally:
                stop.set()


# ---------------------------------------------------------------------------
# Stock canary SLO rules (telemetry/slo.py)
# ---------------------------------------------------------------------------


class TestCanaryRules:
    def test_default_rules_include_the_canary_triple(self):
        rules = {r.name: r for r in default_rules()}
        assert rules["canary_error_burn"].series == "canary_errors_total"
        assert rules["canary_error_burn"].severity == "warn"
        latency = rules["canary_latency"]
        assert latency.kind == "ratio"
        assert latency.series == "canary_e2e_seconds_sum"
        assert latency.denom == "canary_e2e_seconds_count"
        correctness = rules["canary_correctness"]
        assert correctness.series == "canary_fitness_drift_total"
        assert correctness.severity == "page"
        assert correctness.threshold == 0.0 and correctness.op == ">"
        # Zero tolerance: no for_s hold — the first drift pages.
        assert correctness.for_s == 0.0

    def test_scale_shrinks_windows_not_thresholds(self):
        full = {r.name: r for r in default_rules()}
        drill = {r.name: r for r in default_rules(0.1)}
        for name in ("canary_error_burn", "canary_latency",
                     "canary_correctness"):
            assert drill[name].window_s == pytest.approx(
                full[name].window_s * 0.1)
            assert drill[name].threshold == full[name].threshold


# ---------------------------------------------------------------------------
# CanaryDaemon end to end
# ---------------------------------------------------------------------------


class TestCanaryDaemon:
    def test_probe_cycle_seals_then_verifies(self):
        with _broker() as broker:
            port = broker.address[1]
            _, stop, _ = _spawn_worker(OneMax, port, "cd-w0")
            cn = CanaryDaemon(f"127.0.0.1:{port}", _probes(1),
                              space_key="onemax", probe_interval=999,
                              probe_timeout=15, serve_http=False)
            try:
                r1 = cn.probe_once()
                assert r1["result"] == "ok" and r1["newly_sealed"]
                assert r1["open_s"] >= 0 and r1["e2e_s"] >= r1["open_s"]
                assert r1["ttfd_s"] >= 0.0
                r2 = cn.probe_once()
                assert r2["result"] == "ok" and not r2["newly_sealed"]
                assert r2["sealed"] == r1["fitness"]
                assert _counter_total("canary_probes_total", result="ok") == 2
                assert _counter_total("canary_fitness_drift_total") == 0
            finally:
                cn.stop()
                stop.set()

    def test_drift_detected_within_one_cycle(self):
        inj = FaultInjector(FaultPlan([FaultSpec(
            hook="worker_pre_eval", kind="fitness_corrupt", at=1)]))
        with _broker() as broker:
            port = broker.address[1]
            _, stop, _ = _spawn_worker(OneMax, port, "cd-w1",
                                       fault_injector=inj)
            cn = CanaryDaemon(f"127.0.0.1:{port}", _probes(1),
                              space_key="onemax", probe_interval=999,
                              probe_timeout=15, serve_http=False)
            try:
                assert cn.probe_once()["result"] == "ok"  # seals the truth
                r = cn.probe_once()  # the corrupted cycle
                assert r["result"] == "drift"
                assert r["fitness"] != r["sealed"]
                assert _counter_total("canary_fitness_drift_total") == 1
            finally:
                cn.stop()
                stop.set()

    def test_workerless_fleet_probes_error_not_hang(self):
        with _broker() as broker:
            port = broker.address[1]
            cn = CanaryDaemon(f"127.0.0.1:{port}", _probes(1),
                              probe_interval=999, probe_timeout=0.5,
                              serve_http=False)
            try:
                r = cn.probe_once()
                assert r["result"] == "error" and r["stage"] == "result"
                assert _counter_total("canary_errors_total",
                                      stage="result") == 1
            finally:
                cn.stop()

    def test_dead_broker_probes_error_at_open(self):
        # Grab a port nobody listens on.
        import socket

        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        cn = CanaryDaemon(f"127.0.0.1:{port}", _probes(1),
                          probe_interval=999, probe_timeout=0.5,
                          serve_http=False)
        try:
            r = cn.probe_once()
            assert r["result"] == "error" and r["stage"] == "open"
            assert _counter_total("canary_errors_total", stage="open") == 1
        finally:
            cn.stop()

    def test_http_plane(self):
        with _broker() as broker:
            port = broker.address[1]
            _, stop, _ = _spawn_worker(OneMax, port, "cd-w2")
            cn = CanaryDaemon(f"127.0.0.1:{port}", _probes(1),
                              probe_interval=999, probe_timeout=15,
                              serve_http=True)
            cn.start()
            try:
                cn.probe_once()
                hz = json.loads(urllib.request.urlopen(
                    cn.url + "/healthz").read())
                assert hz["status"] == "ok" and hz["cycles"] == 1
                sz = json.loads(urllib.request.urlopen(
                    cn.url + "/statusz").read())
                assert sz["config"]["probes"] == 1
                assert len(sz["goldens"]) == 1
                cz = json.loads(urllib.request.urlopen(
                    cn.url + "/canaryz").read())
                assert cz["total"] == 1 and cz["ok"] == 1
                assert cz["probes"][0]["result"] == "ok"
                with pytest.raises(urllib.error.HTTPError):
                    urllib.request.urlopen(cn.url + "/nope")
            finally:
                cn.stop()
                stop.set()

    def test_golden_persists_across_daemon_restarts(self, tmp_path):
        path = str(tmp_path / "golden.json")
        with _broker() as broker:
            port = broker.address[1]
            _, stop, _ = _spawn_worker(OneMax, port, "cd-w3")
            try:
                cn = CanaryDaemon(f"127.0.0.1:{port}", _probes(1),
                                  space_key="onemax", probe_interval=999,
                                  probe_timeout=15, golden_path=path,
                                  serve_http=False)
                r1 = cn.probe_once()
                assert r1["newly_sealed"]
                cn.stop()
                # A NEW daemon must verify against the persisted seal,
                # not re-seal.
                cn2 = CanaryDaemon(f"127.0.0.1:{port}", _probes(1),
                                   space_key="onemax", probe_interval=999,
                                   probe_timeout=15, golden_path=path,
                                   serve_http=False)
                r2 = cn2.probe_once()
                assert not r2["newly_sealed"] and r2["result"] == "ok"
                cn2.stop()
            finally:
                stop.set()

    def test_telemetry_records_probe_and_drift(self):
        sink_records = []

        class _Sink:
            def record(self, rec):
                sink_records.append(rec)

        spans_mod.enable()
        spans_mod.set_run_sink(_Sink())
        inj = FaultInjector(FaultPlan([FaultSpec(
            hook="worker_pre_eval", kind="fitness_corrupt", at=1)]))
        with _broker() as broker:
            port = broker.address[1]
            _, stop, _ = _spawn_worker(OneMax, port, "cd-w4",
                                       fault_injector=inj)
            cn = CanaryDaemon(f"127.0.0.1:{port}", _probes(1),
                              probe_interval=999, probe_timeout=15,
                              serve_http=False)
            try:
                cn.probe_once()
                cn.probe_once()
                probes = [r for r in sink_records
                          if r.get("type") == "canary_probe"]
                assert len(probes) == 2
                assert probes[1]["result"] == "drift"
                drifts = [r for r in sink_records
                          if r.get("type") == "event"
                          and r.get("name") == "canary_drift"]
                assert len(drifts) == 1
            finally:
                cn.stop()
                stop.set()

    def test_needs_probes_and_brokers(self):
        with pytest.raises(ValueError):
            CanaryDaemon("127.0.0.1:1", [], serve_http=False)
        with pytest.raises(ValueError):
            CanaryDaemon([], _probes(1), serve_http=False)
