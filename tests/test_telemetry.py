"""Telemetry-plane tests: registry semantics, span propagation, artifacts.

Covers the acceptance criteria of the telemetry subsystem
(docs/OBSERVABILITY.md):

- metrics registry semantics, including concurrent increments,
- JSONL / Prometheus renderer round-trips,
- span nesting + trace-id propagation across a fake broker round trip
  (capture → wire → attach → ingest, no double counting),
- disabled mode is a shared no-op singleton (no per-call allocation),
- end-to-end: a 2-worker in-process distributed search with telemetry
  enabled produces a ``telemetry.jsonl`` whose worker-side train/eval
  spans carry the same trace_id as the master-side generation spans,
  with non-zero percentiles — and the search trajectory is bit-identical
  to a telemetry-disabled run.
"""

import contextlib
import json
import math
import threading
import time

import numpy as np
import pytest

from gentun_tpu import GeneticAlgorithm, Individual, genetic_cnn_genome
from gentun_tpu.telemetry import spans as spans_mod
from gentun_tpu.telemetry.export import RunTelemetry, _percentile
from gentun_tpu.telemetry.registry import (
    DEFAULT_BUCKETS,
    MetricsRegistry,
    get_registry,
)


@pytest.fixture(autouse=True)
def _pristine_telemetry():
    """Telemetry state is process-global; every test starts and ends clean."""
    spans_mod.disable()
    spans_mod.set_run_sink(None)
    get_registry().reset()
    yield
    spans_mod.disable()
    spans_mod.set_run_sink(None)
    get_registry().reset()


class _ListSink:
    """Minimal run sink: records into a list (thread-safe enough for tests)."""

    def __init__(self):
        self.records = []

    def record(self, rec):
        self.records.append(rec)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_counter_monotone(self):
        reg = MetricsRegistry()
        c = reg.counter("jobs_total", worker="w0")
        c.inc()
        c.inc(2.5)
        assert c.value == 3.5
        with pytest.raises(ValueError):
            c.inc(-1)

    def test_gauge_up_and_down(self):
        reg = MetricsRegistry()
        g = reg.gauge("queue_depth")
        g.set(5)
        g.inc()
        g.dec(3)
        assert g.value == 3.0

    def test_get_or_create_identity_and_label_order(self):
        reg = MetricsRegistry()
        a = reg.counter("x", species="OneMax", phase="train")
        b = reg.counter("x", phase="train", species="OneMax")  # order-insensitive
        assert a is b
        assert reg.counter("x", phase="eval", species="OneMax") is not a

    def test_histogram_buckets_fixed_and_quantiles_ordered(self):
        reg = MetricsRegistry()
        h = reg.histogram("span_seconds", kind="train")
        assert h.bounds == DEFAULT_BUCKETS
        for v in (1e-4, 1e-3, 1e-2, 0.1, 1.0, 10.0):
            h.observe(v)
        assert h.count == 6
        assert h.sum == pytest.approx(11.1111, rel=1e-3)
        q50, q95 = h.quantile(0.5), h.quantile(0.95)
        assert 0 < q50 <= q95
        # log-interpolated estimate lands within a bucket of the true median
        assert 1e-3 <= q50 <= 3e-2

    def test_histogram_overflow_clamps(self):
        reg = MetricsRegistry()
        h = reg.histogram("h", buckets=(1.0, 10.0))
        h.observe(1e9)  # way past the top bound → +Inf bucket
        assert h.quantile(0.99) == 10.0  # clamped to the top finite bound
        buckets = h.snapshot_buckets()
        assert buckets[-1] == (math.inf, 1)
        assert buckets[-2] == (10.0, 0)

    def test_concurrent_increments_lose_nothing(self):
        reg = MetricsRegistry()
        c = reg.counter("hits")
        h = reg.histogram("lat")
        n_threads, per_thread = 8, 1000

        def _hammer():
            for _ in range(per_thread):
                c.inc()
                h.observe(0.001)

        threads = [threading.Thread(target=_hammer) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == n_threads * per_thread
        assert h.count == n_threads * per_thread

    def test_snapshot_shape_and_reset(self):
        reg = MetricsRegistry()
        reg.counter("c", a="1").inc()
        reg.gauge("g").set(2)
        reg.histogram("h").observe(0.5)
        snap = reg.snapshot()
        assert [m["name"] for m in snap["counters"]] == ["c"]
        assert snap["counters"][0]["labels"] == {"a": "1"}
        assert snap["gauges"][0]["value"] == 2.0
        hist = snap["histograms"][0]
        assert hist["count"] == 1 and hist["sum"] == 0.5
        assert hist["buckets"][-1][0] == "+Inf"  # JSON-native (no float inf)
        reg.reset()
        assert reg.snapshot() == {"counters": [], "gauges": [], "histograms": []}


class TestRenderers:
    def test_jsonl_round_trip(self):
        reg = MetricsRegistry()
        reg.counter("jobs_total", worker="w0").inc(3)
        reg.gauge("depth").set(7)
        reg.histogram("lat", kind="eval").observe(0.25)
        lines = [json.loads(l) for l in reg.render_jsonl().splitlines()]
        by_name = {(r["metric"], r["name"]): r for r in lines}
        assert by_name[("counter", "jobs_total")]["value"] == 3.0
        assert by_name[("counter", "jobs_total")]["labels"] == {"worker": "w0"}
        assert by_name[("gauge", "depth")]["value"] == 7.0
        hist = by_name[("histogram", "lat")]
        assert hist["count"] == 1
        # cumulative buckets end at the +Inf total
        assert hist["buckets"][-1] == ["+Inf", 1]

    def test_prometheus_exposition(self):
        reg = MetricsRegistry()
        reg.counter("jobs_total", worker="w0").inc(3)
        reg.histogram("lat", buckets=(0.1, 1.0), kind="eval").observe(0.25)
        text = reg.render_prometheus()
        assert "# TYPE jobs_total counter" in text
        assert 'jobs_total{worker="w0"} 3' in text
        assert "# TYPE lat histogram" in text
        # cumulative: 0.25 falls in the le="1" bucket, +Inf repeats the total
        assert 'lat_bucket{kind="eval",le="0.1"} 0' in text
        assert 'lat_bucket{kind="eval",le="1"} 1' in text
        assert 'lat_bucket{kind="eval",le="+Inf"} 1' in text
        assert 'lat_sum{kind="eval"} 0.25' in text
        assert 'lat_count{kind="eval"} 1' in text


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class TestSpansDisabled:
    def test_noop_singleton_no_allocation(self):
        assert not spans_mod.enabled()
        s1 = spans_mod.span("anything")
        s2 = spans_mod.span("else", {"never": "built"})
        assert s1 is s2  # the shared _NOOP instance: zero per-call allocation
        with s1 as s:
            s.set(ignored=True)
        assert spans_mod.current_context() is None

    def test_record_helpers_are_noops(self):
        sink = _ListSink()
        spans_mod.set_run_sink(sink)
        spans_mod.record_span("k", time.monotonic(), 0.1)
        spans_mod.record_event("e", {"x": 1})
        assert sink.records == []
        assert get_registry().snapshot()["histograms"] == []


class TestSpansEnabled:
    def test_nesting_links_parent_child(self):
        spans_mod.enable()
        sink = _ListSink()
        spans_mod.set_run_sink(sink)
        with spans_mod.span("outer") as outer:
            with spans_mod.span("inner", {"n": 1}) as inner:
                assert inner.trace_id == outer.trace_id
                assert inner.parent_id == outer.span_id
                ctx = spans_mod.current_context()
                assert ctx == {"trace_id": inner.trace_id, "span_id": inner.span_id}
        # records arrive innermost-first, duration fields populated
        kinds = [r["kind"] for r in sink.records]
        assert kinds == ["inner", "outer"]
        inner_rec, outer_rec = sink.records
        assert inner_rec["attrs"] == {"n": 1}
        assert inner_rec["dur_s"] >= 0.0
        assert outer_rec["parent_id"] is None
        # durations observed into the shared histogram (one per span)
        assert get_registry().histogram("span_seconds", kind="inner").count == 1

    def test_error_span_records_exception_name(self):
        spans_mod.enable()
        sink = _ListSink()
        spans_mod.set_run_sink(sink)
        with pytest.raises(RuntimeError):
            with spans_mod.span("boom"):
                raise RuntimeError("x")
        assert sink.records[0]["error"] == "RuntimeError"

    def test_fake_broker_round_trip_propagates_trace(self):
        """Master span context → wire (JSON) → worker attach/capture →
        result frame → master ingest.  One histogram observation per span
        (capture defers, ingest observes), worker spans in the master's
        sink carry the master's trace_id."""
        spans_mod.enable()
        sink = _ListSink()
        spans_mod.set_run_sink(sink)
        wire = {}

        with spans_mod.span("generation") as gen:
            # master builds the payload while the span is live
            wire["job"] = json.dumps({"genes": [1, 0], "trace": spans_mod.current_context()})

            def worker():
                job = json.loads(wire["job"])
                with spans_mod.attach(job["trace"]), spans_mod.capture() as captured:
                    with spans_mod.span("train", {"individuals": 1}):
                        time.sleep(0.001)
                for rec in captured:
                    rec.setdefault("src", "w0")
                wire["result"] = json.dumps({"fitness": 1.0, "spans": captured})

            t = threading.Thread(target=worker)  # own thread = own context
            t.start()
            t.join()
            # captured spans were NOT observed locally (defer to ingest)
            assert get_registry().histogram("span_seconds", kind="train").count == 0
            spans_mod.ingest(json.loads(wire["result"])["spans"])

        train_recs = [r for r in sink.records if r.get("kind") == "train"]
        assert len(train_recs) == 1
        (tr,) = train_recs
        assert tr["trace_id"] == gen.trace_id
        assert tr["parent_id"] == gen.span_id  # parented under the master span
        assert tr["src"] == "w0"
        # exactly ONE observation despite capture + ingest in one process
        assert get_registry().histogram("span_seconds", kind="train").count == 1

    def test_attach_none_is_noop(self):
        spans_mod.enable()
        with spans_mod.attach(None):
            assert spans_mod.current_context() is None

    def test_record_event_carries_context(self):
        spans_mod.enable()
        sink = _ListSink()
        spans_mod.set_run_sink(sink)
        with spans_mod.span("outer") as outer:
            spans_mod.record_event("fault_injected", {"hook": "recv"})
        ev = [r for r in sink.records if r["type"] == "event"][0]
        assert ev["name"] == "fault_injected"
        assert ev["trace_id"] == outer.trace_id
        assert ev["data"] == {"hook": "recv"}


# ---------------------------------------------------------------------------
# export (RunTelemetry artifact)
# ---------------------------------------------------------------------------


class TestRunTelemetry:
    def test_percentile_exact(self):
        vals = sorted([1.0, 2.0, 3.0, 4.0])
        assert _percentile(vals, 0.5) == 2.5
        assert _percentile(vals, 0.0) == 1.0
        assert _percentile(vals, 1.0) == 4.0
        assert _percentile([], 0.5) == 0.0
        assert _percentile([7.0], 0.95) == 7.0

    def test_artifact_lifecycle(self, tmp_path):
        path = tmp_path / "telemetry.jsonl"
        with RunTelemetry(str(path), label="unit") as run:
            assert spans_mod.enabled()  # install enables tracing
            with spans_mod.span("step"):
                pass
            spans_mod.record_event("tick")
        assert not spans_mod.enabled()  # close disables it again
        lines = [json.loads(l) for l in path.read_text().splitlines()]
        assert lines[0]["type"] == "run_start" and lines[0]["label"] == "unit"
        assert lines[-1]["type"] == "summary"
        kinds = {r.get("kind") for r in lines if r["type"] == "span"}
        assert kinds == {"step"}
        summ = run.summary()
        assert summ["spans"]["step"]["count"] == 1
        assert summ["events"] == {"tick": 1}

    def test_summary_percentiles_from_raw_durations(self, tmp_path):
        run = RunTelemetry(str(tmp_path / "t.jsonl"))
        run.install()
        try:
            for d in (0.1, 0.2, 0.3, 0.4, 0.5):
                run.record({"type": "span", "kind": "k", "dur_s": d})
        finally:
            summ = run.close()
        k = summ["spans"]["k"]
        assert k["count"] == 5
        assert k["p50"] == pytest.approx(0.3)
        assert k["total_s"] == pytest.approx(1.5)


# ---------------------------------------------------------------------------
# end-to-end: 2-worker in-process distributed search
# ---------------------------------------------------------------------------


class OneMax(Individual):
    """Cheap deterministic fitness: count of set bits."""

    def build_spec(self, **params):
        return genetic_cnn_genome(tuple(params.get("nodes", (4, 4))))

    def evaluate(self):
        return float(sum(sum(g) for g in self.genes.values()))


DATA = (np.zeros(1, np.float32), np.zeros(1, np.float32))


def _run_search(telemetry_path=None):
    """One deterministic distributed search; returns its trajectory."""
    from gentun_tpu.distributed import DistributedPopulation, GentunClient

    with DistributedPopulation(OneMax, size=8, seed=6, port=0) as pop, contextlib.ExitStack() as tele:
        # Telemetry goes on BEFORE the workers connect: the broker sets
        # ``broker_workers_connected`` on a connect, telemetry on only.
        run = tele.enter_context(RunTelemetry(telemetry_path, label="e2e")) if telemetry_path is not None else None
        _, port = pop.broker_address
        stops = []
        for i in range(2):
            stop = threading.Event()
            threading.Thread(
                target=lambda s=stop, wid=f"w{i}": GentunClient(
                    OneMax, *DATA, host="127.0.0.1", port=port,
                    heartbeat_interval=0.2, reconnect_delay=0.1,
                    worker_id=wid,
                ).work(stop_event=s),
                daemon=True,
            ).start()
            stops.append(stop)
        try:
            ga = GeneticAlgorithm(pop, seed=6)
            best = ga.run(3)
            tele.close()
            summary = run.summary() if run is not None else None
            trajectory = [
                (h["generation"], h["best_fitness"], h["best_genes"])
                for h in ga.history
            ]
            return best.get_genes(), best.get_fitness(), trajectory, summary
        finally:
            for s in stops:
                s.set()


@pytest.fixture(scope="module")
def traced_search(tmp_path_factory):
    """ONE telemetry-enabled 2-worker search, shared by the E2E tests."""
    path = str(tmp_path_factory.mktemp("tele") / "telemetry.jsonl")
    genes, fit, traj, summary = _run_search(telemetry_path=path)
    return {"path": path, "genes": genes, "fitness": fit,
            "trajectory": traj, "summary": summary}


class TestEndToEndTelemetry:
    def test_two_worker_search_produces_linked_artifact(self, traced_search):
        summary = traced_search["summary"]
        lines = [json.loads(l) for l in open(traced_search["path"], encoding="utf-8")]
        assert lines[0]["type"] == "run_start"
        assert lines[-1]["type"] == "summary"
        spans = [r for r in lines if r["type"] == "span"]
        by_kind = {}
        for r in spans:
            by_kind.setdefault(r["kind"], []).append(r)

        # master-side structure: one run, 3 generations, evaluate+reproduce
        assert len(by_kind["run"]) == 1
        assert len(by_kind["generation"]) == 3
        assert len(by_kind["evaluate"]) == 4  # 3 gens + final evaluate
        assert len(by_kind["reproduce"]) == 3
        # broker-side + worker-side kinds all present
        for kind in ("queue_wait", "job", "eval", "train", "select"):
            assert by_kind.get(kind), f"missing span kind {kind!r}"

        # cross-process trace stitching: every worker-shipped span (it has a
        # `src` worker id) carries a generation span's trace_id
        gen_traces = {r["trace_id"] for r in by_kind["generation"]}
        worker_spans = [r for r in spans if "src" in r]
        assert worker_spans, "no worker-side spans shipped back"
        assert {r["src"] for r in worker_spans} <= {"w0", "w1"}
        for r in worker_spans:
            assert r["trace_id"] in gen_traces
        # worker eval groups parent directly under master evaluate spans
        eval_span_ids = {r["span_id"] for r in by_kind["evaluate"]}
        for r in by_kind["eval"]:
            assert r["parent_id"] in eval_span_ids

        # summary percentiles are non-zero for the acceptance kinds
        for kind in ("evaluate", "queue_wait", "train"):
            stats = summary["spans"][kind]
            assert stats["count"] > 0
            assert stats["p50"] > 0.0, f"{kind} p50 is zero"
            assert stats["p95"] > 0.0, f"{kind} p95 is zero"

        # registry picked up the broker instruments
        gauge_names = {g["name"] for g in summary["gauges"]}
        assert "broker_queue_depth" in gauge_names
        assert "broker_workers_connected" in gauge_names

    def test_disabled_run_is_bit_identical(self, traced_search):
        """Same seeds with telemetry off → identical trajectory."""
        genes_p, fit_p, traj_p, _ = _run_search(telemetry_path=None)
        assert traced_search["genes"] == genes_p
        assert traced_search["fitness"] == fit_p
        assert traced_search["trajectory"] == traj_p
