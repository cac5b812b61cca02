"""CI guards over the observability surface itself.

Three drift traps that previously only existed as eyeballs:

- the broker-throughput hot-path gate table, now embedded in the
  committed ``scripts/broker_throughput.json`` artifact — a gated plane
  creeping past 2% of dispatch fails HERE, not in a stderr table nobody
  re-reads;
- the registry ↔ ``docs/OBSERVABILITY.md`` metric-catalog agreement
  (``scripts/check_metric_docs.py``) — every library metric has a doc
  row, every doc row still names a live metric;
- the ``gentun_trace.py slo`` timeline reconstruction — fire→clear
  episode pairing, durations, evidence tails.
"""

import importlib.util
import json
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# hot-path gate table (scripts/broker_throughput.py + committed artifact)
# ---------------------------------------------------------------------------


class TestHotPathGate:
    @pytest.fixture(scope="class")
    def artifact(self):
        path = os.path.join(REPO, "scripts", "broker_throughput.json")
        with open(path, encoding="utf-8") as fh:
            return json.load(fh)

    def test_committed_artifact_has_the_table(self, artifact):
        table = artifact["hot_path_table"]
        assert table["gate_max_pct"] == 2.0
        gated = [r for r in table["rows"] if r["gated"]]
        assert len(gated) >= 8  # every gated control plane has a row
        assert table["within_gate"] is True

    def test_every_gated_plane_within_two_percent(self, artifact):
        over = [(r["plane"], r["overhead_pct"])
                for r in artifact["hot_path_table"]["rows"]
                if r["gated"] and r["overhead_pct"] > 2.0]
        assert not over, f"hot-path planes over the 2% gate: {over}"

    def test_builder_is_pure_and_consistent(self, artifact):
        bt = _load_script("broker_throughput")
        rebuilt = bt.hot_path_table(artifact)
        assert rebuilt == artifact["hot_path_table"]
        # Every plane held to the gate is represented in the constant.
        keys = {r.get("key") for r in rebuilt["rows"] if r["gated"]}
        assert keys == {k for k, _name in bt.HOT_PATH_GATED_PLANES}

    def test_builder_flags_a_regression(self, artifact):
        bt = _load_script("broker_throughput")
        bad = json.loads(json.dumps(artifact))  # deep copy
        bad["journal"]["overhead_pct"] = 3.7
        assert bt.hot_path_table(bad)["within_gate"] is False


# ---------------------------------------------------------------------------
# metric-catalog drift guard (scripts/check_metric_docs.py)
# ---------------------------------------------------------------------------


class TestMetricDocs:
    def test_repo_catalog_and_registry_agree(self):
        cmd = _load_script("check_metric_docs")
        result = cmd.check()
        assert not result["missing_from_docs"], (
            "registry metrics without a docs/OBSERVABILITY.md row: "
            f"{result['missing_from_docs']}")
        assert not result["stale_doc_rows"], (
            "doc rows for metrics that no longer exist: "
            f"{result['stale_doc_rows']}")
        assert result["ok"]

    def test_doc_row_parser(self, tmp_path):
        cmd = _load_script("check_metric_docs")
        doc = tmp_path / "OBS.md"
        doc.write_text(
            "| metric | type | labels | meaning |\n"
            "|---|---|---|---|\n"
            "| `jobs_total` | counter | — | jobs |\n"
            "| `depth` | gauge | `shard` | depth |\n"
            "| `not_a_metric` | fires when | page |\n"  # SLO-rule row shape
            "plain prose mentioning `other_name` |\n")
        rows = cmd.doc_metrics(str(doc))
        assert rows == {"jobs_total": "counter", "depth": "gauge"}

    def test_instrument_regex_matches_multiline_calls(self):
        cmd = _load_script("check_metric_docs")
        src = ('reg.counter("a_total", x=1).inc()\n'
               'reg.histogram(\n    "b_seconds").observe(1)\n'
               'reg.gauge(name_var).set(1)\n')  # variable: not collected
        assert cmd._INSTRUMENT_RE.findall(src) == ["a_total", "b_seconds"]


# ---------------------------------------------------------------------------
# gentun_trace slo subcommand
# ---------------------------------------------------------------------------


class TestSloTimeline:
    @pytest.fixture(scope="class")
    def trace_mod(self):
        return _load_script("gentun_trace")

    def _records(self):
        return [
            {"type": "alert", "event": "fire", "rule": "canary_correctness",
             "severity": "page", "subject": "fleet", "value": 1.0,
             "threshold": 0.0, "transition_seq": 1, "firing_since": 100.0,
             "t": 100.0},
            {"type": "scale", "action": "up", "rule": "canary_correctness",
             "subject": "fleet", "transition_seq": 1, "value": 1.0,
             "threshold": 0.0, "evidence": [[98.0, 0.0], [99.0, 0.0],
                                            [100.0, 1.0], [101.0, 1.0]],
             "from": 2, "to": 3, "outcome": "spawned 1", "t": 101.0},
            {"type": "event", "name": "canary_drift", "t_wall": 100.5,
             "data": {"genome": "g1"}},
            {"type": "alert", "event": "clear", "rule": "canary_correctness",
             "severity": "page", "subject": "fleet", "value": 0.0,
             "threshold": 0.0, "transition_seq": 2, "firing_since": 100.0,
             "t": 160.0},
            {"type": "alert", "event": "fire", "rule": "worker_idle_ratio",
             "severity": "warn", "subject": "w0", "value": 0.9,
             "threshold": 0.5, "transition_seq": 3, "firing_since": 200.0,
             "t": 200.0},
            {"type": "canary_probe", "cycle": 1, "result": "ok", "t": 90.0},
            {"type": "canary_probe", "cycle": 2, "result": "drift",
             "t": 100.5},
        ]

    def test_episodes_pair_fire_with_clear(self, trace_mod):
        tl = trace_mod.slo_timeline(self._records())
        assert tl["summary"] == {
            "fires": 2, "clears": 1, "open": 1,
            "by_severity": {"page": 1, "warn": 1},
            "scale_actions": 1,
            "canary_probes": {"drift": 1, "ok": 1},
            "canary_drift_events": 1,
        }
        ep = tl["episodes"][0]
        assert (ep["fire_seq"], ep["clear_seq"]) == (1, 2)
        assert ep["duration_s"] == 60.0 and not ep["open"]

    def test_window_gathers_actions_and_drifts(self, trace_mod):
        ep = trace_mod.slo_timeline(self._records())["episodes"][0]
        assert len(ep["actions"]) == 1
        act = ep["actions"][0]
        assert (act["from"], act["to"]) == (2, 3)
        assert act["evidence_tail"] == [[99.0, 0.0], [100.0, 1.0],
                                        [101.0, 1.0]]  # last 3 only
        assert ep["drifts"][0]["data"] == {"genome": "g1"}

    def test_open_episode_and_render(self, trace_mod):
        tl = trace_mod.slo_timeline(self._records())
        assert tl["episodes"][1]["open"] is True
        assert tl["episodes"][1]["duration_s"] is None
        text = trace_mod.render_slo(tl)
        assert "canary_correctness" in text and "(open)" in text

    def test_empty_ledger(self, trace_mod):
        tl = trace_mod.slo_timeline([])
        assert tl["episodes"] == [] and tl["summary"]["fires"] == 0
        assert "no alert transitions" in trace_mod.render_slo(tl)
