"""PR 38: the host sampler, ``wait_cpu_s`` on a fenced span, and the stall reader.

- off (the default): an evaluation call starts no thread, opens no ``/proc``
  file, and ``spans.span(...)`` is still the shared no-op;
- on: ``evaluation_prelude`` starts one thread however often it is called,
  the thread ticks at its period, ``spans.disable()`` joins it and a prelude
  that lost the race with it starts nothing; a late wake (clock and sleep
  handed in, no real waiting) is one ``host_hiccup`` event, one count of
  ``host_hiccups_total`` and one observation of ``host_hiccup_seconds``; a
  ``/proc`` without pressure files, without a ``steal`` column or with
  gVisor's zeros is tolerated;
- ``fence`` sets ``wait_cpu_s`` beside ``dispatch_s``;
- the ticks are ``gentun/tick`` annotations with their stats in the profiler's
  own file, and change no count that ``scope_reduce`` takes from the others;
- ``benchmark/stall_reduce.py`` gives ``benchmark/fixtures/stall_fixture.json``'s
  four numbers and two stall records (a late tick through which the process
  burned CPU is "GIL held", not "host late"), and nothing, without raising, for
  a trace with no TPU plane; the manifest holds 91 per-layer metrics.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import scope_reduce  # noqa: E402
import stall_reduce  # noqa: E402

from gentun_tpu.models import evaluation  # noqa: E402
from gentun_tpu.models.cnn import GeneticCnnModel  # noqa: E402
from gentun_tpu.telemetry import spans  # noqa: E402
from gentun_tpu.telemetry.registry import get_registry  # noqa: E402

THREAD = "gentun-host-sampler"
KW = dict(nodes=(3, 2), kernels_per_layer=(4, 8), kfold=2, epochs=(1,), learning_rate=(0.01,),
          batch_size=8, dense_units=16, cache_dir=False, seed=3)
GENOMES = [{"S_1": (1, 0, 1), "S_2": (1,)}, {"S_1": (0, 0, 0), "S_2": (0,)}]
NUMBERS = stall_reduce.METRICS


def sampler_threads():
    return [t for t in threading.enumerate() if t.name == THREAD and t.is_alive()]


class Records:
    def __init__(self):
        self.items = []

    def record(self, rec):
        self.items.append(rec)

    def events(self, name):
        return [r for r in self.items if r.get("type") == "event" and r["name"] == name]


@pytest.fixture
def telemetry():
    """Telemetry on with a sink of its own; everything back as it was after."""
    sink = Records()
    seen = set(evaluation._seen_programs)
    spans.set_run_sink(sink)
    spans.enable()
    try:
        yield sink
    finally:
        spans.disable()
        spans.set_run_sink(None)
        evaluation._seen_programs.clear()
        evaluation._seen_programs.update(seen)


@pytest.fixture(scope="module")
def data():
    rng = np.random.default_rng(0)
    return (rng.normal(size=(64, 8, 8, 3)).astype(np.float32), rng.integers(0, 10, 64).astype(np.int32))


# -- off ---------------------------------------------------------------------------------------


def test_off_an_evaluation_call_starts_no_thread_and_reads_no_proc_file(data, monkeypatch):
    assert not spans.enabled()
    opened = []
    real_open, real_os_open = open, os.open
    monkeypatch.setattr("builtins.open", lambda f, *a, **k: (opened.append(str(f)), real_open(f, *a, **k))[1])
    monkeypatch.setattr(os, "open", lambda f, *a, **k: (opened.append(str(f)), real_os_open(f, *a, **k))[1])
    fitness = GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
    monkeypatch.undo()
    assert np.isfinite(fitness).all()
    assert not sampler_threads() and spans._sampler is None
    assert not [f for f in opened if f.startswith("/proc")]


def test_off_the_prelude_alone_opens_nothing(monkeypatch):
    opened = []
    monkeypatch.setattr("builtins.open", lambda f, *a, **k: opened.append(f))
    monkeypatch.setattr(os, "open", lambda f, *a, **k: opened.append(f))
    evaluation.evaluation_prelude(False)
    monkeypatch.undo()
    assert opened == [] and not sampler_threads()


def test_off_span_is_still_the_shared_noop():
    assert spans.span("train") is spans.span("eval")
    assert spans.span("train").fence(7) == 7


# -- on: the thread ---------------------------------------------------------------------------


def test_on_the_prelude_starts_one_thread_and_disable_joins_it(telemetry):
    evaluation.evaluation_prelude(False)
    evaluation.evaluation_prelude(False)
    spans.enable()  # a second enable changes nothing
    evaluation.evaluation_prelude(False)
    assert len(sampler_threads()) == 1
    thread = sampler_threads()[0]
    assert thread.daemon
    spans.disable()
    assert not thread.is_alive() and not sampler_threads() and spans._sampler is None


def test_on_a_later_enable_starts_a_thread_again(telemetry):
    evaluation.evaluation_prelude(False)
    spans.disable()
    spans.enable()
    assert not sampler_threads()  # not before a traced evaluation starts
    evaluation.evaluation_prelude(False)
    assert len(sampler_threads()) == 1


def test_on_an_evaluation_call_runs_under_the_sampler(data, telemetry):
    GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
    assert len(sampler_threads()) == 1 and spans._sampler.ticks > 0


def test_a_prelude_that_lost_the_race_with_disable_starts_nothing(telemetry):
    from gentun_tpu.telemetry import sampler

    spans.disable()  # after the prelude's look at the switch, before its call
    assert sampler.ensure_started() is None and not sampler_threads() and spans._sampler is None


def test_the_constants_are_stated_once_in_the_module_and_the_reader():
    from gentun_tpu.telemetry import sampler

    assert sampler.PERIOD_S == 0.050 and sampler.LATE_S == 0.050
    assert stall_reduce.LATE_S == sampler.LATE_S and stall_reduce.STALL_S == 0.157


# -- on: a planted late wake, no real waiting ---------------------------------------------------


class Script:
    """A clock that the handed-in sleep moves: each sleep lasts what was asked plus the next planted lateness."""

    def __init__(self, lateness):
        self.now, self.lateness, self.slept = 100.0, list(lateness), []

    def clock(self):
        return self.now

    def sleep(self, seconds):
        self.slept.append(seconds)
        self.now += seconds + self.lateness.pop(0)
        return False


def planted(lateness, proc="/proc"):
    from gentun_tpu.telemetry import sampler

    script = Script(lateness)
    s = sampler.HostSampler(clock=script.clock, sleep=script.sleep, proc=proc)
    try:
        for _ in lateness:
            assert s.step()
    finally:
        s.close()
    return s, script


def test_ticks_are_counted_at_the_period(telemetry):
    """0.3 s of the handed-in clock: a sleep of a period and a tick for each period in it, no record."""
    from gentun_tpu.telemetry import sampler

    n = round(0.3 / sampler.PERIOD_S)
    s, script = planted([0.0] * n)
    assert s.ticks == n and script.now - 100.0 == pytest.approx(0.3)
    assert script.slept == pytest.approx([sampler.PERIOD_S] * n)
    assert not telemetry.items  # a tick on time is no record


def test_a_late_wake_is_one_hiccup(telemetry):
    reg = get_registry()
    count0 = reg.counter("host_hiccups_total").value
    seen0 = reg.histogram("host_hiccup_seconds").count
    from gentun_tpu.telemetry.sampler import PERIOD_S

    s, script = planted([0.001, 0.5, 0.002, 0.049])
    assert s.ticks == 4
    (event,) = telemetry.events("host_hiccup")
    assert event["data"]["late_s"] == pytest.approx(0.5)
    assert event["data"]["gap_s"] == pytest.approx(PERIOD_S - 0.001 + 0.5)
    assert {"cpu_s", "nivcsw", "majflt", "mach_busy_s", "mach_steal_s"} <= set(event["data"])
    assert reg.counter("host_hiccups_total").value == count0 + 1
    assert reg.histogram("host_hiccup_seconds").count == seen0 + 1
    # the schedule holds through small lateness and starts anew after the long pause
    assert script.slept == pytest.approx([PERIOD_S, PERIOD_S - 0.001, PERIOD_S, PERIOD_S - 0.002])


def test_a_hiccup_needs_telemetry_on():
    assert not spans.enabled()
    sink = Records()
    spans.set_run_sink(sink)
    try:
        planted([0.2])
    finally:
        spans.set_run_sink(None)
    assert sink.items == []


@pytest.mark.parametrize("stat_line, busy_ticks, steal_ticks", [
    ("cpu  100 5 50 1000 7 3 2 40 0 0\n", 200, 40),  # a kernel of today: user nice system irq softirq steal
    ("cpu  100 5 50 1000 7 3 2\n", 160, 0),  # no steal column
    ("cpu  100 5 50 1000\n", 155, 0),  # four columns
    ("", 0, 0),  # an empty file
])
def test_proc_stat_without_steal_and_no_pressure_files_are_tolerated(tmp_path, stat_line, busy_ticks, steal_ticks):
    from gentun_tpu.telemetry import sampler

    (tmp_path / "stat").write_text(stat_line + "cpu0 1 2 3 4\n" * bool(stat_line))
    tick_us = 1_000_000 // os.sysconf("SC_CLK_TCK")
    s = sampler.HostSampler(proc=str(tmp_path))
    try:
        assert s.names == ("cpu_us", "nivcsw", "majflt", "mach_busy_us", "mach_steal_us")
        seen = dict(zip(s.names, s._counters()))
        assert (seen["mach_busy_us"], seen["mach_steal_us"]) == (busy_ticks * tick_us, steal_ticks * tick_us)
    finally:
        s.close()


def test_a_kernel_whose_proc_stat_counts_nothing_reads_zero_and_the_reader_says_not_counted(tmp_path):
    """gVisor, which the chip machines run: every field of ``/proc/stat`` reads 0."""
    from gentun_tpu.telemetry import sampler

    (tmp_path / "stat").write_text("cpu  0 0 0 0 0 0 0 0 0 0\ncpu0 0 0 0 0 0 0 0 0 0 0\n")
    s = sampler.HostSampler(proc=str(tmp_path))
    try:
        seen = dict(zip(s.names, s._counters()))
        assert (seen["mach_busy_us"], seen["mach_steal_us"]) == (0, 0) and seen["cpu_us"] > 0
        assert s._stat_fd is None  # not read again: a read lets go of the GIL
    finally:
        s.close()
    ticks = [{"kind": "tick", "start": 0.1 * i, "end": 0.1 * i, "stats": {"late_us": 0, "gap_us": 100000, "cpu_us": 10000,
                                                                          "mach_busy_us": 0}} for i in range(1, 31)]
    got = stall_reduce.reduce({"/device:TPU:0": {"ops": [("a", 0.0, 1.0), ("b", 2.0, 3.0)], "runs": []}}, ticks, {}, (0.0, 3.0))
    (stall,) = got["stalls"]
    assert got["host_cpu_other_share"] is None and stall["other_cpu_s"] is None and stall["cpu_s"] == pytest.approx(0.1)
    assert "other cpu not counted" in stall_reduce.describe(stall)


def test_no_proc_at_all_is_tolerated(tmp_path):
    s, _ = planted([0.0, 0.0], proc=str(tmp_path / "absent"))
    assert s.ticks == 2 and s._last[3:5] == [0, 0]


def test_pressure_files_are_read_where_they_exist(tmp_path):
    from gentun_tpu.telemetry import sampler

    (tmp_path / "pressure").mkdir()
    (tmp_path / "pressure" / "memory").write_text("some avg10=0.00 avg60=0.00 avg300=0.00 total=285814\nfull avg10=0.00 total=7\n")
    s = sampler.HostSampler(proc=str(tmp_path))
    try:
        assert s.names[-1] == "psi_mem_us" and s._counters()[-1] == 285814
    finally:
        s.close()


# -- fence ---------------------------------------------------------------------------------------


def test_fence_sets_wait_cpu_s_beside_dispatch_s(telemetry):
    import jax.numpy as jnp

    with spans.span("train") as sp:
        sp.fence(jnp.ones((64, 64)) @ jnp.ones((64, 64)))
    (rec,) = [r for r in telemetry.items if r.get("type") == "span"]
    assert rec["attrs"]["wait_cpu_s"] >= 0.0 and 0.0 <= rec["attrs"]["dispatch_s"] <= rec["dur_s"]


def test_every_fenced_span_of_an_evaluation_carries_wait_cpu_s(data, telemetry):
    GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
    fenced = [r for r in telemetry.items if r.get("type") == "span" and "dispatch_s" in (r.get("attrs") or {})]
    assert fenced and all(r["attrs"]["wait_cpu_s"] >= 0.0 for r in fenced)


# -- the ticks in the profiler's own file ----------------------------------------------------------


@pytest.fixture(scope="module")
def cpu_trace(tmp_path_factory, data):
    """A trace this jax writes of one evaluation call under the sampler: no TPU plane."""
    import jax

    folder = str(tmp_path_factory.mktemp("trace"))
    seen = set(evaluation._seen_programs)
    spans.enable()
    try:
        GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)  # compiled before the trace starts
        jax.profiler.start_trace(folder)
        with jax.profiler.TraceAnnotation("bench_anchor"):
            pass
        GeneticCnnModel.cross_validate_population(*data, GENOMES, **KW)
        time.sleep(0.1)
        jax.profiler.stop_trace()
    finally:
        spans.disable()
        evaluation._seen_programs.clear()
        evaluation._seen_programs.update(seen)
    import trace_reduce

    return trace_reduce.newest_xplane(folder)


def test_ticks_lie_in_the_trace_with_their_stats(cpu_trace):
    trace = stall_reduce.read(cpu_trace)
    ticks = [a for a in trace["annotations"] if a["kind"] == stall_reduce.TICK]
    assert len(ticks) >= 3 and trace["anchor"] is not None
    for key in ("late_us", "gap_us", "cpu_us", "nivcsw", "majflt", "mach_busy_us", "mach_steal_us"):
        assert all(key in t["stats"] for t in ticks), key
    assert any(a["kind"] == "cv_call" for a in trace["annotations"])
    assert trace["devices"] == {}
    # the sampler's own thread is no witness of the others
    assert not any(n.endswith(" step") and "sampler.py" in n for events in trace["host_lines"].values()
                   for n, _, _ in events)


def test_ticks_change_no_count_of_the_accepted_reader(cpu_trace):
    """``individuals_traced`` goes by the call annotation's kind."""
    trace = scope_reduce.read(cpu_trace)
    assert any(a["kind"] == stall_reduce.TICK for a in trace["annotations"])
    assert scope_reduce.individuals_traced(trace, {}) == len(GENOMES)


def test_a_trace_with_no_tpu_plane_gives_nothing_and_does_not_raise(cpu_trace, monkeypatch, capsys):
    monkeypatch.setattr(scope_reduce, "newest_trace", lambda cell: cpu_trace)
    run = {"cell": {"name": "c10_flagship.popeval"}, "trace": {"window_s": 1.0}, "window": (0.0, 1.0),
           "records": [], "units": []}
    assert stall_reduce.table(run) is None and run["stall_table"] is None
    for name in NUMBERS:
        reader = {}
        exec(open(os.path.join(ROOT, "benchmark", "layer_metrics", name + ".py"), encoding="utf-8").read(), reader)
        assert reader["read"](run) is None
    assert "info stall" not in capsys.readouterr().out


def test_no_trace_at_all_gives_nothing(monkeypatch):
    monkeypatch.setattr(scope_reduce, "newest_trace", lambda cell: None)
    assert stall_reduce.table({"cell": {"name": "x"}}) is None


# -- the reduction on the fixture ------------------------------------------------------------------


@pytest.fixture(scope="module")
def fixture():
    with open(os.path.join(ROOT, "benchmark", "fixtures", "stall_fixture.json"), encoding="utf-8") as fh:
        return json.load(fh)


def reduce_fixture(f, annotations=None):
    events = lambda d: {k: [tuple(e) for e in v] for k, v in d.items()}
    return stall_reduce.reduce({d: events(lines) for d, lines in f["devices"].items()},
                               f["annotations"] if annotations is None else annotations, events(f["host_lines"]),
                               tuple(f["stretch"]), [tuple(s) for s in f["host_spans"]], f["shift"])


@pytest.mark.parametrize("name", NUMBERS + ("host_cpu_other_share",))
def test_the_fixtures_numbers(fixture, name):
    assert reduce_fixture(fixture)[name] == pytest.approx(fixture["expect"][name], abs=1e-9)


@pytest.mark.parametrize("index", [0, 1])
def test_the_fixtures_two_stall_records(fixture, index):
    got = reduce_fixture(fixture)
    assert len(got["stalls"]) == 2
    stall, want = got["stalls"][index], fixture["expect"]["stalls"][index]
    for key, value in want.items():
        if isinstance(value, float):
            assert stall[key] == pytest.approx(value, abs=1e-9), key
        elif key == "host_events":
            assert stall[key][:len(value)] == value
        else:
            assert stall[key] == value, key
    assert stall_reduce.describe(stall).startswith(f"/device:TPU:0 at {want['start_s']:.3f} s for {want['length_s']:.3f} s")


def test_the_fixtures_short_gap_is_no_stall_but_is_on_record(fixture):
    got = reduce_fixture(fixture)
    lengths = [g["length_s"] for g in got["longest_gaps"]]
    assert lengths == pytest.approx(fixture["expect"]["longest_gap_lengths"], abs=1e-9)
    assert lengths == sorted(lengths, reverse=True) and lengths[2] < stall_reduce.STALL_S


def test_a_program_without_the_sampler_reads_the_device_numbers_only(fixture):
    """The parent commit under this PR's readers: no tick in the trace."""
    got = reduce_fixture(fixture, [a for a in fixture["annotations"] if a["kind"] != stall_reduce.TICK])
    assert got["device_stall_s"] == pytest.approx(0.5) and got["stall_between_programs_s"] == pytest.approx(0.3)
    assert got["stall_host_late_s"] is None and got["host_tick_late_max_ms"] is None
    assert got["host_cpu_other_share"] is None


def test_a_run_without_a_stall_reads_zero_not_nothing(fixture):
    quiet = dict(fixture, devices={"/device:TPU:1": fixture["devices"]["/device:TPU:1"]})
    got = reduce_fixture(quiet)
    assert got["stalls"] == [] and got["device_stall_s"] == 0.0 and got["stall_between_programs_s"] == 0.0
    assert got["stall_host_late_s"] == 0.0 and got["host_tick_late_max_ms"] == pytest.approx(400.0)


def test_a_loop_op_does_not_hide_a_stall_in_its_body():
    ops = [("while.1", 0.0, 10.0), ("a", 0.0, 1.0), ("b", 4.0, 10.0)]
    assert [o[0] for o in stall_reduce.leaves(ops)] == ["a", "b"]
    got = stall_reduce.reduce({"/device:TPU:0": {"ops": ops, "runs": [("jit_f(1)", 0.0, 10.0)]}}, [], {}, (0.0, 10.0))
    (stall,) = got["stalls"]
    assert stall["where"] == "in_program" and stall["length_s"] == pytest.approx(3.0)
    assert (stall["program"], stall["before"], stall["after"]) == ("jit_f", "a", "b")


def test_a_late_tick_through_which_the_process_ran_is_gil_held_not_host_late():
    """A stall under a main thread that kept the GIL (``cpu_us`` near the tick's stretch) is not "the process did
    not run"; the same tick with no CPU time is."""
    ops = [("a", 0.0, 1.0), ("b", 1.5, 2.0)]
    tick = lambda cpu_us: [{"kind": "tick", "start": 1.45, "end": 1.45, "stats": {"late_us": 400000, "gap_us": 420000,
                                                                                  "cpu_us": cpu_us}}]
    reduce = lambda cpu_us: stall_reduce.reduce({"/device:TPU:0": {"ops": ops, "runs": []}}, tick(cpu_us), {}, (0.0, 2.0))
    held, paused = reduce(410000), reduce(20000)
    assert (held["stall_host_late_s"], held["stalls"][0]["gil_held_s"]) == (0.0, pytest.approx(0.4))
    assert (paused["stall_host_late_s"], paused["stalls"][0]["gil_held_s"]) == (pytest.approx(0.4), 0.0)
    assert held["host_tick_late_max_ms"] == paused["host_tick_late_max_ms"] == pytest.approx(400.0)
    assert (held["host_tick_late_max_paused"], paused["host_tick_late_max_paused"]) == (False, True)
    assert "GIL held 0.400 s" in stall_reduce.describe(held["stalls"][0])


# -- the manifest ---------------------------------------------------------------------------------


def test_the_manifest_checks_and_lists_this_modules_metrics_for_the_cells_that_read_them():
    import check_manifest

    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as fh:
        manifest = json.load(fh)
    assert check_manifest.check(manifest) == []
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert set(NUMBERS) <= set(by_name)
    # the five cells there were (the sixth reads them as ``q3n_*``) and PR 42's, which reads the accepted entries
    cells = ["c10_flagship.popeval", "c100_deep.popeval", "lfm2_24b_a2b_ep8.popeval", "deepseek_v2_lite_ep8.popeval",
             "mellum2_12b_a2p5b_ep8.popeval", "laguna_xs2_ep8.popeval", "keye_vl2_30b_a3b_ep8.popeval"]  # and PR 49's
    assert set(cells) <= {w["name"] for w in manifest["workloads"]}
    for name in NUMBERS:
        m = by_name[name]
        assert m["workloads"] == cells and m["better"] == "lower" and m["moves"] == "individuals_per_hour_per_chip", name
    assert {by_name[name]["layer"] for name in NUMBERS} == {"device", "host_runtime"}
