"""From a profiler trace to the device's stalls and what the host did in each.

A **stall** is one gap of at least ``STALL_S`` in which no op ran on a device:
1.4 times the longest ordinary gap PR 38's study found (0.1118 s, the stretch
between the window's opening and the first call's first program in the
Mellum2 cell; 25 whole traced runs, PERF.md section 6), and under the smallest
stall on record, 0.19 s (PERF.md section 7).  Every time here is on the trace's own clock: the ops, the
program runs, the program's ``gentun/<kind>`` annotations and the host
sampler's ``gentun/tick`` annotations (``gentun_tpu/telemetry/sampler.py``) all
lie in one ``.xplane.pb``.  The reader is family-neutral and takes no rules.

What the host side of a v5e trace holds (looked at by hand, PR 38;
``trace_reduce.py`` has the device planes): one plane ``/host:CPU`` whose lines
are threads, ``<thread name>/<tid>``.  ``main`` is the Python main thread's side
of the runtime (``AllocateRawBuffer``, ``Wait for donation holds``, ``Acquire
semaphore``, ``CommonPjRtClient::CreateOutputs``); ``tfrt-non-blocking-queue``
issues programs to the chip (``DoEnqueueProgram``, ``EnqueueContinuationProgram``,
``tpu::System::Execute=>IssueSequencedEvent``); ``futex-default-SDomainT`` hears
of their end (``tpu::System::Execute=>Done``, ``ReadSyncFlag``,
``CompleteCallbacks``, ``Release semaphore``); several ``pjrt-tpu-tasks`` move
data (``H2D Dispatch``, ``Linearize``, ``tpu::System::TransferToDevice``, ``D2H
Dispatch``); ``EventFDAsyncWorker`` ends the transfers; and a ``python`` line a
Python thread: the tracer's frames (``$file:line function``) with that thread's
annotations among them, the main thread's ``gentun/<kind>`` and, on a line of
its own, the sampler's ``gentun/tick``.  ``/host:metadata`` holds the programs'
HLO (``scope_reduce.py``); ``Task Environment``, ``#Chip0 Host Interface``,
``#Chip0 Misc`` and ``/device:CUSTOM:Megascale Trace`` hold no line.  The machine
runs the program under gVisor (the file is ``runsc.xplane.pb``): its
``/proc/stat`` counts nothing, it has no ``/proc/pressure``, ``ru_nivcsw`` stays
0 and CPU times move in steps of 10 ms, so of the ticks' stats ``late_us`` and
``cpu_us`` are what this machine fills; the machine's CPU, steal and pressure
read 0 there and print as "not counted".

For each stall the reduction says

- *where*: **between programs** (no run open on "XLA Modules" of that device:
  the base names of the run before and the run after) or **in a program** (the
  run it lies in, the op before and the op after the gap);
- its *name*: the innermost ``gentun/<kind>`` annotation over its midpoint, and
  beside it what ``trace_reduce.name_gap`` says from the span records through
  the anchor shift, so that a disagreement of the two clocks is on the page;
- **host late**: the seconds of it under ticks that woke ``LATE_S`` or more
  late (a tick that woke ``late`` late at ``t`` covers ``t - late .. t``) while
  the process's CPU time (``cpu_us``, all its threads) moved by less than half
  the lateness: no thread of this process ran.  A late tick through which the
  process did burn CPU is **GIL held**, counted apart and in no metric: the
  sampler's thread waited for another thread of the program (a compile,
  ``prepare``'s Python), which the `python` line's frames name;
- over its ticks, each tick's deltas by the share of the tick's stretch that
  lies in the stall: the process's CPU seconds, the machine's other CPU
  seconds (none where the kernel counts no machine CPU), steal, involuntary
  switches, major faults, pressure;
- the events of the other host lines (the runtime's threads, the Python
  tracer's frames) that overlap it most, as ``line: event``.

Busy time is the union of the *leaf* ops: a ``while``, a ``conditional`` or a
``call`` spans its body's events and would hide a stall inside a loop (the
Genetic-CNN's train program is one scan), so an op that holds other ops does
not count.  The slivers that leaves leave between them are microseconds.

``reduce`` is pure arithmetic, checked on ``fixtures/stall_fixture.json``
(``tests/test_host_sampler.py``); ``read`` turns a trace file into its inputs;
``table`` is what the four readers under ``layer_metrics/`` call: it finds the
newest trace of the run's cell, prints ``info stall`` lines once, writes
``stalls.json`` beside ``inventory.json`` and rides on ``run``.
"""

from __future__ import annotations

import json
import os
import traceback
from typing import Any, Dict, List, Optional, Sequence, Tuple

import scope_reduce
import trace_reduce

STALL_S = 0.157  # one gap this long with no op on a device is a stall: 1.4 x 0.1118 s, the longest ordinary gap seen
LATE_S = 0.050  # a tick that woke this late is a late tick (telemetry/sampler.py): host late or GIL held, by its cpu_us
TICK = "tick"
LONGEST = 5
HOST_EVENTS = 5
# what ``reduce`` returns for the four readers under ``layer_metrics/``
METRICS = ("device_stall_s", "stall_between_programs_s", "stall_host_late_s", "host_tick_late_max_ms")

Event = Tuple[str, float, float]


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The ops that hold no other op."""
    out: List[Event] = []
    ordered = sorted(ops, key=lambda o: (o[1], -o[2]))
    for i, op in enumerate(ordered):
        if i + 1 < len(ordered) and ordered[i + 1][1] < op[2] and ordered[i + 1][2] <= op[2]:
            continue
        out.append(op)
    return out


def overlap(a0: float, a1: float, b0: float, b1: float) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


def covered(intervals: Sequence[Tuple[float, float]], lo: float, hi: float) -> float:
    return sum(overlap(s, e, lo, hi) for s, e in trace_reduce.union(intervals))


def _around(events: Sequence[Event], lo: float, hi: float) -> Tuple[int, int]:
    """Indices of the last event that ended by the midpoint of ``lo .. hi`` and of the first that starts
    after it (-1: none): by the midpoint, because a run opens a few microseconds before its first op."""
    mid = 0.5 * (lo + hi)
    before = max((i for i, e in enumerate(events) if e[2] <= mid), key=lambda i: events[i][2], default=-1)
    after = min((i for i, e in enumerate(events) if e[1] >= mid), key=lambda i: events[i][1], default=-1)
    return before, after


def _name(events: Sequence[Event], index: int) -> str:
    return events[index][0] if index >= 0 else "-"


def _paused(tick: Dict[str, Any]) -> bool:
    """Through this tick's lateness no thread of the process ran (else another thread held the GIL)."""
    return float(tick["stats"].get("cpu_us", 0)) < 0.5 * float(tick["stats"].get("late_us", 0))


def _tick_sums(ticks: Sequence[Dict[str, Any]], lo: float, hi: float) -> Dict[str, Any]:
    """Each tick's deltas, by the share of its stretch (``gap_us`` back from its wake) inside ``lo .. hi``.
    ``other_cpu_s`` is None where the kernel counted no machine CPU at all."""
    out = {"ticks": 0, "cpu_s": 0.0, "mach_busy_s": 0.0, "steal_s": 0.0, "nivcsw": 0.0, "majflt": 0.0,
           "psi_cpu_s": 0.0, "psi_mem_s": 0.0, "psi_io_s": 0.0}
    for t in ticks:
        stats, length = t["stats"], max(float(t["stats"].get("gap_us", 0)) / 1e6, 1e-9)
        share = overlap(t["start"] - length, t["start"], lo, hi) / length
        if share <= 0:
            continue
        get = lambda key: float(stats.get(key, 0)) * share
        out["ticks"] += 1
        out["cpu_s"] += get("cpu_us") / 1e6
        out["mach_busy_s"] += get("mach_busy_us") / 1e6
        out["steal_s"] += get("mach_steal_us") / 1e6
        out["nivcsw"] += get("nivcsw")
        out["majflt"] += get("majflt")
        for name in ("cpu", "mem", "io"):
            out[f"psi_{name}_s"] += get(f"psi_{name}_us") / 1e6
    busy = out.pop("mach_busy_s")
    out["other_cpu_s"] = max(0.0, busy - out["cpu_s"]) if busy > 0 else None
    return out


def _host_events(host_lines: Dict[str, Sequence[Event]], lo: float, hi: float) -> List[str]:
    """``line: event`` of the host events that overlap ``lo .. hi`` most: the
    innermost first among equals, at most two a line."""
    found = []
    for line, events in host_lines.items():
        best = sorted(((overlap(s, e, lo, hi), e - s, n) for n, s, e in events if s < hi and e > lo),
                      key=lambda x: (-x[0], x[1]))[:2]
        found += [(ov, dur, f"{line}: {n[:100]} ({ov:.3f} s of its {dur:.3f})") for ov, dur, n in best if ov > 0]
    return [text for _, _, text in sorted(found, key=lambda x: (-x[0], x[1]))[:HOST_EVENTS]]


def reduce(devices: Dict[str, Dict[str, Sequence[Event]]], annotations: Sequence[Dict[str, Any]],
           host_lines: Dict[str, Sequence[Event]], stretch: Tuple[float, float],
           host_spans: Sequence[Event] = (), shift: Optional[float] = None) -> Dict[str, Any]:
    """``devices``: plane -> {"ops": [(HLO line, start, end)], "runs": [(module, start, end)]};
    ``annotations``: [{"kind", "start", "end", "stats"}], the ticks among them;
    ``host_lines``: "plane line" -> [(event, start, end)]; ``stretch``: the traced
    stretch; all in seconds on the trace's clock.  ``host_spans`` are the span
    records on the wall clock and ``shift`` takes the trace's clock to it."""
    ticks = [a for a in annotations if a["kind"] == TICK and stretch[0] <= a["start"] <= stretch[1]]
    phases = [(a["kind"], a["start"], a["end"]) for a in annotations if a["kind"] != TICK]
    late = [(t["start"] - float(t["stats"].get("late_us", 0)) / 1e6, t["start"], _paused(t)) for t in ticks
            if float(t["stats"].get("late_us", 0)) >= LATE_S * 1e6]
    stalls: List[Dict[str, Any]] = []
    longest: List[Dict[str, Any]] = []
    for device in sorted(devices):
        ops = [o for o in leaves(devices[device].get("ops", ())) if o[2] > stretch[0] and o[1] < stretch[1]]
        if not ops:  # a device that ran nothing in the stretch has no gap between ops to name
            continue
        runs = sorted(devices[device].get("runs", ()), key=lambda r: r[1])
        gaps = trace_reduce.gaps(trace_reduce.union((s, e) for _, s, e in ops), stretch)
        named = lambda lo, hi: {"device": device, "start_s": lo - stretch[0], "length_s": hi - lo,
                                "span": trace_reduce.name_gap((lo, hi), phases)}
        longest += [named(lo, hi) for lo, hi in sorted(gaps, key=lambda g: g[0] - g[1])[:LONGEST]]
        for lo, hi in gaps:
            length = hi - lo
            if length < STALL_S:
                continue
            mid = 0.5 * (lo + hi)
            gap = named(lo, hi)
            in_run = covered([(s, e) for _, s, e in runs], lo, hi)
            inside = next((r for r in runs if r[1] <= mid <= r[2]), None)
            if inside is not None:
                body = [o for o in ops if inside[1] <= o[1] and o[2] <= inside[2]]
                before, after = _around(body, lo, hi)
                gap.update(where="in_program", program=scope_reduce.base_name(inside[0]),
                           before=trace_reduce.short_name(_name(body, before)),
                           after=trace_reduce.short_name(_name(body, after)))
            else:
                before, after = _around(runs, lo, hi)
                gap.update(where="between_programs", before=scope_reduce.base_name(_name(runs, before)),
                           after=scope_reduce.base_name(_name(runs, after)))
            gap["between_programs_s"] = length - in_run
            gap["span_by_anchor"] = (trace_reduce.name_gap((lo + shift, hi + shift), host_spans)
                                     if shift is not None else None)
            gap["host_late_s"] = covered([(s, e) for s, e, paused in late if paused], lo, hi)
            gap["gil_held_s"] = covered([(s, e) for s, e, paused in late if not paused], lo, hi)
            gap.update(_tick_sums(ticks, lo, hi))
            gap["host_events"] = _host_events(host_lines, lo, hi)
            stalls.append(gap)
    n = max(1, sum(1 for d in devices.values() if d.get("ops")))
    whole = _tick_sums(ticks, *stretch)
    latest = max(ticks, key=lambda t: float(t["stats"].get("late_us", 0)), default=None)
    other = whole["other_cpu_s"]
    return {
        "stretch_s": stretch[1] - stretch[0], "devices": len(devices), "ticks": len(ticks),
        "device_stall_s": sum(s["length_s"] for s in stalls) / n,
        "stall_between_programs_s": sum(s["between_programs_s"] for s in stalls) / n,
        "stall_host_late_s": sum(s["host_late_s"] for s in stalls) / n if ticks else None,
        "host_tick_late_max_ms": float(latest["stats"].get("late_us", 0)) / 1e3 if ticks else None,
        "host_tick_late_max_at_s": latest["start"] - stretch[0] if ticks else None,
        "host_tick_late_max_paused": _paused(latest) if ticks else None,
        "host_cpu_other_share": 100.0 * other / (other + whole["cpu_s"]) if other is not None else None,
        "stalls": stalls,
        "longest_gaps": sorted(longest, key=lambda g: -g["length_s"])[:LONGEST],
    }


# -- reading a trace ------------------------------------------------------------------------


def read(path: str) -> Dict[str, Any]:
    """The inputs of ``reduce`` from one trace file, seconds on the trace's clock."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    annotations: List[Dict[str, Any]] = []
    host_lines: Dict[str, List[Event]] = {}
    anchor = None
    keys = {trace_reduce.OPS_LINE: "ops", trace_reduce.MODULES_LINE: "runs"}
    for plane in data.planes:
        on_device = bool(trace_reduce.DEVICE_PLANE.match(plane.name))
        if not on_device and not plane.name.startswith("/host:"):
            continue
        for index, line in enumerate(plane.lines):
            if on_device:
                if line.name in keys:
                    devices.setdefault(plane.name, {"ops": [], "runs": []})[keys[line.name]] += [
                        (e.name, e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9) for e in line.events]
                continue
            others: List[Event] = []
            ticking = False
            for e in line.events:
                start, end = e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9
                if e.name.startswith(scope_reduce.ANNOTATION):
                    kind = e.name[len(scope_reduce.ANNOTATION):]
                    ticking = ticking or kind == TICK
                    annotations.append({"kind": kind, "start": start, "end": end, "stats": dict(e.stats)})
                elif e.name == trace_reduce.ANCHOR:
                    anchor = start if anchor is None else anchor
                else:
                    others.append((e.name, start, end))
            if others and not ticking:  # the sampler's own thread says nothing of the others
                host_lines[f"{plane.name} {line.name}#{index}"] = others
    return {"path": path, "devices": devices, "annotations": sorted(annotations, key=lambda a: a["start"]),
            "host_lines": host_lines, "anchor": anchor}


# -- what the layer_metrics readers call -------------------------------------------------------


def _stretch(trace: Dict[str, Any], run: Dict[str, Any]) -> Optional[Tuple[float, float]]:
    """The traced stretch on the trace's clock: from the harness's anchor for
    as long as the harness says it traced; without either, the device's ops."""
    ops = [o for d in trace["devices"].values() for o in d["ops"]]
    if not ops:
        return None
    if trace["anchor"] is not None and run.get("trace"):
        return trace["anchor"], trace["anchor"] + run["trace"]["window_s"]
    return min(o[1] for o in ops), max(o[2] for o in ops)


def _counted(seconds: Optional[float]) -> str:
    return "not counted" if seconds is None else f"{seconds:.3f} s"


def describe(stall: Dict[str, Any]) -> str:
    """One stall on one line."""
    if stall["where"] == "in_program":
        where = f"in program {stall['program']} after {stall['before']} before {stall['after']}"
    else:
        where = f"between programs {stall['before']} -> {stall['after']}"
    return (f"{stall['device']} at {stall['start_s']:.3f} s for {stall['length_s']:.3f} s, {where} "
            f"({stall['between_programs_s']:.3f} s of it with no run open); span {stall['span']} "
            f"(by the anchor shift: {stall['span_by_anchor']}); host late {stall['host_late_s']:.3f} s "
            f"(the process did not run), GIL held {stall['gil_held_s']:.3f} s (late ticks, the process ran); "
            f"over its {stall['ticks']} ticks: process cpu {stall['cpu_s']:.3f} s, other cpu {_counted(stall['other_cpu_s'])}, "
            f"steal {stall['steal_s']:.3f} s, nivcsw {stall['nivcsw']:.1f}, majflt {stall['majflt']:.1f}, "
            f"pressure cpu/mem/io {stall['psi_cpu_s']:.3f}/{stall['psi_mem_s']:.3f}/{stall['psi_io_s']:.3f} s")


def table(run: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The stalls of the newest trace of the run's cell; None if there is no
    trace or no device plane in it (a CPU rehearsal).  The first call prints
    and writes ``stalls.json``; the result rides on ``run``."""
    if "stall_table" in run:
        return run["stall_table"]
    run["stall_table"] = got = None
    path = scope_reduce.newest_trace(run["cell"]["name"])
    try:
        trace = read(path) if path else None
        stretch = _stretch(trace, run) if trace else None
        if stretch is not None:
            spans = [(r["kind"], r["t_wall"], r["t_wall"] + r["dur_s"]) for r in run.get("records", ())
                     if r.get("type") == "span"]
            spans += [("unit", u["t_wall"], u["t_wall"] + u["wall_s"]) for u in run.get("units", ())]
            shift = run["window"][0] - trace["anchor"] if trace["anchor"] is not None and run.get("window") else None
            run["stall_table"] = got = reduce(trace["devices"], trace["annotations"], trace["host_lines"], stretch,
                                              spans, shift)
    except Exception:  # a reader that cannot read leaves its metrics out; it does not end the run
        traceback.print_exc()
    if got is None:
        return None
    if got["ticks"]:
        ran = "the process did not run" if got["host_tick_late_max_paused"] else "GIL held: the process ran"
        host = (f"host late {got['stall_host_late_s']:.4f} s, latest tick {got['host_tick_late_max_ms']:.2f} ms at "
                f"{got['host_tick_late_max_at_s']:.3f} s ({ran} through it), other processes' share of the machine's "
                f"busy CPU {'not counted' if got['host_cpu_other_share'] is None else '%.1f %%' % got['host_cpu_other_share']}")
    else:
        host = "no tick: a program without the host sampler"
    print(f"info stall trace {os.path.relpath(path, scope_reduce.HERE)}: {got['devices']} device(s), "
          f"{got['stretch_s']:.3f} s traced, {got['ticks']} ticks on {len(trace['host_lines'])} other host lines; "
          f"{len(got['stalls'])} stall(s) of {STALL_S} s or more: {got['device_stall_s']:.4f} s, "
          f"{got['stall_between_programs_s']:.4f} s between programs; {host}")
    for g in got["longest_gaps"]:
        print(f"info stall longest_gap {g['device']} at {g['start_s']:.3f} s for {g['length_s']:.4f} s in {g['span']}")
    for s in got["stalls"]:
        print("info stall", describe(s))
        for text in s["host_events"]:
            print("info stall   host", text)
    print("info stall host lines:", ", ".join(f"{line} ({len(events)})" for line, events in sorted(trace["host_lines"].items())))
    with open(os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(path)))), "stalls.json"),
              "w", encoding="utf-8") as fh:
        json.dump({"path": path, **got}, fh, indent=1, default=str)
    return got
