"""From a profiler trace and host spans to device busy/idle time and its causes.

``reduce`` is pure arithmetic on intervals and is checked on
``fixtures/trace_fixture.json`` by ``check_manifest.py``.  ``read_xplane``
turns a ``jax.profiler`` trace into those intervals.

Definitions: *busy* on a device is the union of the intervals in which an
operation ran there; ``busy_s`` is its mean over the devices traced;
``window_s`` is the length of the traced window; a *gap* is a stretch of the
window in which nothing ran on a device.  A gap is named by the innermost
host span (the shortest one) that contains its midpoint, or ``no_span``.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, merged copy of ``intervals`` (start, end)."""
    out: List[List[float]] = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of ``window`` not covered by the merged ``busy`` intervals."""
    out, at = [], window[0]
    for s, e in busy:
        s, e = max(s, window[0]), min(e, window[1])
        if e <= s:
            continue
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if window[1] > at:
        out.append((at, window[1]))
    return out


def name_gap(gap: Interval, host_spans: Sequence[Tuple[str, float, float]]) -> str:
    mid = 0.5 * (gap[0] + gap[1])
    inside = [(e - s, name) for name, s, e in host_spans if s <= mid <= e]
    return min(inside)[1] if inside else "no_span"


def self_times(ops: Sequence[Tuple[str, float, float]]) -> List[Tuple[str, float]]:
    """(name, seconds) per op with the time of ops nested inside it taken out:
    a ``while`` loop's event spans the events of its body, and only the body's
    are work."""
    out: List[List[Any]] = []
    stack: List[Tuple[float, int]] = []  # (end, index into out) of the ops still open
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= min(e, stack[-1][0]) - s
        out.append([n, e - s])
        stack.append((e, len(out) - 1))
    return [(n, max(t, 0.0)) for n, t in out]


def reduce(device_ops: Dict[str, List[Tuple[str, float, float]]],
           host_spans: Sequence[Tuple[str, float, float]],
           window: Interval, top: int = 10) -> Dict[str, Any]:
    """``device_ops``: device -> [(op name, start, end)], seconds on one clock
    shared with ``host_spans`` [(name, start, end)] and ``window``."""
    busy_per_device, op_seconds, gap_seconds = [], {}, {}
    for ops in device_ops.values():
        clipped = [(n, max(s, window[0]), min(e, window[1])) for n, s, e in ops]
        clipped = [(n, s, e) for n, s, e in clipped if e > s]
        merged = union((s, e) for _, s, e in clipped)
        busy_per_device.append(sum(e - s for s, e in merged))
        for n, t in self_times(clipped):
            op_seconds[n] = op_seconds.get(n, 0.0) + t / len(device_ops)
        for g in gaps(merged, window):
            key = name_gap(g, host_spans)
            gap_seconds[key] = gap_seconds.get(key, 0.0) + (g[1] - g[0]) / len(device_ops)
    rank = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    window_s = window[1] - window[0]
    busy_s = sum(busy_per_device) / len(busy_per_device) if busy_per_device else 0.0
    return {"busy_s": busy_s, "window_s": window_s,
            "idle_share": 1.0 - busy_s / window_s if window_s > 0 else None,
            "device_ops": rank(op_seconds), "idle_gaps": rank(gap_seconds)}


# -- reading a jax.profiler trace ------------------------------------------------

# What a v5e trace holds (looked at by hand, PR 24): one plane per chip,
# "/device:TPU:<n>", with the lines "Steps", "XLA Modules" (one event per program
# run, named jit_<function>(<fingerprint>)), "XLA Ops" (every op, a while loop's
# event spanning its body's; an op's name is its whole HLO line), "Async XLA Ops"
# (copy-start/done pairs, overlapping the ops) and two empty ones; host planes
# carry a "python" line on the same clock, which starts at the trace.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
ANCHOR = "bench_anchor"


def short_name(hlo_line: str, module: str = "") -> str:
    """``%fusion.512 = bf16[...] fusion(...)`` -> ``jit_train_segment/%fusion.512``."""
    op = hlo_line.split(" = ", 1)[0].strip()[:80]
    module = re.sub(r"\(\d+\)$", "", module)
    return f"{module}/{op}" if module else op


def newest_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str, anchor_wall_s: float, device_plane=DEVICE_PLANE) -> Dict[str, Any]:
    """Device op intervals of ``path`` on the host's wall clock.

    The trace's clock starts at the trace; the harness opens a
    ``bench_anchor`` annotation at a known wall time, and its start in the
    trace gives the shift.  Returns the per-device op lists, an inventory of
    planes and lines (for a look by hand), and whether the anchor was found.
    """
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    inventory, anchor_ns, raw, modules = [], None, {}, {}
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            inventory.append({"plane": plane.name, "line": line.name, "events": len(events),
                              "first": [e.name for e in events[:3]]})
            if anchor_ns is None:
                anchor_ns = next((e.start_ns for e in events if e.name == ANCHOR), None)
            if device_plane.match(plane.name) and line.name in (OPS_LINE, MODULES_LINE):
                (raw if line.name == OPS_LINE else modules).setdefault(plane.name, []).extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns) for e in events)
    shift = anchor_wall_s - (anchor_ns or 0.0) / 1e9
    ops = {}
    for dev, evs in raw.items():
        runs = sorted(modules.get(dev, []), key=lambda m: m[1])
        starts = [m[1] for m in runs]
        named = []
        for n, s, e in evs:
            i = bisect.bisect_right(starts, s) - 1
            inside = runs[i][0] if i >= 0 and s < runs[i][2] else ""
            named.append((short_name(n, inside), s / 1e9 + shift, e / 1e9 + shift))
        ops[dev] = named
    return {"device_ops": ops, "inventory": inventory, "anchor_found": anchor_ns is not None}
