"""Host sampler: a thread that keeps time while the main thread waits.

A fenced span (``spans._Span.fence``) can say that the host waited for the
device, not why the device had nothing to run.  This thread wakes every
``PERIOD_S`` and notes what the process and the machine did since its last
wake.  Each wake is one ``gentun/tick`` profiler annotation whose scalar
stats are the deltas, so in a profiled run the ticks lie in the profiler's
own file, on its clock, beside the ``gentun/<kind>`` phase annotations
(``models/evaluation.py``) and the device's ops:

- ``late_us``: woke this long after the intended time;
- ``gap_us``: time since the previous wake (the stretch the deltas cover);
- ``cpu_us``: ``time.process_time()``, this process, all threads;
- ``nivcsw``, ``majflt``: involuntary context switches and major faults
  (``getrusage(RUSAGE_SELF)``);
- ``mach_busy_us``, ``mach_steal_us``: the first line of ``/proc/stat``,
  every field but ``idle`` and ``iowait`` (``guest`` is inside ``user``), and
  ``steal`` apart; a ``/proc/stat`` that counts nothing when the sampler
  starts (gVisor, which the chip machines run: every field reads 0) is not
  read again, as an absent file is not, and both stay 0: a read lets go of the
  GIL, and taking it back from a main thread in Python is a second forced
  switch a tick;
- ``psi_cpu_us``, ``psi_mem_us``, ``psi_io_us``: ``some total=`` of
  ``/proc/pressure/*``, where the kernel has them.

A wake ``LATE_S`` or more late is also a record, the only one this module
sends to the sinks: the ``host_hiccup`` event (``late_s`` and the same deltas
in seconds and counts), the counter ``host_hiccups_total`` and the histogram
``host_hiccup_seconds``.  A late wake has two causes, and ``cpu_s`` (all
threads) beside ``late_s`` tells them apart: near 0, no thread of the process
ran (it was stopped, or starved of a core); near ``gap_s``, another thread ran
and kept the GIL from this one (a compile, ``prepare``'s Python).  While the
main thread waits in ``block_until_ready`` the GIL is free.

Lifecycle: ``evaluation_prelude`` calls :func:`ensure_started` when
``spans.enabled()``; ``spans.disable()`` stops and joins the thread.  With
telemetry off this module is never imported.
"""

from __future__ import annotations

import os
import resource
import threading
import time
from typing import Callable, List, Optional

from . import spans
from .registry import get_registry

__all__ = ["PERIOD_S", "LATE_S", "HostSampler", "ensure_started"]

# 20 wakes a second.  A wake is ~0.1 ms of Python, but one that meets the main
# thread in Python takes the GIL from it by a forced switch, which under the
# chip machines' gVisor costs the main thread about a millisecond.  At 20 ms
# that showed in the host-side metrics of traced runs (PERF.md section 6, PR 38).
PERIOD_S = 0.050
LATE_S = 0.050  # a wake this late is a hiccup: one period

_PRESSURE = ("cpu", "memory", "io")


def _open(path: str) -> Optional[int]:
    try:
        return os.open(path, os.O_RDONLY)
    except OSError:
        return None


def _annotation(name: str, **stats):
    import jax  # the evaluators that start the sampler have jax loaded

    return jax.profiler.TraceAnnotation(name, **stats)


class HostSampler:
    """One sampling thread.  ``clock``, ``sleep`` and ``proc`` are handed in
    by tests; ``sleep(seconds)`` returns true when the sampler should end."""

    def __init__(self, period_s: float = PERIOD_S, clock: Callable[[], float] = time.monotonic,
                 sleep: Optional[Callable[[float], bool]] = None, proc: str = "/proc") -> None:
        self.period_s = period_s
        self._clock = clock
        self._stopped = threading.Event()
        self._sleep = sleep or self._stopped.wait
        self._tick_us = 1_000_000 // os.sysconf("SC_CLK_TCK")
        self._thread: Optional[threading.Thread] = None
        self.ticks = 0
        # The files stay open for the sampler's life: a tick is a few calls,
        # which is also what the profiler's Python tracer then has to record.
        self._stat_fd = _open(os.path.join(proc, "stat"))
        if self._stat_fd is not None and not any(self._stat_fields()):
            os.close(self._stat_fd)
            self._stat_fd = None
        self._pressure_fds = [(name, fd) for name, fd in
                              ((name, _open(os.path.join(proc, "pressure", name))) for name in _PRESSURE) if fd is not None]
        self.names = ("cpu_us", "nivcsw", "majflt", "mach_busy_us", "mach_steal_us",
                      *(f"psi_{name[:3]}_us" for name, _ in self._pressure_fds))
        self._due = self._woke = clock()
        self._last = self._counters()

    def _stat_fields(self) -> List[int]:
        """``cpu user nice system idle iowait irq softirq steal ...`` of ``/proc/stat``, eight fields."""
        if self._stat_fd is None:
            return [0] * 8
        try:
            fields = [int(v) for v in os.pread(self._stat_fd, 256, 0).split(b"\n", 1)[0].split()[1:]]
        except (OSError, ValueError):
            fields = []
        return (fields + [0] * 8)[:8]

    def _counters(self) -> List[int]:
        """Cumulative readings in the order of ``names``: microseconds and counts."""
        usage = resource.getrusage(resource.RUSAGE_SELF)
        fields = self._stat_fields()
        out = [time.process_time_ns() // 1000, usage.ru_nivcsw, usage.ru_majflt,
               (sum(fields[:3]) + sum(fields[5:8])) * self._tick_us, fields[7] * self._tick_us]
        for _, fd in self._pressure_fds:
            try:  # some avg10=0.00 avg60=0.00 avg300=0.00 total=<microseconds>
                out.append(int(os.pread(fd, 128, 0).split(b"\n", 1)[0].rsplit(b"=", 1)[1]))
            except (OSError, ValueError, IndexError):
                out.append(0)
        return out

    def step(self) -> bool:
        """Sleep until the next intended wake, then tick; true to go on."""
        self._due += self.period_s
        if self._sleep(max(0.0, self._due - self._clock())):
            return False
        now = self._clock()
        late_s, gap_s = max(0.0, now - self._due), now - self._woke
        seen = self._counters()
        stats = dict(zip(self.names, (max(0, new - old) for new, old in zip(seen, self._last))))
        self._woke, self._last = now, seen
        if late_s >= self.period_s:
            self._due = now  # a long pause is one late tick, not a burst of them
        with _annotation("gentun/tick", late_us=int(late_s * 1e6), gap_us=int(gap_s * 1e6), **stats):
            self.ticks += 1
            if late_s >= LATE_S:
                reg = get_registry()
                reg.counter("host_hiccups_total").inc()
                reg.histogram("host_hiccup_seconds").observe(late_s)
                data = {"late_s": late_s, "gap_s": gap_s}
                for key, value in stats.items():  # seconds in the record, as its other times are
                    if key.endswith("_us"):
                        key, value = key[:-3] + "_s", value / 1e6
                    data[key] = value
                spans.record_event("host_hiccup", data)
        return True

    def _run(self) -> None:
        try:
            while self.step():
                pass
        finally:
            self.close()

    def close(self) -> None:
        for fd in [self._stat_fd] + [fd for _, fd in self._pressure_fds]:
            if fd is not None:
                os.close(fd)
        self._stat_fd, self._pressure_fds = None, []

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, name="gentun-host-sampler", daemon=True)
        self._thread.start()

    def alive(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def stop(self) -> None:
        """End the thread and wait for it (``spans.disable``)."""
        self._stopped.set()
        if self._thread is not None and self._thread is not threading.current_thread():
            self._thread.join()


def ensure_started() -> Optional[HostSampler]:
    """The process's sampler, started on the first traced evaluation; none
    if ``spans.disable()`` came between the caller's look at the switch and here."""
    with spans._sampler_lock:
        sampler = spans._sampler
        if spans.enabled() and (sampler is None or not sampler.alive()):
            sampler = spans._sampler = HostSampler()
            sampler.start()
        return sampler
