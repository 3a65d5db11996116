"""The program's own spans on the device trace's clock, and the device's
idle time under them.

The program records its spans on its process tracer while a profiler
session runs, and reads them back with ``spans_in`` in
``time.perf_counter`` seconds. The runners read ``perf_counter`` into
``run.t0`` and at once open the window annotation that the reduced trace
keeps, so one offset, ``trace.window()[0] - run.t0 * 1e9``, puts every span
on the trace's nanoseconds. A program whose tracer has no ``spans_in``
gives no spans, and the readers built on them read nothing.

Device idle time is time in the window that no ``XLA Ops`` event covers,
averaged over the cell's devices: the union ``readers.busy_window`` takes.
"""
from __future__ import annotations

import bisect

from . import readers, xtrace

__all__ = ["spans", "union", "Idle"]


def spans(ctx):
    """The program's spans finished inside the run's window, as
    ``(name, track, start_ns, end_ns)`` on the trace's clock; None when
    the program's tracer cannot read its spans back."""
    from repro.obs.trace import default_tracer

    read = getattr(default_tracer(), "spans_in", None)
    if read is None:
        return None
    offset = ctx.trace.window()[0] - ctx.run.t0 * 1e9
    return [(name, track, s * 1e9 + offset, e * 1e9 + offset)
            for name, track, s, e in read(ctx.run.t0, ctx.run.t1)]


def union(intervals) -> list:
    """Sorted disjoint ``(start, end)`` covering the given intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


class Idle:
    """Device idle ns in any interval of the window, averaged over the
    cell's devices. Each device's busy time is its ``XLA Ops`` union,
    kept sorted with the busy time before each piece, so that an
    interval costs two binary searches a device."""

    def __init__(self, trace, chips: int):
        self.lo, self.hi = trace.window()
        self._devices = []
        for d in readers.used_devices(trace, chips):
            busy = union((s, e) for _, s, e in xtrace.clip(
                trace.line(d, readers.OPS), self.lo, self.hi))
            before = [0.0]
            for s, e in busy:
                before.append(before[-1] + e - s)
            self._devices.append(([s for s, _ in busy], busy, before))

    @staticmethod
    def _busy_until(dev, t: float) -> float:
        starts, busy, before = dev
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        s, e = busy[i]
        return before[i] + min(t, e) - s

    def __call__(self, start: float, end: float) -> float:
        start, end = max(start, self.lo), min(end, self.hi)
        if end <= start or not self._devices:
            return 0.0
        busy = sum(self._busy_until(d, end) - self._busy_until(d, start)
                   for d in self._devices) / len(self._devices)
        return (end - start) - busy

