"""Reduction of a JAX profiler trace to the numbers the readers need.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes. It holds one
plane per device (``/device:TPU:<n>``), each with a line of XLA module
executions (``XLA Modules``) and a line of the operations inside them
(``XLA Ops``), and a host plane whose thread lines carry the harness's
``TraceAnnotation`` spans. All times are in nanoseconds on one clock.

The reduction keeps plain lists of ``(name, start_ns, end_ns)`` so that the
tests can build a trace by hand and the readers never see the protobuf.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

__all__ = ["Trace", "load", "find_xplane", "union_ns", "clip", "within",
           "WINDOW_SPAN"]

#: The host span the harness opens around the traced window.
WINDOW_SPAN = "bench.window"

@dataclasses.dataclass
class Trace:
    """``devices``: device plane name -> line name -> events;
    ``host``: the host's named spans (every thread), as events."""

    devices: dict
    host: list

    @property
    def device_names(self) -> list:
        return sorted(self.devices)

    def window(self) -> tuple:
        """(start_ns, end_ns) of the harness's window span."""
        spans = [e for e in self.host if e[0] == WINDOW_SPAN]
        if not spans:
            raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
        return min(e[1] for e in spans), max(e[2] for e in spans)

    def line(self, device: str, name: str) -> list:
        return self.devices[device].get(name, [])

    def host_spans(self, name: str) -> list:
        return [e for e in self.host if e[0] == name]

    @classmethod
    def from_json(cls, obj: dict) -> "Trace":
        """A trace stored as ``{"devices": {plane: {line: [[name, start,
        end], ...]}}, "host": [[name, start, end], ...]}``."""
        return cls(devices={d: {ln: [tuple(e) for e in evs]
                                for ln, evs in lines.items()}
                            for d, lines in obj["devices"].items()},
                   host=[tuple(e) for e in obj["host"]])


DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
#: Device lines the readers use; the others (steps, sparse cores) are
#: dropped so the kept trace stays small.
DEVICE_LINES = ("XLA Modules", "XLA Ops")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` file (or the directory the profiler wrote)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in DEVICE_LINES:
                    lines[line.name] = [
                        (e.name, float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns))
                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        host.append((e.name, float(e.start_ns),
                                     float(e.start_ns) + float(e.duration_ns)))
    return Trace(devices=devices, host=host)


def clip(events, lo: float, hi: float) -> list:
    """Events cut to [lo, hi]; those wholly outside are dropped."""
    out = []
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((name, s, e))
    return out


def union_ns(events) -> float:
    """Length of the union of the events' intervals."""
    total = 0.0
    end = float("-inf")
    for _, s, e in sorted(events, key=lambda ev: ev[1]):
        if s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def within(events, spans) -> list:
    """Events whose midpoint lies inside one of ``spans``, which do not
    overlap each other (the executions of one module on one device)."""
    spans = sorted((s, e) for _, s, e in spans)
    starts = [s for s, _ in spans]
    out = []
    for ev in events:
        mid = 0.5 * (ev[1] + ev[2])
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid <= spans[i][1]:
            out.append(ev)
    return out
