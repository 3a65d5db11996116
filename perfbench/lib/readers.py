"""What the per-layer readers share: device busy time, the executions of
one jitted step, the operations inside them, and the run's breakdown.

The readers identify a jitted step by how often it ran, not by its name:
the harness counts its own calls into the step (host spans ``bench.*``),
and the step is the XLA module that ran exactly that often and took the
most device time. The names the chip prints today (``jit__lambda`` for the
prefill, ``jit_decode_step`` for the decode step) are lambdas and
functions of the system's, and may change.
"""
from __future__ import annotations

import re

from . import xtrace

__all__ = ["used_devices", "busy_window", "step_modules", "ops_matching",
           "calls_in_window", "breakdown"]

MODULES, OPS = "XLA Modules", "XLA Ops"


def used_devices(trace, chips: int) -> list:
    """The devices of the cell (the trace can name devices it never used)."""
    names = sorted(trace.device_names,
                   key=lambda n: int(n.rsplit(":", 1)[1]))
    return names[:chips]


def busy_window(trace, chips: int) -> tuple:
    """(busy seconds averaged over the cell's devices, window seconds)."""
    lo, hi = trace.window()
    devs = used_devices(trace, chips)
    busy = sum(xtrace.union_ns(xtrace.clip(trace.line(d, OPS), lo, hi))
               for d in devs) / max(len(devs), 1)
    return busy * 1e-9, (hi - lo) * 1e-9


def calls_in_window(trace, span: str) -> int:
    lo, hi = trace.window()
    return sum(1 for _, s, e in trace.host_spans(span) if s >= lo and e <= hi)


def step_modules(trace, device: str, n_calls: int) -> list:
    """Executions of the module that ran ``n_calls`` times in the window
    and took the most time there; [] when none ran that often."""
    lo, hi = trace.window()
    by_name: dict = {}
    for ev in trace.line(device, MODULES):
        if ev[1] >= lo and ev[2] <= hi:
            by_name.setdefault(ev[0], []).append(ev)
    fits = [evs for evs in by_name.values() if len(evs) == n_calls]
    if not fits or n_calls == 0:
        return []
    return max(fits, key=lambda evs: sum(e - s for _, s, e in evs))


def ops_matching(trace, device: str, pattern: str, inside=None) -> list:
    """Operations whose text matches ``pattern``, inside ``inside`` (a
    list of events) when given, else inside the window."""
    rx = re.compile(pattern)
    if inside is None:
        lo, hi = trace.window()
        inside = [("window", lo, hi)]
    return xtrace.within([ev for ev in trace.line(device, OPS)
                          if rx.search(ev[0])], inside)


def seconds(events) -> float:
    return sum(e - s for _, s, e in events) * 1e-9


def _short(op: str) -> str:
    """``%fusion.131 = bf16[32,512]{...} fusion(...)`` -> ``fusion.131
    bf16[32,512]``: the op and its result shape."""
    name, _, rest = op.partition(" = ")
    shape = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return name.lstrip("%") + (" " + shape.group(1) if shape else "")


def breakdown(trace, chips: int, top: int = 10) -> dict:
    """The device operations that took most time on the first device (a
    loop's time counts in its body's operations, not in the loop), and
    the longest idle gaps, each named by the innermost host span open at
    its middle."""
    dev = used_devices(trace, chips)[0]
    lo, hi = trace.window()
    ops = xtrace.clip(trace.line(dev, OPS), lo, hi)
    tot: dict = {}
    for name, s, e in ops:
        if name.startswith("%while"):
            continue
        key = _short(name)
        tot[key] = tot.get(key, 0.0) + (e - s) * 1e-9
    device_ops = sorted(tot.items(), key=lambda kv: -kv[1])[:top]

    gaps = []
    end = lo
    for _, s, e in sorted(ops, key=lambda ev: ev[1]):
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if hi > end:
        gaps.append((end, hi))
    spans = [ev for ev in trace.host if ev[0] != xtrace.WINDOW_SPAN]
    named = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = 0.5 * (s + e)
        open_ = [ev for ev in spans if ev[1] <= mid <= ev[2]]
        what = (min(open_, key=lambda ev: ev[2] - ev[1])[0] if open_
                else "outside the harness's spans")
        named.append([what, (e - s) * 1e-9])
    return {"device_ops": [[k, v] for k, v in device_ops],
            "idle_gaps": named}
