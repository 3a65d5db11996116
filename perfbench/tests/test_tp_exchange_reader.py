"""``tp_exchange_gbps`` on a hand-built two-chip trace and hand-built
program spans: the bytes the ``serve.decode`` spans count over the
collectives' device time inside them, averaged over the chips; nothing
where the spans carry no counter or the cell has one chip."""
from __future__ import annotations

import importlib.util
import types

import pytest

from conftest import BENCH
from lib import xtrace

import repro.obs.trace as trace_mod

#: run.t0 in perf_counter seconds, and the window's start on the trace
T0, LO = 2.0, 1_000.0
GATHER = "%all-gather.{} = bf16[32,1,11008]{{2,1,0}} all-gather(%copy.98)"


def read(ctx):
    spec = importlib.util.spec_from_file_location(
        "tp_exchange_gbps", BENCH / "metrics" / "tp_exchange_gbps.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(ctx)


def seconds(ns):
    return T0 + (ns - LO) * 1e-9


@pytest.fixture
def spans():
    """A process tracer that gives back the spans set on it (ns on the
    trace's clock, handed out in perf_counter seconds, with their
    attributes when asked)."""
    previous = trace_mod._DEFAULT

    class Tracer:
        spans: list = []

        def spans_in(self, t0, t1, args=False):
            return [(n, "serve", seconds(s), seconds(e))
                    + ((dict(a),) if args else ())
                    for n, s, e, a in self.spans]

    tracer = Tracer()
    trace_mod.set_default_tracer(tracer)
    yield tracer
    trace_mod.set_default_tracer(previous)


#: Two decode steps; chip 0 spends 1,000 + 2,000 ns in all-gathers inside
#: them and chip 1 1,000 + 1,000 ns. The all-gather at 65,000 lies outside
#: every step and the fusions are no collectives: neither counts.
OPS = {
    "/device:TPU:0": [(GATHER.format(1), 12_000.0, 13_000.0),
                      ("%fusion.1 = bf16[32,4096]", 13_000.0, 20_000.0),
                      (GATHER.format(2), 41_000.0, 43_000.0),
                      (GATHER.format(3), 65_000.0, 70_000.0)],
    "/device:TPU:1": [(GATHER.format(1), 12_500.0, 13_500.0),
                      (GATHER.format(2), 44_000.0, 45_000.0),
                      ("%fusion.2 = bf16[32,4096]", 46_000.0, 50_000.0)],
}
STEPS = [("serve.decode", 11_000.0, 31_000.0),
         ("serve.wait", 20_000.0, 29_000.0),
         ("serve.decode", 40_000.0, 60_000.0)]


def ctx(chips):
    devices = {d: {"XLA Ops": ops, "XLA Modules": []}
               for d, ops in OPS.items()}
    tr = xtrace.Trace(devices=devices,
                      host=[(xtrace.WINDOW_SPAN, LO, 101_000.0)])
    return types.SimpleNamespace(
        trace=tr, chips=chips,
        run=types.SimpleNamespace(kind="lm", t0=T0, t1=T0 + 1.0))


def test_reads_the_planted_rate(spans):
    counter = {"tp": 2, "collectives": 5, "exchange_bytes": 3_000}
    spans.spans = [(n, s, e, counter if n == "serve.decode" else {})
                   for n, s, e in STEPS]
    # 6,000 bytes over (3,000 + 2,000) / 2 ns of collectives: 2.4 GB/s
    assert read(ctx(2)) == pytest.approx(2.4)


def test_reads_nothing_without_the_counter(spans):
    """The spans of a program that does not count its exchange, as the
    parent's, and of one chip, whose counter is 0."""
    spans.spans = [(n, s, e, {}) for n, s, e in STEPS]
    assert read(ctx(2)) is None
    spans.spans = [(n, s, e, {"tp": 1, "collectives": 0,
                              "exchange_bytes": 0}) for n, s, e in STEPS]
    assert read(ctx(2)) is None


def test_reads_nothing_on_one_chip(spans):
    counter = {"tp": 2, "collectives": 5, "exchange_bytes": 3_000}
    spans.spans = [(n, s, e, counter) for n, s, e in STEPS]
    assert read(ctx(1)) is None
