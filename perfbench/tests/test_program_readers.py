"""The readers of the program's spans, on a hand-built trace and hand-built
spans: the anchor that puts the spans on the trace's clock, device idle
time under them, and the three metrics, each against the value worked
out by hand."""
from __future__ import annotations

import importlib.util
import types

import pytest

from conftest import BENCH
from lib import program, xtrace

import repro.obs.trace as trace_mod

#: run.t0 in perf_counter seconds, and the window's start on the trace
T0, LO = 2.0, 1_000.0
KERNEL_OP = "%mc_moments.1 = f32[8,128]{1,0} custom-call(s32[8])"


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def seconds(ns):
    """A time on the trace (ns) as the program's perf_counter reads it."""
    return T0 + (ns - LO) * 1e-9


@pytest.fixture
def program_spans():
    """Installs a process tracer that gives back the spans set on it (in
    ns on the trace's clock, handed out in perf_counter seconds)."""
    previous = trace_mod._DEFAULT
    asked = []

    class Tracer:
        spans: list = []

        def spans_in(self, t0, t1):
            asked.append((t0, t1))
            return [(n, tr, seconds(s), seconds(e))
                    for n, tr, s, e in self.spans]

    tracer = Tracer()
    tracer.asked = asked
    trace_mod.set_default_tracer(tracer)
    yield tracer
    trace_mod.set_default_tracer(previous)


def trace(ops, ops1=None):
    devices = {"/device:TPU:0": {"XLA Ops": ops, "XLA Modules": []}}
    if ops1 is not None:
        devices["/device:TPU:1"] = {"XLA Ops": ops1, "XLA Modules": []}
    return xtrace.Trace(devices=devices,
                        host=[(xtrace.WINDOW_SPAN, LO, 101_000.0)])


def ctx(kind, tr, chips=1):
    return types.SimpleNamespace(
        trace=tr, chips=chips,
        run=types.SimpleNamespace(kind=kind, t0=T0, t1=T0 + 1.0))


LM_OPS = [("%fusion.1", 12_000.0, 28_000.0),
          ("%fusion.2", 40_000.0, 50_000.0),
          ("%fusion.3", 45_000.0, 55_000.0),
          ("%fusion.4", 60_000.0, 89_000.0)]
LM_SPANS = [("serve.decode", "serve", 11_000.0, 31_000.0),   # idle 4,000
            ("serve.wait", "serve", 20_000.0, 29_000.0),
            ("serve.decode", "serve", 40_000.0, 60_000.0),   # idle 5,000
            ("serve.decode", "serve", 70_000.0, 90_000.0)]   # idle 1,000
MC_OPS = [(KERNEL_OP, 12_000.0, 22_000.0), ("%fusion.7", 22_000.0, 24_000.0),
          (KERNEL_OP, 32_000.0, 42_000.0)]
MC_SPANS = [("pricing.run_batch", "pricing", 5_000.0, 61_000.0),
            ("pricing.warm", "pricing", 10_000.0, 30_000.0),
            ("pricing.finalize", "pricing", 25_000.0, 30_000.0),
            ("pricing.launch", "pricing", 30_000.0, 50_000.0),
            ("pricing.finalize", "pricing", 45_000.0, 50_000.0),
            ("pricing.records", "pricing", 50_000.0, 60_000.0)]


def test_spans_move_onto_the_trace_by_the_window_anchor(program_spans):
    program_spans.spans = [("a", "t", 11_000.0, 31_000.0)]
    (name, track, s, e), = program.spans(ctx("lm", trace([])))
    assert (name, track) == ("a", "t")
    assert s == pytest.approx(11_000.0, abs=1e-3)
    assert e == pytest.approx(31_000.0, abs=1e-3)
    assert program_spans.asked == [(T0, T0 + 1.0)]
    # the offset itself: run.t0 lands on the window's start, and half a
    # second later half a second later
    program_spans.spans = [("b", "t", LO, LO + 0.5e9)]
    (_, _, s, e), = program.spans(ctx("lm", trace([])))
    assert (s, e) == (pytest.approx(LO, abs=1e-3),
                      pytest.approx(LO + 0.5e9, abs=1e-3))


def test_idle_by_hand_one_and_two_devices():
    idle = program.Idle(trace(LM_OPS), 1)
    assert idle(11_000.0, 31_000.0) == pytest.approx(4_000.0)
    assert idle(40_000.0, 60_000.0) == pytest.approx(5_000.0)
    assert idle(0.0, 200_000.0) == pytest.approx(100_000.0 - 60_000.0)
    two = program.Idle(trace(LM_OPS, [("%f", 0.0, 200_000.0)]), 2)
    assert two(11_000.0, 31_000.0) == pytest.approx(2_000.0)
    assert program.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]


def test_decode_gap_ms_by_hand(program_spans):
    program_spans.spans = LM_SPANS
    assert reader("decode_gap_ms")(ctx("lm", trace(LM_OPS))) == \
        pytest.approx(4_000.0 * 1e-6)


def test_pricing_finish_idle_share_by_hand(program_spans):
    program_spans.spans = MC_SPANS
    # finalize 25,000-30,000 and 45,000-50,000, records 50,000-60,000: no
    # op runs in them, 20,000 of the window's 100,000
    assert reader("pricing_finish_idle_share")(
        ctx("pricing", trace(MC_OPS))) == pytest.approx(20.0)


def test_warm_launch_share_by_hand(program_spans):
    program_spans.spans = MC_SPANS
    assert reader("warm_launch_share")(ctx("pricing", trace(MC_OPS))) == \
        pytest.approx(50.0)


@pytest.mark.parametrize("name, kind", [
    ("decode_gap_ms", "lm"), ("pricing_finish_idle_share", "pricing"),
    ("warm_launch_share", "pricing")])
def test_readers_read_nothing_elsewhere(program_spans, name, kind):
    program_spans.spans = LM_SPANS + MC_SPANS
    ops = LM_OPS + MC_OPS
    read = reader(name)
    assert read(ctx(kind, trace(ops))) is not None
    other = "pricing" if kind == "lm" else "lm"
    assert read(ctx(other, trace(ops))) is None
    # a program whose tracer cannot read its spans back
    trace_mod.set_default_tracer(types.SimpleNamespace(enabled=False))
    assert read(ctx(kind, trace(ops))) is None
    # a program that recorded none of the spans the reader reads
    program_spans.spans = []
    trace_mod.set_default_tracer(program_spans)
    assert read(ctx(kind, trace(ops))) is None
