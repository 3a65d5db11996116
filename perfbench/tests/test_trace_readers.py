"""The trace reduction and the per-layer readers on traces recorded on a
TPU v5e (one Qwen2.5-3B job of 8 new tokens, 32 x 512; eight Table 1 tasks
through the Pallas kernel), and on a hand-built two-chip trace."""
from __future__ import annotations

import gzip
import importlib.util
import json
import types

import pytest

from conftest import BENCH
from lib import counts, readers, xtrace

PEAK = json.loads((BENCH / "lib" / "peaks.json").read_text())["devices"][
    "TPU v5 lite"]
QWEN = json.loads((BENCH / "configs" / "qwen25_3b.json").read_text())


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.fixture(scope="module")
def lm_trace():
    return xtrace.Trace.from_json(json.loads(
        (BENCH / "data" / "qwen25_3b_one_job.trace.json").read_text()))


@pytest.fixture(scope="module")
def mc_trace(tmp_path_factory):
    path = tmp_path_factory.mktemp("xplane") / "t.xplane.pb"
    path.write_bytes(gzip.decompress(
        (BENCH / "data" / "pricing_8_tasks.xplane.pb.gz").read_bytes()))
    return xtrace.load(str(path))


def lm_run(gen=8):
    return types.SimpleNamespace(
        kind="lm", config=QWEN, traffic={"batch": 32, "prompt_len": 512},
        decode_positions=lambda: [512 + s for s in range(gen)],
        held_weight_bytes=lambda: 6.17e9)


def test_union_and_clip_by_hand():
    evs = [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("d", 22, 25)]
    assert xtrace.union_ns(evs) == 25
    assert xtrace.clip(evs, 8, 24) == [("a", 8, 10), ("b", 8, 15),
                                       ("c", 20, 24), ("d", 22, 24)]
    assert xtrace.within(evs, [("s", 4, 12)]) == [("a", 0, 10), ("b", 5, 15)]


def test_lm_trace_steps_found_by_call_count(lm_trace):
    dev = readers.used_devices(lm_trace, 1)[0]
    assert readers.calls_in_window(lm_trace, "bench.prefill") == 1
    assert readers.calls_in_window(lm_trace, "bench.decode") == 8
    pre = readers.step_modules(lm_trace, dev, 1)
    dec = readers.step_modules(lm_trace, dev, 8)
    assert [e[0].split("(")[0] for e in pre] == ["jit__lambda"]
    assert {e[0].split("(")[0] for e in dec} == {"jit_decode_step"}
    # the recorded times: one prefill ~0.97 s, decode steps ~23 ms
    assert 0.9 < readers.seconds(pre) < 1.05
    assert 0.02 < readers.seconds(dec) / 8 < 0.027


def test_lm_readers_on_recorded_trace(lm_trace):
    ctx = types.SimpleNamespace(trace=lm_trace, run=lm_run(), peak=PEAK,
                                chips=1)
    dev = readers.used_devices(lm_trace, 1)[0]
    lo, hi = lm_trace.window()
    busy = xtrace.union_ns(xtrace.clip(lm_trace.line(dev, "XLA Ops"), lo, hi))
    assert reader("idle_share.lm")(ctx) == pytest.approx(
        100 * (1 - busy / (hi - lo)))
    t_pre = readers.seconds(readers.step_modules(lm_trace, dev, 1))
    assert reader("prefill_mfu")(ctx) == pytest.approx(
        100 * counts.prefill_flops(QWEN, 32, 512) / (t_pre * 1.97e14))
    t_dec = readers.seconds(readers.step_modules(lm_trace, dev, 8))
    flops = sum(counts.decode_flops(QWEN, 32, p) for p in range(512, 520))
    assert reader("decode_mfu")(ctx) == pytest.approx(
        100 * flops / (t_dec * 1.97e14))
    least = sum((6.17e9 + counts.kv_bytes(QWEN, 32, p)) / 8.19e11
                for p in range(512, 520))
    roof = reader("decode_roofline")(ctx)
    assert roof == pytest.approx(100 * least / t_dec)
    for name in ("idle_share.lm", "prefill_mfu", "decode_mfu",
                 "decode_roofline"):
        assert 0 < reader(name)(ctx) < 100
    # a single chip has no collectives; a pricing reader finds nothing here
    assert reader("tp_collective_ms")(ctx) is None
    assert reader("mc_kernel_roofline")(ctx) is None


def test_readers_silent_when_call_counts_do_not_match(lm_trace):
    ctx = types.SimpleNamespace(trace=lm_trace, run=lm_run(gen=9), peak=PEAK,
                                chips=1)
    assert reader("decode_mfu")(ctx) is None
    assert reader("decode_roofline")(ctx) is None


def test_breakdown_names_gaps_by_host_span(lm_trace):
    b = readers.breakdown(lm_trace, 1)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert all(not name.startswith("while") for name, _ in b["device_ops"])
    secs = [s for _, s in b["idle_gaps"]]
    assert secs == sorted(secs, reverse=True) and secs[0] > 0
    assert {n for n, _ in b["idle_gaps"]} <= {
        "bench.dispatch", "bench.prefill", "bench.decode",
        "outside the harness's spans"}


def test_xplane_load_of_recorded_pricing_trace(mc_trace):
    dev, = mc_trace.device_names
    assert dev == "/device:TPU:0"
    kern = readers.ops_matching(mc_trace, dev, r"mc_moments.*custom-call")
    assert len(kern) == 2
    assert 0.01 < readers.seconds(kern) / 2 < 0.013
    assert [e[0] for e in mc_trace.host] == ["bench.window"]


def test_pricing_readers_on_recorded_trace(mc_trace):
    # the recorded window priced 8 tasks (4 Black-Scholes, 4 Heston) over
    # 65,536 paths of 256 steps, one launch per family
    launched = (counts.mc_ops("black-scholes", 4 * 65536, 256)
                + counts.mc_ops("heston", 4 * 65536, 256))
    run = types.SimpleNamespace(kind="pricing", launched_ops=lambda: launched,
                                needed_ops=lambda: launched, reports=[])
    ctx = types.SimpleNamespace(trace=mc_trace, run=run, peak=PEAK, chips=1)
    dev = "/device:TPU:0"
    t = readers.seconds(readers.ops_matching(mc_trace, dev,
                                             r"mc_moments.*custom-call"))
    roof = reader("mc_kernel_roofline")(ctx)
    assert roof == pytest.approx(100 * launched / (t * PEAK["vpu_ops_per_s"]))
    assert 0 < roof < 100
    mfu = reader("mfu.pricing")(ctx)
    assert 0 < mfu < roof
    assert 0 <= reader("idle_share.pricing")(ctx) < 100
    assert reader("discarded_launch_share")(ctx) is None
    assert reader("prefill_mfu")(ctx) is None


def test_collectives_read_per_step_and_chip():
    """Two chips, two decode steps, each with two all-gathers."""
    def dev_lines(shift):
        mods = [("jit_decode_step(1)", 100 + shift, 200 + shift),
                ("jit_decode_step(1)", 300 + shift, 400 + shift),
                ("jit__argmax(2)", 210, 220), ("jit__argmax(2)", 410, 420)]
        ops = []
        for s in (100, 300):
            ops += [("%all-gather.1 = bf16[1]", s + shift + 10, s + shift + 14),
                    ("%fusion.2 = bf16[1]", s + shift + 20, s + shift + 60),
                    ("%all-gather.3 = bf16[1]", s + shift + 70, s + shift + 76)]
        return {"XLA Modules": mods, "XLA Ops": ops}

    tr = xtrace.Trace(
        devices={"/device:TPU:0": dev_lines(0), "/device:TPU:1": dev_lines(2),
                 "/device:TPU:2": {}},
        host=[("bench.window", 0, 1000), ("bench.decode", 90, 95),
              ("bench.decode", 290, 295)])
    ctx = types.SimpleNamespace(trace=tr, run=types.SimpleNamespace(kind="lm"),
                                peak=PEAK, chips=2)
    # (4 + 6) ns per step on each chip
    assert reader("tp_collective_ms")(ctx) == pytest.approx(10e-6)
