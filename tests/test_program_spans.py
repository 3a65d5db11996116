"""Program spans of the serving engine and the pricing platform.

Under a JAX profiler session the process tracer records the spans that
``ServeEngine.generate_many``, ``LocalLMPlatform.run_batch`` and
``LocalJaxPlatform.run_batch`` open, as many of each kind as the
docstrings say, nested as they say; and the outputs are those of an
untraced run, to the bit.
"""
import collections

import numpy as np
import pytest

from repro.obs.trace import default_tracer, set_default_tracer


@pytest.fixture
def process_tracer(monkeypatch):
    """A fresh, disabled process tracer; the previous one is put back."""
    import repro.obs.trace as trace_mod

    monkeypatch.delenv("REPRO_TRACE", raising=False)
    previous = trace_mod._DEFAULT
    set_default_tracer(None)
    yield default_tracer()
    set_default_tracer(previous)


def _recorded(tracer, trace_dir, fn):
    """``fn()`` under a profiler session, and the spans it recorded."""
    import time

    import jax

    t0 = time.perf_counter()
    with jax.profiler.trace(str(trace_dir)):
        out = fn()
    return out, tracer.spans_in(t0, time.perf_counter(), args=True)


def _inside(spans, outer):
    """The spans lying within ``outer``'s interval, ``outer`` left out."""
    return [s for s in spans if s is not outer
            and outer[2] <= s[2] and s[3] <= outer[3]]


def test_generate_many_spans_under_a_session(process_tracer, tmp_path):
    from repro.configs import get_config
    from repro.launch.serve import ServeEngine

    eng = ServeEngine(get_config("qwen25_3b").smoke(), batch=2, prompt_len=8,
                      max_seq=16)
    plain = eng.generate_many([3, 5], seed=1)
    assert process_tracer.spans == []  # no session, nothing recorded
    traced, spans = _recorded(process_tracer, tmp_path,
                              lambda: eng.generate_many([3, 5], seed=1))
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a.tokens, b.tokens)

    n = collections.Counter(s[0] for s in spans)
    assert n["serve.generate"] == 1 and n["serve.prefill"] == 1
    assert n["serve.decode"] == 5
    assert n["serve.wait"] == 6 and n["serve.fetch"] == 5
    assert {s[1] for s in spans} == {"serve"}
    generate, = [s for s in spans if s[0] == "serve.generate"]
    assert len(_inside(spans, generate)) == len(spans) - 1
    for step in (s for s in spans if s[0] == "serve.decode"):
        held = collections.Counter(s[0] for s in _inside(spans, step))
        assert held == {"serve.wait": 1, "serve.fetch": 1}
    prefill, = [s for s in spans if s[0] == "serve.prefill"]
    assert [s[0] for s in _inside(spans, prefill)] == ["serve.wait"]


def test_pricing_run_batch_spans_under_a_session(process_tracer, tmp_path):
    from repro.pricing import table1_workload
    from repro.pricing.platforms import LocalJaxPlatform

    tasks = table1_workload(seed=3, n_steps=8,
                            categories=[("BS-A", 2), ("H-A", 2)])
    assert len(tasks) == 4
    paths = [512, 1024, 512, 2048]
    plain = LocalJaxPlatform(backend="jnp").run_batch(tasks, paths, seed=5)
    plat = LocalJaxPlatform(backend="jnp")
    traced, spans = _recorded(process_tracer, tmp_path, lambda: [
        plat.run_batch(tasks, paths, seed=5) for _ in range(2)])
    for recs in traced:
        assert [(r.task_id, r.price, r.ci95) for r in plain] == \
            [(r.task_id, r.price, r.ci95) for r in recs]

    tops = [s for s in spans if s[0] == "pricing.run_batch"]
    assert len(tops) == 2
    assert sum(len(_inside(spans, top)) for top in tops) == len(spans) - 2
    # the first call warms its launch shapes, the repeat goes straight to
    # the timed launch; two launch groups (Black-Scholes, Heston) a launch
    for top, launched in zip(sorted(tops, key=lambda s: s[2]), (1, 0)):
        inner = _inside(spans, top)
        n = collections.Counter(s[0] for s in inner)
        for name in ("pricing.warm", "pricing.launch", "pricing.records"):
            assert n[name] == 1, name
        assert n["pricing.pack"] == n["pricing.finalize"] == 2 + 2 * launched
        warm, = [s for s in inner if s[0] == "pricing.warm"]
        assert warm[4] == {"launched": launched}
        launch, = [s for s in inner if s[0] == "pricing.launch"]
        for phase, held in ((warm, launched), (launch, 1)):
            got = collections.Counter(s[0] for s in _inside(spans, phase))
            assert got == ({"pricing.pack": 2, "pricing.finalize": 2}
                           if held else {})
    assert (plat.warm_launches, plat.warm_skips) == (1, 1)


def test_lm_platform_run_batch_span_holds_the_engine_call(process_tracer,
                                                          tmp_path):
    from repro.domains.lm_serving import LMRequest, LocalLMPlatform

    req = LMRequest("qwen25_3b", prompt_len=8, gen_tokens=3, batch=2,
                    max_new_tokens=8, task_id=0, smoke=True)
    plat = LocalLMPlatform()
    plat.engine(req)  # built and warmed outside the session
    (rec,), spans = _recorded(process_tracer, tmp_path,
                              lambda: plat.run_batch([req], [3], seed=2))
    assert rec.n_tokens == 3
    top, = [s for s in spans if s[0] == "lm.run_batch"]
    inner = collections.Counter(s[0] for s in _inside(spans, top))
    assert inner["serve.generate"] == 1 and inner["serve.decode"] == 3
    assert len(_inside(spans, top)) == len(spans) - 1
