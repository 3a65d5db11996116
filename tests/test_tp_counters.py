"""The exchange counters on the serving engine's spans: at tp=1, 2 and 4
(virtual CPU devices), on a small Yi-shaped configuration (8 query heads,
4 kv heads), every ``serve.prefill`` and ``serve.decode`` span carries the
mesh width and the all-gathers and received bytes that the benchmark's own
count (``perfbench/lib/tp_counts.py``) gives from the configuration's
widths; 0 at tp=1. ``serve.generate`` carries the width. Every
``serve.decode`` span carries ``cache_in_place`` 1 (the step took the
donated cache), and a second ``generate_many`` on the same engine serves
the same tokens."""
from __future__ import annotations

import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
BATCH, PROMPT, GEN = 2, 8, 3
WIDTHS = (1, 2, 4)

SCRIPT = r"""
import json, sys
sys.path.insert(0, {src!r})
from repro.configs import get_config
from repro.launch.mesh import make_host_mesh
from repro.launch.serve import ServeEngine
from repro.obs.trace import Tracer, set_default_tracer

cfg = get_config("yi_9b").smoke(n_heads=8, n_kv_heads=4)
out = {{}}
for tp in {widths!r}:
    tracer = Tracer(enabled=True)
    set_default_tracer(tracer)
    eng = ServeEngine(cfg, batch={batch}, prompt_len={prompt},
                      max_seq={prompt} + {gen} + 1,
                      mesh=make_host_mesh(data=1, model=tp) if tp > 1 else None)
    first, = eng.generate_many([{gen}], seed=3)
    spans = [[s.name, s.args] for s in tracer.spans
             if s.name in ("serve.generate", "serve.prefill", "serve.decode")]
    again, = eng.generate_many([{gen}], seed=3)
    out[tp] = {{"spans": spans,
               "same_tokens": first.tokens.tolist() == again.tokens.tolist()}}
print(json.dumps(out))
"""


def _tp_counts():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tp_counts", ROOT / "perfbench" / "lib" / "tp_counts.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def runs():
    script = SCRIPT.format(src=str(ROOT / "src"), widths=WIDTHS, batch=BATCH,
                           prompt=PROMPT, gen=GEN)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return {int(k): v for k, v in
            json.loads(proc.stdout.strip().splitlines()[-1]).items()}


@pytest.fixture(scope="module")
def spans(runs):
    return {tp: run["spans"] for tp, run in runs.items()}


def _file_keys(tp: int) -> dict:
    """The small configuration as a configuration file states it."""
    from repro.configs import get_config

    cfg = get_config("yi_9b").smoke(n_heads=8, n_kv_heads=4)
    return {"num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads, "head_dim": cfg.hd,
            "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
            "torch_dtype": cfg.compute_dtype, "tp": tp}


@pytest.mark.parametrize("tp", WIDTHS)
def test_spans_carry_the_benchmarks_count_of_the_exchange(spans, tp):
    counts = _tp_counts()
    keys = _file_keys(tp)
    want = {"serve.prefill": counts.prefill_exchange(keys, BATCH, PROMPT),
            "serve.decode": counts.decode_exchange(keys, BATCH)}
    got = spans[tp]
    names = [name for name, _ in got]
    assert names.count("serve.generate") == names.count("serve.prefill") == 1
    assert names.count("serve.decode") == GEN
    for name, args in got:
        assert args["tp"] == tp, (name, args)
        if name == "serve.generate":
            continue
        assert (args["collectives"], args["exchange_bytes"]) == want[name]
        if tp == 1:
            assert args["collectives"] == args["exchange_bytes"] == 0
        else:
            assert args["collectives"] == 2 * keys["num_hidden_layers"] + 1
            assert args["exchange_bytes"] > 0


@pytest.mark.parametrize("tp", WIDTHS)
def test_decode_spans_carry_cache_in_place(spans, tp):
    decode = [args for name, args in spans[tp] if name == "serve.decode"]
    assert len(decode) == GEN
    assert all(args["cache_in_place"] == 1 for args in decode), decode


@pytest.mark.parametrize("tp", WIDTHS)
def test_generate_many_twice_serves_the_same_tokens(runs, tp):
    assert runs[tp]["same_tokens"]
