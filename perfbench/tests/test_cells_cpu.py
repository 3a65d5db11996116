"""Whole runs of the cells at tiny sizes on the CPU: the harness's look
for a chip is skipped, everything else runs. A sound run is correct; a run
with the timed path broken underneath is not."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT, copy_checkout_files, make_tiny_root

import run as bench
from lib import faults

SEED = 2 ** 31 + 1234


def run_tiny(root, cell, prepare=None, seconds=2.0, control=False,
             readings=False):
    return bench.run_cell(cell, SEED, seconds, False, root=root,
                          require_accelerator=False, prepare=prepare,
                          control=control, readings=readings)


def test_lm_cell_sound_run_is_correct(tiny_root):
    r = run_tiny(tiny_root, "qwen25_3b.decode_mix")
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 1
    assert set(r["metrics"]) == {"tokens_per_s", "itl_p95_ms", "ttft_p50_ms",
                                 "setup_s"}
    assert list(r)[-1] == "checks"
    assert r["checks"]["gap_max"]["value"] <= 1e-4


def test_lm_cell_token_altered_where_produced_is_not_correct(tiny_root):
    r = run_tiny(tiny_root, "qwen25_3b.decode_mix", faults.token_altered)
    assert not r["correct"]
    assert r["checks"]["gap_max"]["value"] > r["checks"]["gap_max"]["limit"]


@pytest.mark.parametrize("seed", [5, 77, 12])
def test_lm_control_is_not_correct(tiny_root, seed):
    """The control in the program's place, at a tiny size over one whole
    cycle and every row: the harness's own comparison finds it not
    correct, and its gap is many times the program's. The tiny LM runs in
    float32, so the control is bfloat16 and the program's gap is rounding
    alone; at this width the control flips a served token on most seeds,
    and these are three of them (conftest.TINY_LIMITS)."""
    r = bench.run_cell("qwen25_3b.decode_mix", seed, 0.01, False,
                       root=tiny_root, require_accelerator=False,
                       control=True)
    assert r["correct"] and r["control"]["correct"] is False
    low = r["control"]["checks"]["gap_max"]
    assert low["value"] > low["limit"]
    assert low["value"] > 3 * max(r["readings"]["gap_max"], 1e-4)


def test_pricing_cell_sound_run_is_correct(tiny_root):
    r = run_tiny(tiny_root, "table1_pricing.ci0.1")
    assert r["correct"], r["checks"]
    assert set(r["metrics"]) == {"pricing_makespan_s", "setup_s"}
    assert r["checks"]["price_gap_ci"]["value"] < 1e-4


@pytest.mark.parametrize("fault", ["half_paths_hidden", "half_paths_reported",
                                   "prices_moved"])
def test_pricing_cell_broken_path_is_not_correct(tiny_root, fault):
    r = run_tiny(tiny_root, "table1_pricing.ci0.1", faults.FAULTS[fault][1])
    assert not r["correct"], r["checks"]


def test_pricing_paths_short_for_every_task_fail_the_widest_ci(tiny_root):
    """Half the paths, reported as run: every task's expected CI is about
    sqrt(2) times its target, which the widest expected CI catches."""
    r = run_tiny(tiny_root, "table1_pricing.ci0.1",
                 faults.half_paths_reported, readings=True)
    c = r["checks"]["ci_expected_over_target"]
    assert c["value"] > c["limit"]
    assert r["readings"]["price_gap_ci"] < 1e-4


def test_pricing_control_is_not_correct(tiny_root):
    r = run_tiny(tiny_root, "table1_pricing.ci0.1", control=True)
    assert r["correct"] and r["control"]["correct"] is False
    low = r["control"]["checks"]["price_gap_ci"]
    assert low["value"] > low["limit"]
    assert low["value"] > 3 * max(r["readings"]["price_gap_ci"], 1e-3)


TP_SCRIPT = r"""
import json, pathlib, sys
sys.path[:0] = [{src!r}, {bench!r}, {tests!r}]
import jax
from conftest import make_tiny_root
import run as bench
if {broken}:
    from repro.launch import tp
    real = jax.lax.all_gather

    def local_only(x, axis_name, *, axis=0, tiled=False, **kw):
        # the exchange left out: every chip repeats its own shard
        n = jax.lax.psum(1, axis_name)
        return jax.numpy.concatenate([x] * n, axis=axis)

    jax.lax.all_gather = local_only
root = make_tiny_root(pathlib.Path({root!r}), tp=2)
r = bench.run_cell("yi_9b_tp4.decode_mix", 2 ** 31 + 99, 2.0, False,
                   root=root, require_accelerator=False)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_tp_cell_with_and_without_the_exchange(tmp_path, broken):
    """The sharded cell on two virtual CPU devices: sound, then with the
    all-gathers between chips left out."""
    script = TP_SCRIPT.format(src=str(ROOT / "src"), bench=str(BENCH),
                              tests=str(BENCH / "tests"), broken=broken,
                              root=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"] is (not broken), r


def test_refuses_to_run_without_an_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "qwen25_3b.decode_mix", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "no accelerator" in out.stderr


def test_refuses_to_run_with_only_the_benchmark_files(tmp_path):
    copy_checkout_files(tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "qwen25_3b.decode_mix", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout == ""


def test_every_cell_finds_its_files():
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for cell in b["workloads"]:
        spec = bench.load_cell(ROOT, cell["name"])
        assert spec["config"]["name"] == cell["config"]
        assert spec["end_to_end"] and spec["per_layer"]
        for m in spec["per_layer"]:
            assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    for entry in b["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        assert sorted(cfg["reduced"]) == sorted(entry["reduced"])
        # every key the file says was changed is a key of the file
        assert set(cfg["reduced"]) <= set(cfg)
