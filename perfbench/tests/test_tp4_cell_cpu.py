"""The tensor-parallel cell at its own width, tp=4, on four virtual CPU
devices, against the plain reference: a tiny Yi with 4 kv heads (one a
device) and 8 query heads, so that every device holds a kv head of its
own. Sound, it is correct; with the all-gathers between devices left out,
it is not."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT

SCRIPT = r"""
import json, pathlib, sys
sys.path[:0] = [{src!r}, {bench!r}, {tests!r}]
import jax
from conftest import make_tiny_root
from repro.configs import get_config
from repro.domains import lm_serving
import run as bench

HEADS = dict(num_attention_heads=8, num_key_value_heads=4)
root = make_tiny_root(pathlib.Path({root!r}), tp=4)
path = root / "perfbench" / "configs" / "yi_9b_tp4.json"
cfg = json.loads(path.read_text())
cfg.update(HEADS)
path.write_text(json.dumps(cfg))

# the system's tiny Yi at the same widths
plain = lm_serving.LMRequest.config
lm_serving.LMRequest.config = lambda req: (
    get_config(req.arch).smoke(n_heads=8, n_kv_heads=4)
    if req.arch == "yi_9b" and req.smoke else plain(req))
if {broken}:
    def local_only(x, axis_name, *, axis=0, tiled=False, **kw):
        # the exchange left out: every device repeats its own shard
        n = jax.lax.psum(1, axis_name)
        return jax.numpy.concatenate([x] * n, axis=axis)

    jax.lax.all_gather = local_only
r = bench.run_cell("yi_9b_tp4.decode_mix", 2 ** 31 + 404, 2.0, False,
                   root=root, require_accelerator=False)
print(json.dumps({{"correct": r["correct"], "checks": r["checks"]}}))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_tp4_cell_with_and_without_the_exchange(tmp_path, broken):
    script = SCRIPT.format(src=str(ROOT / "src"), bench=str(BENCH),
                           tests=str(BENCH / "tests"), broken=broken,
                           root=str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["correct"] is (not broken), r
    if broken:
        gap = r["checks"]["gap_max"]
        assert gap["value"] > gap["limit"], r
