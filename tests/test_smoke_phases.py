"""The bring-up phases of ``chip_smoke.py``, run on the CPU at smoke sizes.

On the chip the same functions run at full size; here they show the
paths, arguments and checks are right (the Pallas kernel interprets).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_config
from repro.launch import smoke
from repro.pricing.platforms import TABLE2_SPECS
from repro.pricing.workload import table1_workload

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _env(**extra):
    env = dict(os.environ, **extra)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), env.get("PYTHONPATH", "")])
    return env


def test_pricing_phase_prices_every_task_with_the_kernel_on_the_fleet(
        monkeypatch):
    from repro.pricing import platforms

    # two slow Table 2 rows, so the interpreted kernel still wins work
    monkeypatch.setattr(platforms, "TABLE2_SPECS", TABLE2_SPECS[2:4])
    tasks = table1_workload(seed=3, n_steps=8,
                            categories=[("BS-A", 2), ("H-DB", 2)])
    out = smoke.pricing_phase(tasks, accuracy=0.5, oracle_paths=2048,
                              per_family=2)
    assert out["compiled_kernel"] is False  # the CPU interprets
    assert out["tasks_priced"] == 4
    assert 0 < out["chip_share"] <= 1 and out["chip_paths"] > 0
    assert out["kernel_vs_oracle_price_rel"] <= smoke.KERNEL_PRICE_TOL
    assert out["device"] == "CPU/cpu"


def test_pricing_phase_fails_when_the_kernel_disagrees(monkeypatch):
    from repro.pricing import mc

    real = mc.price_batch

    def skewed(tasks, n_paths, seed=0, backend="jnp", **kw):
        res = real(tasks, n_paths, seed=seed, backend=backend, **kw)
        if backend == "pallas":
            res = [mc.PriceResult(r.price * 1.01, r.ci95, r.std_error,
                                  r.n_paths) for r in res]
        return res

    monkeypatch.setattr(mc, "price_batch", skewed)
    tasks = table1_workload(seed=3, n_steps=8, categories=[("BS-A", 1)])
    with pytest.raises(smoke.SmokeCheckError, match="kernel"):
        smoke.pricing_phase(tasks, oracle_paths=1024, per_family=1)


def test_serving_phase_checks_decode_against_extended_prefill():
    out = smoke.serving_phase(arch="qwen25_3b", smoke=True, batch=2,
                              prompt_len=8, gens=(4, 8), max_new_tokens=8,
                              consistency_steps=4)
    assert out["tokens"] >= 12
    gate = out["decode_vs_prefill_2_layers"]
    assert gate["steps"] == 4
    assert gate["max_abs"] <= smoke.BF16_LOGIT_TOL * gate["scale"]
    depth = get_config("qwen25_3b").smoke().n_layers
    assert out[f"ulp_embed_nudge_{depth}_layers"] > 0


_TP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json
    from repro.launch import smoke
    out = smoke.tp_phase(arch="yi_9b", smoke=True, tp=2, depth=2, batch=2,
                         prompt_len=8, gens=(4, 8), max_new_tokens=8,
                         steps=3)
    print("TP_PHASE", json.dumps(out))
""")


def test_tp_phase_on_forced_host_mesh_subprocess():
    """The four-chip phase's path at smoke size on forced host devices
    (XLA_FLAGS must precede JAX's start, hence the subprocess)."""
    proc = subprocess.run([sys.executable, "-c", _TP_SCRIPT],
                          capture_output=True, text=True, timeout=600,
                          env=_env())
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith("TP_PHASE")]
    out = json.loads(line[-1].split(" ", 1)[1])
    f32 = out["depth2_float32"]
    assert f32["rel_l2"] <= smoke.TP_F32_REL_TOL
    assert f32["greedy_disagree_where_decided"] == 0
    assert "depth2_bfloat16" in out


def test_chip_smoke_refuses_to_run_without_a_tpu():
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          capture_output=True, text=True, timeout=120,
                          env=_env(JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr
