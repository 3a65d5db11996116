"""Shared set-up for the benchmark's CPU tests: import paths, and a tiny
copy of a checkout whose cells keep the benchmark's shapes' kinds at sizes
a CPU runs in seconds."""
from __future__ import annotations

import json
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

#: Tiny stand-ins for the cells: same runners, same files, CPU sizes.
TINY_LM = {"smoke": True, "hidden_size": 64, "intermediate_size": 128,
           "num_hidden_layers": 2, "num_attention_heads": 4,
           "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
           "torch_dtype": "float32"}
TINY_PRICING = {"n_steps": 8, "characterise_ladder": [1024, 4096],
                "categories": [["BS-A", 2], ["BS-DDB", 1], ["H-B", 2],
                               ["H-E", 1]]}
TINY_TRAFFIC = {
    "decode_mix": {"kind": "lm_jobs", "batch": 8, "prompt_len": 16,
                   "max_new_tokens": 128,
                   "gen": {"dist": "exponential", "mean": 48, "strata": 4},
                   "check": {"jobs": 4, "rows": 8}},
    "ci0.1": {"kind": "pricing_workloads", "ci": 0.5,
              "check": {"tasks": 3, "ref_paths": 4096}},
}
#: The tiny cells' limits. The tiny LM runs in float32, so the program's
#: gap is rounding alone (0.0 on eight seeds, one cycle, every row) and
#: the control's, in bfloat16, 0.0016-0.011 on seven of them and 0.0 on
#: 2**31 + 1234, where it flips no token. The tiny pricing ladder
#: fits the accuracy models from few paths, so its CI lands further from
#: its target.
TINY_LIMITS = {
    "qwen25_3b.decode_mix": {"gap_max": 5e-4, "gap_mean": 1e-4,
                             "bad_jobs": 0},
    "yi_9b_tp4.decode_mix": {"gap_max": 5e-4, "gap_mean": 1e-4,
                             "bad_jobs": 0},
    "table1_pricing.ci0.1": {"price_gap_ci": 0.01, "ci_gap_rel": 0.01,
                             "ci_expected_over_target": 0.3, "unpriced": 0},
}


#: The tensor-parallel cell, kept out of BENCHMARK.json until it is proved
#: on four chips (PERF.md), but tested here like the others.
YI_CONFIG = {"name": "yi_9b_tp4", "file": "perfbench/configs/yi_9b_tp4.json",
             "source": "https://huggingface.co/01-ai/Yi-9B", "reduced": [],
             "why": "tensor parallel"}
YI_CELL = {"name": "yi_9b_tp4.decode_mix", "config": "yi_9b_tp4",
           "traffic": "decode_mix", "chips": 4, "why": "tensor parallel"}


def make_tiny_root(dest: pathlib.Path, tp: int = 1) -> pathlib.Path:
    """A checkout-shaped directory with the tiny cells, the tensor-parallel
    one among them on ``tp`` devices."""
    data = dest / "perfbench"
    for sub in ("configs", "traffic", "limits"):
        (data / sub).mkdir(parents=True, exist_ok=True)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    if YI_CELL["name"] not in {w["name"] for w in bench["workloads"]}:
        bench["configs"].append(YI_CONFIG)
        bench["workloads"].append(YI_CELL)
    for cell in bench["workloads"]:
        if cell["config"] == "yi_9b_tp4":
            cell["chips"] = tp
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    for entry in bench["configs"]:
        cfg = json.loads((ROOT / entry["file"]).read_text())
        if cfg["kind"] == "lm":
            cfg.update(TINY_LM)
            if cfg["arch"] == "yi_9b":
                cfg.update(tp=tp, num_key_value_heads=2, num_attention_heads=4,
                           rope_theta=10000.0, attention_bias=False)
        else:
            cfg.update(TINY_PRICING)
        (dest / entry["file"]).write_text(json.dumps(cfg))
    for name, t in TINY_TRAFFIC.items():
        (data / "traffic" / f"{name}.json").write_text(json.dumps(t))
    for name, lim in TINY_LIMITS.items():
        (data / "limits" / f"{name}.json").write_text(json.dumps(lim))
    return dest


@pytest.fixture
def tiny_root(tmp_path):
    return make_tiny_root(tmp_path)


@pytest.fixture(autouse=True)
def _restore_kernel_wrapper():
    """The pricing runner taps the kernel wrapper; undo it after a test
    that failed before the runner could."""
    from repro.kernels import ops

    orig = ops.mc_moments_batch
    yield
    ops.mc_moments_batch = orig


def copy_checkout_files(dest: pathlib.Path) -> None:
    """BENCHMARK.json and the benchmark's directory, nothing else."""
    shutil.copy(ROOT / "BENCHMARK.json", dest / "BENCHMARK.json")
    shutil.copytree(BENCH, dest / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
