"""Bring-up smoke run: the main paths, once, on a TPU.

    python3 chip_smoke.py          # one chip: pricing, then LM serving
    python3 chip_smoke.py --tp 4   # four chips: Yi-9B at tp=4 and its
                                   # comparison with tp=1, nothing else

With no option it prices the paper's Table 1 workload (128 tasks,
n_steps=256) through the scheduler over the Table 2 fleet plus the
compiled Pallas kernel on the chip, then serves full-width Qwen2.5-3B
(bf16, random weights from a seed) through the scheduler. Each phase
checks its output and a failed check ends the run with a non-zero exit.
The lines before the last are smoke output from one run, not
measurements. The last line is one JSON object naming the device.
Exits non-zero, printing no result, when JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))


def _say(phase: str, result: dict) -> None:
    print(f"smoke output ({phase}, one run, not a measurement): "
          f"{json.dumps(result, sort_keys=True, default=str)}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--tp", type=int, default=0,
                    help="run only the tensor-parallel phase over this many "
                         "chips")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (default device: "
              f"{dev.platform}); nothing was run", file=sys.stderr)
        return 1

    from repro.compile_cache import enable_compile_cache
    from repro.launch import smoke
    from repro.pricing.workload import table1_workload

    print(f"smoke: compile cache at {enable_compile_cache()}", flush=True)
    t0 = time.perf_counter()
    if args.tp:
        if jax.device_count() < args.tp:
            print(f"chip_smoke: --tp {args.tp} needs {args.tp} chips, JAX "
                  f"found {jax.device_count()}", file=sys.stderr)
            return 1
        _say("yi_9b tp", smoke.tp_phase(tp=args.tp))
    else:
        pricing = smoke.pricing_phase(table1_workload(n_steps=256))
        _say("pricing", pricing)
        if not pricing["compiled_kernel"]:
            print("chip_smoke: the pricing kernel was interpreted",
                  file=sys.stderr)
            return 1
        _say("qwen25_3b serving", smoke.serving_phase())
    print(f"smoke: all phases passed in {time.perf_counter() - t0:.1f} s",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
