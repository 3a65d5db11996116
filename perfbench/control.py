"""Readings for the limits of a cell's output check, on the chip.

    python3 perfbench/control.py --workload qwen25_3b.decode_mix \
        --seeds 11,12,13 --seconds 13 --control 1
    python3 perfbench/control.py --workload table1_pricing.ci0.1 \
        --seeds 21,22,23 --seconds 13 --fault half_paths_reported

Runs the cell once per seed in one process, each with a short window at
the cell's own load, and prints one JSON line per seed with every number
the check read: the program's against the plain reference and, with
``--control 1``, the comparison made with the control's numbers (the
reference computed one precision below the configuration's, put in the
program's place), whose ``correct`` has to come out false. With
``--fault`` the timed path runs with one of ``lib/faults.py``'s faults
planted. The limits in ``perfbench/limits/<cell>.json`` are set from these
readings (PERF.md). The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import run as bench


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=1)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(bench.ROOT / "src"), str(bench.HERE)]
    from lib.faults import FAULTS

    prepare = FAULTS[args.fault][1] if args.fault else None
    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            r = bench.run_cell(args.workload, seed, args.seconds, False,
                               prepare=prepare, control=bool(args.control),
                               readings=True)
        except bench.NoAccelerator as e:
            print(f"control.py: {e}; nothing was run", file=sys.stderr)
            return 2
        line = {"seed": seed, "fault": args.fault, "correct": r["correct"],
                "attempted": r["attempted"], "readings": r["readings"]}
        if "control" in r:
            line["control"] = r["control"]
        print(json.dumps(line), flush=True)
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
