#!/usr/bin/env python3
"""Readings that a cell's ``logit_gap`` limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 11,12,13 --seconds 5 [--control int8]

For every seed, in one process: one run of the cell as the benchmark runs
it (its window at the cell's own load), the program's widest logit gap
over the sample (the lower reading), and each control's widest gap over
the same prompts and served tokens (the upper reading).  One JSON line
per seed, then the largest program gap and the smallest control gap.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))

from benchmarks.chip import run  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", action="append", default=[])
    args = ap.parse_args()
    prog, ctrl = [], {c: [] for c in args.control}
    for seed in (int(s) for s in args.seeds.split(",")):
        out = run.run_cell(args.workload, seed, args.seconds, False,
                           controls=tuple(args.control))
        gap = out["checks"]["logit_gap"]["value"]
        prog.append(gap)
        for c, v in out.get("control_gap", {}).items():
            ctrl[c].append(v)
        print(json.dumps({"seed": seed, "program_gap": gap,
                          "control_gap": out.get("control_gap", {}),
                          "tokens": out["tokens_compared"],
                          "checks": out["checks"]}), flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(prog),
                      "upper": {c: min(v) for c, v in ctrl.items()}}))


if __name__ == "__main__":
    main()
