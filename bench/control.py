#!/usr/bin/env python3
"""Read the bfloat16 control's numbers for a cell on several seeds.

    python bench/control.py --workload tpch-z2-m512.query --seeds 1,2,3

The control is the cell's reference in the precision below the
configuration's, put in the program's place; the cell's traffic kind
decides what it answers or stores (``control_numbers``, ``bench.kinds``).
For the inner-product kinds it is the plain reference in bfloat16 (values,
weights, hashes, ranks, tau, probabilities and products rounded; float32
sums): a query cell's control answers every request of the cell's schedule
at its own rate and window, an ingest cell's stores two passes over the
pool.  Its numbers are compared with the reference exactly as a run
compares the program's; each seed's have to exceed one of the cell's
limits.  Prints one JSON line per seed.  Needs no chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT]
    from bench import drive, spec
    cell = spec.load_cell(ROOT, args.workload)
    seconds = args.seconds
    if seconds is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            seconds = json.load(f)["run_seconds"]
    fails = True
    for seed in map(int, args.seeds.split(",")):
        kind = drive.traffic_kind(cell)(cell, seed)
        kind.setup(seconds, program=False)
        numbers = kind.control_numbers()
        failed = any(numbers[k] > cell.limits[k] for k in cell.limits)
        fails &= failed
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "control": numbers, "limits": cell.limits,
                          "control_fails": failed}), flush=True)
    return 0 if fails else 1


if __name__ == "__main__":
    sys.exit(main())
