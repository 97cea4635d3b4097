#!/usr/bin/env python3
"""Find the highest query rate a query cell sustains (its knee), on the chip.

    python bench/sweep.py --workload tpch-z2-m512.query --seed 7 --seconds 20

Builds the cell's corpus once, serves requests back to back for
``--seconds`` (the closed-loop rate ``mu``), then offers open-loop Poisson
load at fractions of ``mu`` and prints, per rate, the completions per
second, the median and 95th-percentile latency, and the latency growth from
the first to the last quarter of the window (a growing backlog).  The
sustained rate is written into the cell's traffic file by hand.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--fractions", default="0.6,0.7,0.8,0.9,1.0")
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax
    import numpy as np
    from bench import drive, spec
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    cell = spec.load_cell(ROOT, args.workload)
    kind = drive.OpenQuery(cell, args.seed)
    kind.setup(args.seconds)
    null = lambda name: contextlib.nullcontext()
    t0, n = drive.clock(), 0
    while drive.clock() - t0 < args.seconds:
        kind._answers([kind.pool[n % len(kind.pool)]])
        n += 1
    mu = n / (drive.clock() - t0)
    rows = [{"rate": "closed loop", "per_s": mu}]
    print(json.dumps(rows[0]), flush=True)
    for f in map(float, args.fractions.split(",")):
        kind.schedule(f * mu, args.seconds)
        win = kind.window(args.seconds, null)
        lat = np.array([r["end"] - r["due"] for r in win.requests])
        q = max(1, lat.size // 4)
        row = {"fraction": f, "rate": f * mu,
               "per_s": lat.size / win.seconds,
               "p50_ms": float(np.median(lat)) * 1e3,
               "p95_ms": win.e2e["query_p95_ms"],
               "first_quarter_ms": float(np.mean(lat[:q])) * 1e3,
               "last_quarter_ms": float(np.mean(lat[-q:])) * 1e3,
               "failed": win.failed}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
