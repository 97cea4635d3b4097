#!/usr/bin/env python3
"""Find the highest request rate a cell sustains (its knee), on the chip.

    python bench/sweep.py --workload tpch-z2-m512.query --seed 7 --seconds 20

Sets the cell up once through its traffic kind, serves requests back to
back for ``--seconds`` (the closed-loop rate ``mu``), then offers open-loop
load at fractions of ``mu`` and prints, per rate, the completions per
second, the median latency, the kind's end-to-end numbers, and the latency
growth from the first to the last quarter of the window (a growing
backlog).  The sustained rate is written into the cell's traffic file by
hand.  Only a kind with ``serve_one`` and ``schedule`` (``bench.kinds``)
can be swept; for any other the sweep exits non-zero.
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
    from bench import drive, spec
    cell = spec.load_cell(ROOT, args.workload)
    kind_cls = drive.traffic_kind(cell)
    if not (hasattr(kind_cls, "serve_one") and hasattr(kind_cls, "schedule")):
        print(f"sweep: {cell.name}'s traffic kind {cell.traffic['kind']!r} "
              "serves no single request closed loop (no serve_one and "
              "schedule), so it has no knee to sweep", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    import jax
    import numpy as np
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    kind = kind_cls(cell, args.seed)
    kind.setup(args.seconds)
    null = lambda name: contextlib.nullcontext()
    t0, n = drive.clock(), 0
    while drive.clock() - t0 < args.seconds:
        kind.serve_one(n)
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
               "p50_ms": float(np.median(lat)) * 1e3, **win.e2e,
               "first_quarter_ms": float(np.mean(lat[:q])) * 1e3,
               "last_quarter_ms": float(np.mean(lat[-q:])) * 1e3,
               "failed": win.failed}
        rows.append(row)
        print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
