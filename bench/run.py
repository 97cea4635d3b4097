#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark once.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is found by name in ``BENCHMARK.json`` at the root of the checkout
this file lies in.  The run needs a TPU with at least the chips the cell
asks for; without one it exits non-zero and prints no result.  The last
line of standard output is the result as one JSON object; the numbers the
correctness check compared are the last lines of standard error.  JAX's
persistent compilation cache is kept in ``<checkout>/.jax_cache``.
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import spec
    try:
        cell = spec.load_cell(ROOT, args.workload)
    except spec.SpecError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    try:
        from repro.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"bench: the program is not here ({e})", file=sys.stderr)
        return 2
    import jax
    use_compile_cache(ROOT)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"bench: no accelerator ({e})", file=sys.stderr)
        return 1
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 1
    spec.peaks(cell.bench_dir, devices[0].device_kind)  # unknown kind: error
    from bench.harness import run_cell
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
