"""Observability overhead gate (DESIGN.md §19).

Three claims are gated, written to ``BENCH_obs.json``:

- **enabled overhead <= 5%** — with ``repro.obs`` fully enabled (metrics
  + spans + quality gauges), the ingest (``SketchIndex.add_many``) and
  all-pairs hot paths must cost at most ``OVERHEAD_GATE`` times their
  disabled wall time.  Measured as the *median of per-round ratios* over
  paired interleaved rounds (disabled then enabled inside each round), so
  clock drift and thermal state cancel instead of biasing one arm.
- **disabled path is structurally free** — while disabled every accessor
  must return the shared no-op singletons and a hot loop through the full
  accessor surface must not allocate per call (asserted under
  ``tracemalloc``; a timing "zero" would be unmeasurable noise, identity
  + allocation checks are exact).
- **canary flags injected shard loss** — the error-budget SLO gauge must
  flip to violation when half the shards of a
  :class:`~repro.serve.resilience.ResilientSketchIndex` are killed (the
  silent-accuracy-fault detection the whole quality pillar exists for).

Standalone entry point:

    PYTHONPATH=src python -m benchmarks.obs_overhead --json-out BENCH_obs.json
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import tracemalloc

import numpy as np
import jax

from repro import obs
from repro.obs.metrics import NOOP_COUNTER, NOOP_GAUGE, NOOP_HISTOGRAM
from repro.obs.quality import CanaryMonitor
from repro.obs.tracing import NOOP_SPAN
from repro.serve import ResilientSketchIndex, RetryPolicy, SketchIndex

from .common import Csv

OVERHEAD_GATE = 1.05
ALLOC_GATE_BYTES = 2048         # tracemalloc bookkeeping noise ceiling
# (D rows, n coords, m samples, paired rounds, all_pairs calls per side)
QUICK_POINT = (48, 1 << 10, 128, 9, 3)
FULL_POINT = (128, 1 << 12, 128, 15, 3)


def _build(D: int, n: int, m: int, rng) -> SketchIndex:
    idx = SketchIndex(m=m, n_buckets=2 * m, seed=11)
    idx.add_many([f"v{d}" for d in range(D)],
                 rng.standard_normal((D, n)).astype(np.float32))
    return idx


def _time_ingest(D: int, n: int, m: int, V: np.ndarray) -> float:
    idx = SketchIndex(m=m, n_buckets=2 * m, seed=11)
    t0 = time.perf_counter()
    idx.add_many([f"v{d}" for d in range(D)], V)
    return time.perf_counter() - t0


def _time_all_pairs(idx: SketchIndex, calls: int) -> float:
    t0 = time.perf_counter()
    for _ in range(calls):
        jax.block_until_ready(idx.all_pairs())
    return time.perf_counter() - t0


def _paired_rounds(D: int, n: int, m: int, rounds: int, calls: int):
    """Interleaved disabled/enabled measurement rounds; returns per-round
    (ingest_ratio, all_pairs_ratio) lists."""
    rng = np.random.default_rng(31)
    V = rng.standard_normal((D, n)).astype(np.float32)
    obs.disable()
    ap_idx = _build(D, n, m, rng)       # shared read-path corpus
    # warmup: compile every kernel on both paths before any timing
    _time_ingest(D, n, m, V)
    _time_all_pairs(ap_idx, 1)
    ingest_ratios, ap_ratios = [], []
    for _ in range(rounds):
        obs.disable()
        ing_off = _time_ingest(D, n, m, V)
        ap_off = _time_all_pairs(ap_idx, calls)
        obs.enable()
        ing_on = _time_ingest(D, n, m, V)
        ap_on = _time_all_pairs(ap_idx, calls)
        obs.reset()                     # bound registry/ring growth
        ingest_ratios.append(ing_on / ing_off)
        ap_ratios.append(ap_on / ap_off)
    obs.disable()
    return ingest_ratios, ap_ratios


def _disabled_structural() -> dict:
    """Identity + zero-allocation checks for the disabled path."""
    obs.disable()
    singletons = (obs.counter("repro_bench_total") is NOOP_COUNTER
                  and obs.gauge("repro_bench") is NOOP_GAUGE
                  and obs.histogram("repro_bench_s") is NOOP_HISTOGRAM
                  and obs.span("bench") is NOOP_SPAN
                  and obs.op("bench") is NOOP_SPAN
                  and obs.engine_op("bench", False) is NOOP_SPAN)

    def hot():
        for _ in range(1000):
            obs.counter("repro_bench_total").inc()
            with obs.op("bench.op") as sp:
                sp.set("k", 1)
    hot()
    tracemalloc.start()
    base, _ = tracemalloc.get_traced_memory()
    hot()
    now, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    grown = now - base
    return {"singletons": bool(singletons), "alloc_bytes": int(grown),
            "ok": bool(singletons and grown < ALLOC_GATE_BYTES)}


def _canary_chaos(n: int = 1024, shards: int = 4, m: int = 256) -> dict:
    """Kill half the shards; the canary error budget must blow."""
    obs.enable()
    idx = ResilientSketchIndex(n, num_shards=shards, m=m, n_buckets=2 * m,
                               seed=11,
                               retry=RetryPolicy(attempts=1, deadline=None),
                               sleep=lambda s: None)
    ones = np.ones(n, np.float32)
    idx.add("target", ones)
    mon = CanaryMonitor.from_vectors(idx, [("ones", ones, "target", ones)],
                                     registry=obs.registry(), m=m)
    healthy = mon.check()[0]
    for p in range(shards // 2):
        idx.kill_shard(p, "obs_overhead chaos")
    degraded = mon.check()[0]
    out = {
        "healthy_ratio": healthy.budget_ratio,
        "degraded_ratio": degraded.budget_ratio,
        "slo_ok_gauge": obs.registry().value("repro_canary_slo_ok"),
        "ok": bool(not healthy.violated and degraded.violated
                   and obs.registry().value("repro_canary_slo_ok") == 0.0),
    }
    obs.reset()
    obs.disable()
    return out


def run(quick: bool = True) -> Csv:
    csv = Csv()
    was_enabled = obs.enabled()
    D, n, m, rounds, calls = QUICK_POINT if quick else FULL_POINT

    ingest_ratios, ap_ratios = _paired_rounds(D, n, m, rounds, calls)
    med_ingest = float(np.median(ingest_ratios))
    med_ap = float(np.median(ap_ratios))
    csv.add(f"obs/overhead_D{D}_n{n}_m{m}/ingest", 0.0,
            f"median_ratio={med_ingest:.4f};rounds={rounds}")
    csv.add(f"obs/overhead_D{D}_n{n}_m{m}/all_pairs", 0.0,
            f"median_ratio={med_ap:.4f};rounds={rounds}")
    csv.add("obs/validate/ingest_overhead_le_5pct", 0.0,
            ("PASS" if med_ingest <= OVERHEAD_GATE else "FAIL")
            + f";median_ratio={med_ingest:.4f};gate={OVERHEAD_GATE}")
    csv.add("obs/validate/all_pairs_overhead_le_5pct", 0.0,
            ("PASS" if med_ap <= OVERHEAD_GATE else "FAIL")
            + f";median_ratio={med_ap:.4f};gate={OVERHEAD_GATE}")

    structural = _disabled_structural()
    csv.add("obs/validate/disabled_path_free", 0.0,
            ("PASS" if structural["ok"] else "FAIL")
            + f";singletons={structural['singletons']}"
            f";alloc_bytes={structural['alloc_bytes']}")

    canary = _canary_chaos()
    csv.add("obs/validate/canary_flags_shard_loss", 0.0,
            ("PASS" if canary["ok"] else "FAIL")
            + f";healthy_ratio={canary['healthy_ratio']:.3f}"
            f";degraded_ratio={canary['degraded_ratio']:.3f}")

    csv.results = {
        "point": {"D": D, "n": n, "m": m, "rounds": rounds,
                  "all_pairs_calls": calls},
        "ingest_ratios": ingest_ratios,
        "all_pairs_ratios": ap_ratios,
        "median_ingest_ratio": med_ingest,
        "median_all_pairs_ratio": med_ap,
        "disabled_structural": structural,
        "canary_chaos": canary,
    }
    if was_enabled:                     # run.py --obs owns the switch
        obs.enable()
    return csv


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--json-out", default="BENCH_obs.json")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    csv = run(quick=not args.full)
    payload = {
        "benchmark": "obs_overhead",
        "backend": jax.default_backend(),
        "gates": {"overhead_ratio": OVERHEAD_GATE,
                  "disabled_alloc_bytes": ALLOC_GATE_BYTES,
                  "canary_flags_fault": True},
        **csv.results,
        "rows": [{"name": n, "us_per_call": u, "derived": d}
                 for n, u, d in csv.rows],
    }
    with open(args.json_out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    print(f"# wrote {args.json_out}")
    failures = [(n, d) for n, _, d in csv.rows
                if "/validate/" in n and "FAIL" in d]
    if failures:
        print(f"# VALIDATION FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
