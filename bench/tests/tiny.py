"""A tiny copy of the benchmark for CPU tests: the repository's metric
readers and peaks beside tiny configurations, traffic mixes and limits,
written as a later PR would add a cell (files and entries only)."""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TINY_INDEX = {"m": 32, "n_buckets": 64, "slots": 4, "head_h": 4, "seed": 11}
CONFIGS = {
    "tiny-tpch": {
        "name": "tiny-tpch", "source": "test fixture", "columns": 40,
        "precision": "float32", "index": TINY_INDEX, "ingest_block": 16,
        "data": {"generator": "tpch", "universe": 8192, "parts": 6000,
                 "rows_per_day": 3000, "zipf": 2.0}},
}
TRAFFIC = {
    "tiny_query": {"kind": "open_query", "entry": "query",
                   "args": {"top_k": 5, "mode": "plain"}, "k": 5,
                   "rate_per_s": 8.0, "pool_indexed": 4, "pool_fresh": 4,
                   "warmup": 2, "late_limit_s": 60},
    "tiny_topk": {"kind": "open_query", "entry": "top_k_for_query",
                  "args": {"k": 5}, "k": 5, "rate_per_s": 8.0,
                  "pool_indexed": 4, "pool_fresh": 4, "warmup": 2,
                  "late_limit_s": 60},
    "tiny_ingest": {"kind": "bulk_ingest", "block": 8, "pool": 2},
}
QUERY_LIMITS = {"est_gap": 1e-4, "topk_shortfall": 1e-4}
INGEST_LIMITS = {"entries_differ": 1e-3, "tau_gap": 1e-5}
CELLS = {
    "tiny-tpch.query": ("tiny-tpch", "tiny_query", QUERY_LIMITS),
    "tiny-tpch.topk": ("tiny-tpch", "tiny_topk", QUERY_LIMITS),
    "tiny-tpch.ingest": ("tiny-tpch", "tiny_ingest", INGEST_LIMITS),
}


def _dump(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(dst: str) -> str:
    """A checkout-like directory holding the repository's BENCHMARK.json
    with the tiny configurations and cells added, and ``bench/`` with its
    metric readers, peaks and the tiny files."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    b = os.path.join(dst, "bench")
    shutil.copytree(os.path.join(BENCH, "metrics"), os.path.join(b, "metrics"))
    for sub in ("configs", "traffic", "limits"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(b, sub))
    shutil.copy(os.path.join(BENCH, "peaks.json"), b)
    for name, cfg in CONFIGS.items():
        _dump(os.path.join(b, "configs", name + ".json"), cfg)
        spec["configs"].append({"name": name, "source": "test fixture",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "CPU test"})
    for name, tr in TRAFFIC.items():
        _dump(os.path.join(b, "traffic", name + ".json"), tr)
    for cell, (cfg, tr, limits) in CELLS.items():
        _dump(os.path.join(b, "limits", cell + ".json"), limits)
        spec["workloads"].append({"name": cell, "config": cfg, "traffic": tr,
                                  "chips": 1, "why": "CPU test"})
        kind = "query_p95_ms" if "ingest" not in tr else "ingest_cols_per_s"
        for m in spec["end_to_end"] + spec["per_layer"]:
            moved = m.get("moves", m["name"])
            if "workloads" in m and moved == kind:
                m["workloads"].append(cell)
    _dump(os.path.join(dst, "BENCHMARK.json"), spec)
    return dst
