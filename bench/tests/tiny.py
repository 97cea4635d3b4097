"""A tiny copy of the benchmark for CPU tests: the repository's kinds of
traffic, metric readers and peaks beside tiny configurations, traffic mixes
and limits, written as a later PR would add a cell (files and entries
only).  One kind, ``exact_dot``, and its reference exist only in the tiny
root."""
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
    # at most 4 nonzeros a column, below m and within one bucket's slots:
    # every sketch keeps its whole column, so estimates are exact
    "tiny-exact": {
        "name": "tiny-exact", "source": "test fixture", "columns": 12,
        "universe": 64, "nonzeros": 4, "precision": "float32",
        "index": {"m": 8, "n_buckets": 16, "slots": 4, "head_h": 2,
                  "seed": 11}},
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
    "tiny_exact": {"kind": "exact_dot", "pool": 4},
}
QUERY_LIMITS = {"est_gap": 1e-4, "topk_shortfall": 1e-4}
INGEST_LIMITS = {"entries_differ": 1e-3, "tau_gap": 1e-5}
CELLS = {
    "tiny-tpch.query": ("tiny-tpch", "tiny_query", QUERY_LIMITS),
    "tiny-tpch.topk": ("tiny-tpch", "tiny_topk", QUERY_LIMITS),
    "tiny-tpch.ingest": ("tiny-tpch", "tiny_ingest", INGEST_LIMITS),
    "tiny-exact.dot": ("tiny-exact", "tiny_exact", {"dot_gap": 1e-5}),
}

# A kind and its reference that only the tiny root holds.
ROOT_ONLY = {
    "kinds/exact_dot.py": '''"""``exact_dot``: closed-loop ``SketchIndex.query`` of sparse vectors
that every sketch keeps whole, checked against their exact inner
products."""
import numpy as np

from bench import drive, spec


class ExactDot:
    def __init__(self, cell, seed):
        self.config, self.traffic, self.seed = cell.config, cell.traffic, seed
        self.ref = spec.load_file(cell.bench_dir, "exact_dot_reference.py")

    def _vectors(self, rng, rows):
        n, k = self.config["universe"], self.config["nonzeros"]
        out = np.zeros((rows, n), np.float32)
        for r in range(rows):
            out[r, rng.choice(n, k, replace=False)] = rng.uniform(0.5, 2, k)
        return out

    def setup(self, seconds, *, program=True):
        rng = np.random.default_rng([self.seed, 9])
        self.corpus = self._vectors(rng, self.config["columns"])
        self.pool = self._vectors(rng, self.traffic["pool"])
        if not program:
            return
        from repro.serve import SketchIndex
        p = self.config["index"]
        self.index = SketchIndex(p["m"], n_buckets=p["n_buckets"],
                                 slots=p["slots"], seed=p["seed"],
                                 head_h=p["head_h"])
        self.index.add_many([f"c{j}" for j in range(len(self.corpus))],
                            self.corpus)
        self.index.query(self.pool[0])

    def window(self, seconds, ann):
        self.answers, reqs = [], []
        t0 = drive.clock()
        with ann("bench.window"):
            while drive.clock() - t0 < seconds:
                s = drive.clock()
                v = self.pool[len(self.answers) % len(self.pool)]
                with ann("bench.serve"):
                    self.answers.append(self.index.query(v))
                reqs.append({"start": s - t0, "end": drive.clock() - t0})
        lat = [r["end"] - r["start"] for r in reqs]
        return drive.Window(
            seconds=drive.clock() - t0, attempted=len(reqs), failed=0,
            e2e={"query_p95_ms": float(np.percentile(lat, 95)) * 1e3},
            requests=reqs, facts={})

    def _compare(self, answers, exact):
        worst = 0.0
        for r, items in enumerate(answers):
            want = exact[r % len(exact)]
            got = dict(items)
            est = [got.get(f"c{j}", np.inf) for j in range(want.size)]
            worst = max(worst, float(drive.gap(
                est, want, np.abs(want).max()).max()))
        return {"dot_gap": worst}

    def check(self):
        return self._compare(self.answers, self.ref.dots(self.corpus,
                                                         self.pool))

    def control_numbers(self):
        ctl = self.ref.dots(self.corpus, self.pool, "bfloat16")
        answers = [[(f"c{j}", e) for j, e in enumerate(row)] for row in ctl]
        return self._compare(answers, self.ref.dots(self.corpus, self.pool))


Kind = ExactDot
''',
    "exact_dot_reference.py": '''"""Exact inner products of every query with every column, in float64;
``bfloat16`` rounds the inputs (float32 sums) for the control."""
import ml_dtypes
import numpy as np


def dots(corpus, queries, precision="float64"):
    if precision == "bfloat16":
        r = lambda x: x.astype(ml_dtypes.bfloat16).astype(np.float32)
        return (r(queries) @ r(corpus).T).astype(np.float64)
    return queries.astype(np.float64) @ corpus.astype(np.float64).T
''',
}


def _dump(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def make_root(dst: str) -> str:
    """A checkout-like directory holding the repository's BENCHMARK.json
    with the tiny configurations and cells added, and ``bench/`` with its
    kinds, metric readers, peaks, the tiny files and ``ROOT_ONLY``."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    b = os.path.join(dst, "bench")
    for sub in ("kinds", "metrics", "configs", "traffic", "limits"):
        shutil.copytree(os.path.join(BENCH, sub), os.path.join(b, sub),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(BENCH, "peaks.json"), b)
    for rel, text in ROOT_ONLY.items():
        with open(os.path.join(b, rel), "w") as f:
            f.write(text)
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
