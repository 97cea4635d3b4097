#!/usr/bin/env python3
"""Bring-up smoke of the sketch service on one TPU chip.

Runs the data-lake correlation-discovery deployment through the public
``repro.serve.SketchIndex`` surface at serving size: 8192 columns over a
key universe of 2^18, 8188 of them ingested with ``add_many`` in blocks of
256 columns (the fused Pallas build) and 4 with sparse ``add``s (the
served corpus is then exactly 8192 rows), 16 ``query`` calls in the
``plain`` and ``bias_aware`` modes, ``all_pairs()``, ``top_pairs(k=10)``
and a ``merge_from`` of a 512-column partition peer.  Every phase is
checked against an answer that does not come from the code under test:

a) the kernel result against the same call with ``use_pallas=False`` (the
   jnp/XLA oracle, on the chip): bit-exact for builds and merges, within a
   stated tolerance for estimates;
b) planted correlated pairs against exact numpy inner products, inside the
   Theorem-3 Chebyshev band (``repro.core.variance.error_guarantee``);
c) ``top_pairs`` recall 1.0 against ``all_pairs()`` + sort.

Phase wall times are host-clock set-up facts of this run, not benchmark
numbers.  For every kernel entry point a phase ran, the script compiles
that entry point on the phase's own arguments and requires
``tpu_custom_call`` in the compiled HLO.

    python chip_smoke.py [--seed N]                 # one chip, all phases
    python chip_smoke.py --four-chips [--seed N]    # partitioned build only

``--four-chips`` builds one (256, 2^20) block over a 4-device mesh
(``repro.distributed.partitioned_sketch_corpus_sharded``) and compares it
bit for bit with the one-device ``sketch_corpus(backend="pallas")`` build.

The last line of standard output is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Without a TPU, without the repository's ``src/`` beside this script, or
when a check or phase fails, the script exits non-zero and prints no such
line.  Everything runs in this one process.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

M, N_BUCKETS, SLOTS = 256, 512, 4          # SketchIndex defaults
TOL = 1e-4   # estimates: |kernel - oracle| <= TOL * (|oracle| + max|oracle|)
DELTA = 0.01  # Chebyshev band confidence for the planted pairs


@dataclasses.dataclass(frozen=True)
class Deployment:
    """Data-lake correlation discovery at the scale one chip holds."""

    columns: int = 8192       # served rows: dense add_many + sparse adds
    universe: int = 1 << 18
    block: int = 256          # columns per add_many call
    draws: int = 2048         # Zipf key draws per column (~1250 distinct)
    zipf: float = 1.1         # key popularity skew, shared across columns
    pairs: int = 8            # planted correlated pairs in the first block
    sparse_adds: int = 4
    queries: int = 8          # per mode
    merge_columns: int = 512
    seed: int = 0


    @property
    def dense(self) -> int:
        """Columns ingested with add_many."""
        return self.columns - self.sparse_adds


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(f"check failed: {what}")


def report(phase: str, seconds: float, facts: str) -> None:
    print(f"phase {phase}: {facts}; wall {seconds:.3f} s "
          "(host clock, set-up fact, not a benchmark)", flush=True)


# ---------------------------------------------------------------------------
# Data: sparse columns over Zipf-popular keys with Zipf-skewed values
# ---------------------------------------------------------------------------


def key_order(dep: Deployment) -> np.ndarray:
    """Popularity order of the key universe (shared by every column)."""
    return np.random.default_rng([dep.seed, 0]).permutation(dep.universe)


def column_block(dep: Deployment, b: int, order: np.ndarray) -> np.ndarray:
    """Dense (block, universe) f32 block ``b`` of the corpus.  Block 0 holds
    the planted pairs: column 2p+1 is a scaled, noisy copy of column 2p."""
    rng = np.random.default_rng([dep.seed, 1, b])
    rows = min(dep.block, dep.dense - b * dep.block)
    keys = order[(rng.zipf(dep.zipf, (rows, dep.draws)) - 1) % dep.universe]
    mag = rng.pareto(1.5, (rows, dep.draws)) + 1.0
    vals = (mag * rng.choice([-1.0, 1.0], (rows, dep.draws))).astype(np.float32)
    out = np.zeros((rows, dep.universe), np.float32)
    out[np.repeat(np.arange(rows), dep.draws), keys.ravel()] = vals.ravel()
    if b == 0:
        for p in range(dep.pairs):
            x = out[2 * p]
            noise = rng.standard_normal(dep.universe).astype(np.float32)
            out[2 * p + 1] = np.where(
                x != 0, (0.5 + 0.25 * p) * x + 0.2 * np.abs(x) * noise, 0.0)
    return out


def names_of(b: int, rows: int) -> list:
    return [f"col{b * 10**6 + i}" for i in range(rows)]


def blocks_of(idx) -> tuple:
    """The index's bucketized blocks over its occupied rows (host copies)."""
    D = len(idx)
    return (idx._idx[:D], idx._val[:D], idx._tau[:D], idx._dropped[:D])


def same_blocks(a, b) -> bool:
    return all(np.array_equal(x, y) for x, y in zip(blocks_of(a),
                                                     blocks_of(b)))


def close(got, want) -> bool:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = np.abs(want).max() if want.size else 0.0
    return bool(np.all(np.abs(got - want) <= TOL * (np.abs(want) + scale)))


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def phase_ingest(dep: Deployment):
    """add_many in blocks through the build kernels, and through the XLA
    formulation into an oracle index: the blocks must agree bit for bit."""
    from repro.serve import SketchIndex
    order = key_order(dep)
    idx = SketchIndex(M, n_buckets=N_BUCKETS, slots=SLOTS)
    oracle = SketchIndex(M, n_buckets=N_BUCKETS, slots=SLOTS)
    n_blocks = -(-dep.dense // dep.block)
    spent = 0.0
    for b in range(n_blocks):
        X = column_block(dep, b, order)
        names = names_of(b, X.shape[0])
        t0 = time.perf_counter()
        idx.add_many(names, X, use_pallas=True)
        spent += time.perf_counter() - t0
        oracle.add_many(names, X, use_pallas=False)
    check(same_blocks(idx, oracle),
          "add_many blocks: Pallas build == XLA build, bit-exact")
    report("add_many", spent,
           f"{len(idx)} columns x {dep.universe} keys in {n_blocks} blocks "
           f"of {dep.block}, dropped {idx.total_dropped}; "
           "bit-exact vs the XLA build")
    return idx


def sparse_columns(dep: Deployment, order: np.ndarray) -> list:
    rng = np.random.default_rng([dep.seed, 2])
    cols = []
    for _ in range(dep.sparse_adds):
        keys = order[(rng.zipf(dep.zipf, dep.draws) - 1) % dep.universe]
        keys = np.unique(keys).astype(np.int64)
        vals = (rng.pareto(1.5, keys.size) + 1.0).astype(np.float32)
        cols.append((keys, vals))
    return cols


def phase_sparse_adds(dep: Deployment, idx) -> None:
    """Sparse ``add`` (the reference top_k sketch of the given coordinates)
    must yield the rows the fused Pallas build gives the dense columns."""
    from repro.serve import SketchIndex
    cols = sparse_columns(dep, key_order(dep))
    names = [f"sparse{i}" for i in range(len(cols))]
    t0 = time.perf_counter()
    for name, (keys, vals) in zip(names, cols):
        idx.add(name, indices=keys, values=vals)
    spent = time.perf_counter() - t0
    dense = np.zeros((len(cols), dep.universe), np.float32)
    for i, (keys, vals) in enumerate(cols):
        dense[i, keys] = vals
    probe = SketchIndex(M, n_buckets=N_BUCKETS, slots=SLOTS)
    probe.add_many(names, dense, use_pallas=True)
    D = len(idx)
    rows = slice(D - len(cols), D)
    for mine, theirs in zip(blocks_of(idx), blocks_of(probe)):
        check(np.array_equal(mine[rows], theirs),
              "sparse add rows == Pallas build of the dense columns")
    report("add (sparse)", spent,
           f"{len(cols)} columns, {min(k.size for k, _ in cols)}-"
           f"{max(k.size for k, _ in cols)} nonzeros; bit-exact vs the "
           "Pallas build of the dense columns")


def query_vectors(dep: Deployment) -> np.ndarray:
    """Planted columns (strong partners in the corpus) and fresh columns."""
    X = column_block(dep, 0, key_order(dep))
    fresh = column_block(dataclasses.replace(dep, seed=dep.seed + 1, pairs=0),
                         0, key_order(dep))
    half = dep.queries // 2
    return np.concatenate([X[: 2 * half: 2], fresh[: dep.queries - half]])


def phase_queries(dep: Deployment, idx) -> None:
    """16 served queries (plain and bias_aware) against the jnp oracle."""
    Q = query_vectors(dep)
    spent, calls = 0.0, 0
    for mode in ("plain", "bias_aware"):
        for v in Q:
            t0 = time.perf_counter()
            got = idx.query(v, mode=mode)
            spent += time.perf_counter() - t0
            calls += 1
            want = idx.query(v, mode=mode, use_pallas=False)
            check([n for n, _ in got] == [n for n, _ in want],
                  "query returns every indexed column in order")
            check(close([e for _, e in got], [e for _, e in want]),
                  f"{mode} query: kernel == jnp oracle within {TOL}")
    report("query", spent,
           f"{calls} calls ({dep.queries} plain + {dep.queries} bias_aware) "
           f"against {len(idx)} columns; within {TOL} of the jnp oracle")


def phase_all_pairs(dep: Deployment, idx) -> np.ndarray:
    """All-pairs estimates: against the oracle, and the planted pairs
    against exact inner products inside the Chebyshev band."""
    from repro.core.variance import error_guarantee
    t0 = time.perf_counter()
    est = idx.all_pairs()
    spent = time.perf_counter() - t0
    want = idx.all_pairs(use_pallas=False)
    check(est.shape == (len(idx), len(idx)), "all_pairs shape")
    check(bool(np.all(np.isfinite(est))), "all_pairs finite")
    check(close(est, want), f"all_pairs: kernel == jnp oracle within {TOL}")
    X = column_block(dep, 0, key_order(dep)).astype(np.float64)
    worst = 0.0
    for p in range(dep.pairs):
        a, b = X[2 * p], X[2 * p + 1]
        exact = float(a @ b)
        band = float(error_guarantee(a.astype(np.float32),
                                     b.astype(np.float32), M, DELTA,
                                     method="priority"))
        err = abs(float(est[2 * p, 2 * p + 1]) - exact)
        check(err <= band, f"planted pair {p}: |est - exact| = {err} "
                           f"within the Chebyshev band {band}")
        worst = max(worst, err / band)
    report("all_pairs", spent,
           f"({len(idx)}, {len(idx)}) estimates; within {TOL} of the jnp "
           f"oracle; {dep.pairs} planted pairs inside the delta={DELTA} "
           f"band (worst {worst:.3f} of the half-width)")
    return est


def phase_top_pairs(dep: Deployment, idx, est: np.ndarray) -> None:
    """Bound-pruned top-k: recall 1.0 against all_pairs() + sort, and the
    scan's estimates against the oracle scan."""
    from repro.serve import DiscoveryEngine
    k = 10
    t0 = time.perf_counter()
    res = idx.top_pairs(k=k)
    spent = time.perf_counter() - t0
    names = idx._names
    iu, ju = np.triu_indices(est.shape[0], k=1)
    v = est[iu, ju]
    best = np.lexsort((ju, iu, -v))[:k]
    want = {(names[iu[o]], names[ju[o]]) for o in best}
    got = {(a, b) for a, b, _ in res.items}
    check(len(res.items) == k, "top_pairs returns k pairs")
    check(got == want, "top_pairs recall 1.0 vs all_pairs() + sort")
    ref = DiscoveryEngine(idx, use_pallas=False).top_pairs(k=k)
    check([(a, b) for a, b, _ in res.items]
          == [(a, b) for a, b, _ in ref.items],
          "top_pairs: kernel scan == oracle scan")
    check(close([e for *_, e in res.items], [e for *_, e in ref.items]),
          f"top_pairs estimates within {TOL} of the oracle scan")
    report("top_pairs", spent,
           f"k={k}, {res.stats.tiles_launched} of {res.stats.tiles_total} "
           "tile pairs launched; recall 1.0 vs all_pairs() + sort")


def phase_merge(dep: Deployment) -> None:
    """merge_from of a same-seed partition peer over the first
    ``merge_columns`` columns: kernel == oracle merge, and == the one-shot
    build of the whole columns when no bucket overflowed."""
    from repro.serve import SketchIndex
    order = key_order(dep)
    half = dep.universe // 2
    parts = {k: SketchIndex(M, n_buckets=N_BUCKETS, slots=SLOTS)
             for k in ("lo", "lo_ref", "hi", "full")}
    for b in range(-(-dep.merge_columns // dep.block)):
        X = column_block(dep, b, order)[: dep.merge_columns - b * dep.block]
        names = names_of(b, X.shape[0])
        lo, hi = X.copy(), X.copy()
        lo[:, half:] = 0.0
        hi[:, :half] = 0.0
        for key, block in (("lo", lo), ("lo_ref", lo), ("hi", hi),
                           ("full", X)):
            parts[key].add_many(names, block, use_pallas=True)
    t0 = time.perf_counter()
    parts["lo"].merge_from(parts["hi"], use_pallas=True)
    spent = time.perf_counter() - t0
    parts["lo_ref"].merge_from(parts["hi"], use_pallas=False)
    check(same_blocks(parts["lo"], parts["lo_ref"]),
          "merge_from: Pallas merge == jnp oracle merge, bit-exact")
    lossless = parts["lo"].total_dropped == parts["full"].total_dropped == 0
    if lossless:
        check(same_blocks(parts["lo"], parts["full"]),
              "lossless merge == one-shot build of the whole columns")
    report("merge_from", spent,
           f"{len(parts['lo'])} columns; bit-exact vs the oracle merge"
           + ("; == the one-shot build" if lossless else
              f"; {parts['lo'].total_dropped} overflow drops"))


def run_phases(dep: Deployment):
    """Every one-chip phase with its checks; returns the served index."""
    idx = phase_ingest(dep)
    phase_sparse_adds(dep, idx)
    phase_queries(dep, idx)
    est = phase_all_pairs(dep, idx)
    phase_top_pairs(dep, idx, est)
    del est
    phase_merge(dep)
    return idx


# ---------------------------------------------------------------------------
# Compiled-kernel inspection (TPU only)
# ---------------------------------------------------------------------------


def check_kernels_compiled(dep: Deployment, idx) -> None:
    """Compile each kernel entry point the phases ran, on the phase's own
    argument shapes, and require a Mosaic kernel in the compiled HLO."""
    import jax
    import jax.numpy as jnp
    from repro.core import priority_sketch
    from repro.kernels import (bucketize, build_priority_corpus,
                               estimate_all_pairs_bucketized,
                               estimate_tile_rows, merge_bucketized_corpora,
                               query_corpus, slot_inclusion_probs)
    corpus = idx._corpus()
    probs = slot_inclusion_probs(corpus)
    q = bucketize(priority_sketch(jnp.zeros((dep.universe,), jnp.float32)
                                  .at[0].set(1.0), M, idx.seed),
                  n_buckets=N_BUCKETS, slots=SLOTS)
    rows = jnp.zeros((64,), jnp.int32)
    block = jax.ShapeDtypeStruct((dep.block, dep.universe), jnp.float32)
    part = jax.tree.map(lambda x: x[: dep.merge_columns], corpus)
    entries = {
        "add_many (hash_rank_hist + rank_hist)": (
            lambda A: build_priority_corpus(A, M, idx.seed, use_pallas=True),
            (block,)),
        "query (all-pairs kernel, one query row)": (
            lambda q, c: query_corpus(q, c), (q, corpus)),
        "all_pairs (all-pairs kernel)": (
            lambda c: estimate_all_pairs_bucketized(c, c), (corpus,)),
        "top_pairs (all-pairs kernel, gathered tiles)": (
            lambda c, p, r: estimate_tile_rows(c.idx, c.val, p, c.idx, c.val,
                                               p, r, r, use_pallas=True),
            (corpus, probs, rows)),
        "merge_from (merge + rank_hist)": (
            lambda a, b: merge_bucketized_corpora(a, b, idx.seed, m=M,
                                                  use_pallas=True),
            (part, part)),
    }
    for name, (fn, args) in entries.items():
        text = jax.jit(fn).lower(*args).compile().as_text()
        found = "tpu_custom_call" in text
        print(f"kernel {name}: compiled HLO holds tpu_custom_call={found}",
              flush=True)
        check(found, f"{name} runs a compiled Mosaic kernel")


# ---------------------------------------------------------------------------
# Four chips: the partitioned build over a mesh
# ---------------------------------------------------------------------------


def four_chip_build(rows: int = 256, universe: int = 1 << 20,
                    seed: int = 0) -> str:
    """Partitioned (rows, universe) build over a 4-device mesh, bit-exact
    against the one-device ``sketch_corpus(backend="pallas")`` build."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import sketch_corpus
    from repro.distributed import partitioned_sketch_corpus_sharded
    devices = jax.devices()
    check(len(devices) >= 4, f"four devices, found {len(devices)}")
    dep = Deployment(columns=rows, universe=universe, block=rows, seed=seed,
                     pairs=0, sparse_adds=0)
    A = column_block(dep, 0, key_order(dep))
    mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
    t0 = time.perf_counter()
    sharded = partitioned_sketch_corpus_sharded(
        jax.device_put(A, NamedSharding(mesh, P(None, "data"))), M, seed,
        mesh=mesh)
    sharded = jax.tree.map(np.asarray, sharded)
    spent = time.perf_counter() - t0
    single = jax.tree.map(np.asarray, sketch_corpus(
        jax.device_put(A, devices[0]), M, seed, backend="pallas"))
    for field in ("idx", "val", "tau"):
        check(np.array_equal(getattr(sharded, field), getattr(single, field)),
              f"four-device build {field} == one-device build, bit-exact")
    facts = (f"({rows}, {universe}) block over a 4-device mesh; idx, val and "
             "tau bit-exact vs the one-device Pallas build")
    report("partitioned build", spent, facts)
    return facts


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the partitioned build over 4 devices")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, SRC)
    from repro.compile_cache import use_compile_cache
    cache = use_compile_cache(ROOT)
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    print(f"device {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache {cache}", flush=True)
    if args.four_chips:
        four_chip_build(seed=args.seed)
    else:
        dep = Deployment(seed=args.seed)
        print(f"deployment: {dep}", flush=True)
        check_kernels_compiled(dep, run_phases(dep))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
