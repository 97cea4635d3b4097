"""Streaming top-k correlation discovery: bound-pruned tile scans over the
sketch indexes (DESIGN.md §17).

The all-pairs path materializes the full (D1, D2) estimate matrix —
quadratic in corpus size and a non-starter at the million-column scale the
discovery workload (most-correlated column pairs across unjoined tables)
actually runs at.  This engine replaces "compute everything, then sort"
with "prune, scan, stream":

1. **Summaries.** Every indexed row carries two scalars maintained
   incrementally at ingest (``SketchIndex._refresh_row_stats``): the
   rescaled kept norm ``G`` and the plain kept norm ``N``
   (:func:`repro.core.variance.rescaled_kept_norms`).  For ANY pair the
   estimator's value — every realization, not just in expectation — obeys
   ``|est| <= min(G_a G_b, G_a N_b + N_a G_b)``
   (:func:`repro.core.variance.pair_estimate_ceiling`), so per-tile maxima
   of (G, N) give an admissible ceiling on anything a (tile, tile) kernel
   launch could produce.

2. **Bound-ordered scan.** Rows are tiled in descending-``G`` order
   (:class:`TileSummaries`), tile pairs are visited in descending ceiling
   order, and a streaming top-k heap's current k-th score is the pruning
   threshold: once the heap is full and the next ceiling falls below it,
   every remaining tile is provably incapable of contributing a top-k pair
   and the scan stops — no kernel launch, no estimate matrix.  Working set
   is O(D m) (corpus blocks + summaries + one tile buffer), never O(D^2).

3. **Sharded fan-out.** :class:`ShardedDiscoveryEngine` scans shard pairs
   concurrently with per-task partial heaps merged at the coordinator,
   each task guarded by :class:`repro.serve.resilience.RetryPolicy`
   semantics (retry/backoff/deadline, ``TimeoutError`` terminal) — a slow
   or dead shard degrades the answer (quantified ``coverage``) instead of
   stalling it (DESIGN.md §16).

4. **Dirty-tile invalidation.** Ingest refreshes per-row summaries for the
   touched rows only; :class:`TileSummaries` recomputes maxima only for
   tiles whose membership or member stats actually changed.
"""
from __future__ import annotations

import heapq
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Callable, Optional

import numpy as np
import jax.numpy as jnp

from repro import obs
from repro.core import priority_sketch
from repro.core.variance import chebyshev_estimate_ceiling
from repro.kernels import (bucketize, estimate_tile_rows, round_up_pow2,
                           slot_inclusion_probs)
from repro.kernels.dispatch import resolve_use_pallas
from repro.serve.resilience import RetryPolicy, ShardDownError, ShardHealth
from repro.serve.sketch_service import _row_summaries
from repro.serve.validation import check_vector

DEFAULT_TILE = 64


def _pair_ceiling_np(ga, na, gb, nb):
    """Numpy twin of :func:`repro.core.variance.pair_estimate_ceiling`
    (broadcasting outer products for the tile-pair ceiling matrix)."""
    return np.minimum(ga * gb, ga * nb + na * gb)


class TileSummaries:
    """Bound-ordered tile view of one index's (G, N) row summaries.

    Rows are ranked by descending ``G`` and partitioned into blocks of
    ``tile`` rows; each block carries its (max G, max N) — all a scan needs
    to ceiling-bound every estimate the block can produce.  ``refresh``
    is the dirty-tile half of DESIGN.md §17's invalidation contract: it
    no-ops when the index's ``summary_epoch`` is unchanged, and otherwise
    recomputes maxima only for tiles whose member set or member stats
    differ from the cached snapshot — an append of low-``G`` rows dirties
    only the trailing tiles, not the corpus.
    """

    def __init__(self, index, tile: int = DEFAULT_TILE):
        if tile < 1 or round_up_pow2(tile) != tile:
            raise ValueError(f"tile must be a positive power of two, "
                             f"got {tile}")
        self.index = index
        self.tile = tile
        self._epoch = -1
        self._tile_rows: list = []     # per tile: np array of original row ids
        self._g_snap: Optional[np.ndarray] = None
        self._n_snap: Optional[np.ndarray] = None
        self.tile_g = np.empty((0,), np.float32)
        self.tile_n = np.empty((0,), np.float32)
        self.refreshes = 0             # cumulative tiles recomputed
        self.refresh_calls = 0         # refreshes that did any work

    @property
    def n_tiles(self) -> int:
        return len(self._tile_rows)

    def tile_rows(self, t: int) -> np.ndarray:
        """Original row ids of tile ``t`` (descending-G order)."""
        return self._tile_rows[t]

    def nbytes(self) -> int:
        snap = 0 if self._g_snap is None else \
            self._g_snap.nbytes + self._n_snap.nbytes
        return snap + self.tile_g.nbytes + self.tile_n.nbytes + \
            sum(r.nbytes for r in self._tile_rows)

    def refresh(self) -> None:
        if self.index.summary_epoch == self._epoch:
            return
        g_view, n_view = self.index.row_summaries()
        g = np.array(g_view, np.float32)   # snapshot: views mutate on ingest
        n = np.array(n_view, np.float32)
        D, T = g.shape[0], self.tile
        # stable: equal-G rows keep insertion order, so appends that don't
        # outrank existing rows leave leading tiles' membership untouched
        order = np.argsort(-g, kind="stable").astype(np.int64)
        nt = -(-D // T)
        rows = [order[t * T:(t + 1) * T] for t in range(nt)]
        tile_g = np.zeros((nt,), np.float32)
        tile_n = np.zeros((nt,), np.float32)
        d_old = 0 if self._g_snap is None else self._g_snap.shape[0]
        for t in range(nt):
            r = rows[t]
            clean = (t < len(self._tile_rows)
                     and r.shape == self._tile_rows[t].shape
                     and np.array_equal(r, self._tile_rows[t])
                     and (r.size == 0 or r.max() < d_old)
                     and np.array_equal(g[r], self._g_snap[r])
                     and np.array_equal(n[r], self._n_snap[r]))
            if clean:
                tile_g[t] = self.tile_g[t]
                tile_n[t] = self.tile_n[t]
            else:
                if r.size:
                    tile_g[t] = g[r].max()
                    tile_n[t] = n[r].max()
                self.refreshes += 1
        self._tile_rows = rows
        self.tile_g, self.tile_n = tile_g, tile_n
        self._g_snap, self._n_snap = g, n
        self._epoch = self.index.summary_epoch
        self.refresh_calls += 1


@dataclass
class ScanStats:
    """Accounting for one pruned scan (DESIGN.md §17): how many tile
    kernel launches the bound certificate saved, and the peak working-set
    bytes the scan ever held (corpus blocks + summaries + ceiling table +
    one tile buffer + heap — never the (D1, D2) estimate matrix)."""
    tiles_total: int = 0
    tiles_launched: int = 0
    tiles_pruned: int = 0
    kernel_launches: int = 0
    threshold: float = float("-inf")
    peak_bytes: int = 0
    summary_tiles_refreshed: int = 0


def _publish_scan(stats: ScanStats, scan: str) -> None:
    """Fold one scan's :class:`ScanStats` into the metrics registry
    (DESIGN.md §19) — the dataclass stays the caller-facing view, the
    registry gets the fleet-wide accumulation; no call-site plumbing."""
    if not obs.enabled():
        return
    r = obs.registry()
    lab = ("scan",)
    r.counter("repro_discovery_scans_total",
              "pruned discovery scans", lab).labels(scan).inc()
    r.counter("repro_discovery_tiles_total",
              "candidate tile(-pair)s considered", lab
              ).labels(scan).inc(stats.tiles_total)
    r.counter("repro_discovery_tiles_launched_total",
              "tile kernel launches actually made", lab
              ).labels(scan).inc(stats.tiles_launched)
    r.counter("repro_discovery_tiles_pruned_total",
              "tile(-pair)s skipped by the bound certificate", lab
              ).labels(scan).inc(stats.tiles_pruned)
    r.counter("repro_discovery_kernel_launches_total",
              "estimate_tile_rows dispatches", lab
              ).labels(scan).inc(stats.kernel_launches)
    r.gauge("repro_discovery_peak_bytes",
            "peak working-set bytes of the last scan", lab
            ).labels(scan).set(stats.peak_bytes)
    r.gauge("repro_discovery_summary_tiles_refreshed",
            "cumulative dirty-tile summary refreshes at the last scan",
            lab).labels(scan).set(stats.summary_tiles_refreshed)


@dataclass
class DiscoveryResult:
    """Top-k discovery answer.  ``items`` is descending by score:
    ``(name_a, name_b, estimate)`` for pair scans, ``(name, estimate)``
    for query scans.  When shards were lost, ``degraded`` flags it,
    ``coverage`` is the fraction of candidate pairs (rows, for query
    scans) actually scanned, and ``lost_pairs``/``lost_shards`` name the
    shard(-pair) tasks that failed their retries (DESIGN.md §16)."""
    items: list
    stats: ScanStats
    degraded: bool = False
    coverage: float = 1.0
    lost_pairs: tuple = ()
    lost_shards: tuple = ()
    audit: Optional[list] = None

    @property
    def pairs(self) -> list:
        return self.items


def _push_candidates(heap, k, scores, payloads):
    """Stream tile candidates into the bounded min-heap."""
    for sc, payload in zip(scores, payloads):
        item = (float(sc),) + payload
        if len(heap) < k:
            heapq.heappush(heap, item)
        elif item > heap[0]:
            heapq.heappushpop(heap, item)


def _drain(heap) -> list:
    """Heap -> descending score, ties broken by ascending ids (matching the
    index ``query(top_k=...)`` tie contract)."""
    return sorted(heap, key=lambda it: (-it[0],) + it[1:-1])


class DiscoveryEngine:
    """Bound-pruned streaming top-k discovery over one
    :class:`~repro.serve.sketch_service.SketchIndex` (DESIGN.md §17).

    ``tile``: rows per scan tile (power of two).  ``use_pallas``: None =
    auto (Pallas kernel on TPU, fused XLA tile elsewhere).  ``ceiling``:
    ``"admissible"`` (default) prunes only on the deterministic certificate
    — lossless, exact top-k parity with ``all_pairs()`` + sort;
    ``"chebyshev"`` additionally applies the Theorem-3-style probabilistic
    ceiling at confidence ``1 - delta`` per pair — tighter pruning, recall
    no longer guaranteed 1.0.
    """

    def __init__(self, index, *, tile: int = DEFAULT_TILE,
                 use_pallas: Optional[bool] = None,
                 ceiling: str = "admissible", delta: float = 0.05):
        if ceiling not in ("admissible", "chebyshev"):
            raise ValueError(f"ceiling must be 'admissible' or 'chebyshev', "
                             f"got {ceiling!r}")
        self.index = index
        self.tile = tile
        self.ceiling = ceiling
        self.delta = delta
        self._use_pallas = resolve_use_pallas(use_pallas)
        self._summaries = TileSummaries(index, tile)
        self._lock = threading.Lock()
        self._dev_epoch = -1
        self._dev = None
        self._probs = None

    # -- device/summary preparation (idempotent, epoch-keyed) --------------

    def _prepare(self):
        with self._lock:
            self._summaries.refresh()
            ep = self.index.summary_epoch
            if self._dev_epoch != ep:
                self._dev = self.index._corpus()
                self._probs = slot_inclusion_probs(self._dev)
                self._dev_epoch = ep
        return self._dev, self._probs

    def _corpus_nbytes(self) -> int:
        return int(self._dev.idx.nbytes + self._dev.val.nbytes +
                   self._probs.nbytes)

    def tile_members(self, t: int) -> np.ndarray:
        """Original row ids of scan tile ``t`` (audit/introspection)."""
        return np.array(self._summaries.tile_rows(t))

    def _ceiling_matrix(self, other: "DiscoveryEngine") -> np.ndarray:
        sa, sb = self._summaries, other._summaries
        ceil = _pair_ceiling_np(sa.tile_g[:, None], sa.tile_n[:, None],
                                sb.tile_g[None, :], sb.tile_n[None, :])
        if self.ceiling == "chebyshev":
            cheb = np.asarray(chebyshev_estimate_ceiling(
                sa.tile_n[:, None], sb.tile_n[None, :], self.index.m,
                self.delta))
            ceil = np.minimum(ceil, cheb)
        return ceil

    def _pad_rows(self, rows: np.ndarray) -> np.ndarray:
        out = np.zeros((self.tile,), np.int32)  # pad id 0: masked host-side
        out[: rows.size] = rows
        return out

    # -- scans -------------------------------------------------------------

    def top_pairs(self, k: int = 10, *, absolute: bool = False,
                  audit: bool = False) -> DiscoveryResult:
        """Global top-k pairs of the index against itself (each unordered
        pair once, self-pairs excluded)."""
        with obs.op("serve.discovery.top_pairs") as sp:
            res = _pair_scan(self, self, k, absolute=absolute, audit=audit)
            sp.set("launched", res.stats.tiles_launched)
            sp.set("pruned", res.stats.tiles_pruned)
            _publish_scan(res.stats, "pairs")
            return res

    def top_k_for_query(self, vector, k: int = 10, *,
                        absolute: bool = False) -> DiscoveryResult:
        """Top-k indexed rows for one query vector: corpus tiles whose
        ceiling falls below the running k-th score are never launched."""
        with obs.op("serve.discovery.top_k_for_query") as sp:
            res = self._top_k_for_query(vector, k, absolute=absolute)
            sp.set("launched", res.stats.tiles_launched)
            sp.set("pruned", res.stats.tiles_pruned)
            _publish_scan(res.stats, "query")
            return res

    def _top_k_for_query(self, vector, k: int = 10, *,
                         absolute: bool = False) -> DiscoveryResult:
        index = self.index
        if not index._names:
            raise ValueError("discovery on an empty index: add vectors "
                             "before querying")
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        vector = check_vector(vector, "query vector", dim=index._dim,
                              nonfinite=index.nonfinite)
        sq = priority_sketch(jnp.asarray(vector), index.m, index.seed)
        q = bucketize(sq, n_buckets=index.n_buckets, slots=index.slots)
        q_val = np.asarray(q.val)[None]
        q_tau = np.asarray(q.tau).reshape(1)
        gq, nq = _row_summaries(q_val, q_tau)
        cb, pb = self._prepare()
        s = self._summaries
        stats = ScanStats(tiles_total=s.n_tiles,
                          summary_tiles_refreshed=s.refreshes)
        ceil = _pair_ceiling_np(float(gq[0]), float(nq[0]),
                                s.tile_g, s.tile_n)
        if self.ceiling == "chebyshev":
            ceil = np.minimum(ceil, np.asarray(chebyshev_estimate_ceiling(
                float(nq[0]), s.tile_n, index.m, self.delta)))
        order = np.argsort(-ceil, kind="stable")
        qi = jnp.asarray(np.asarray(q.idx)[None])
        qv = jnp.asarray(q_val)
        qp = slot_inclusion_probs(
            type(cb)(qi, qv, jnp.asarray(q_tau), jnp.zeros((1,), jnp.int32)))
        rows_q = jnp.zeros((1,), jnp.int32)
        heap: list = []
        tile_bytes = 0
        for t in order:
            c = float(ceil[t])
            if len(heap) == k and c < heap[0][0]:
                break
            rows = s.tile_rows(int(t))
            est = np.asarray(estimate_tile_rows(
                qi, qv, qp, cb.idx, cb.val, pb, rows_q,
                jnp.asarray(self._pad_rows(rows)),
                use_pallas=self._use_pallas))[0]
            stats.kernel_launches += 1
            stats.tiles_launched += 1
            tile_bytes = max(tile_bytes, 3 * est.nbytes)
            score = np.abs(est) if absolute else est
            nv = rows.size
            sel = np.arange(nv)
            if nv > k:
                sel = np.argpartition(-score[:nv], k - 1)[:k]
            _push_candidates(heap, k, score[sel],
                             [(int(rows[i]), float(est[i])) for i in sel])
        stats.tiles_pruned = stats.tiles_total - stats.tiles_launched
        stats.threshold = heap[0][0] if len(heap) == k else float("-inf")
        stats.peak_bytes = (self._corpus_nbytes() + s.nbytes() + ceil.nbytes
                            + tile_bytes + 64 * max(len(heap), 1))
        names = index._names
        items = [(names[rid], est) for _, rid, est in _drain(heap)]
        return DiscoveryResult(items=items, stats=stats)


def _pair_scan(ea: DiscoveryEngine, eb: DiscoveryEngine, k: int, *,
               absolute: bool = False, audit: bool = False,
               names_a: Optional[list] = None,
               names_b: Optional[list] = None) -> DiscoveryResult:
    """Bound-pruned scan over all (row of ``ea``) x (row of ``eb``) pairs;
    when both engines wrap the same index, each unordered pair is scored
    once and self-pairs are excluded.  The core of DESIGN.md §17."""
    symmetric = ea.index is eb.index
    if ea.tile != eb.tile:
        raise ValueError("engines must share a tile size to scan jointly")
    if k < 1:
        raise ValueError(f"k must be >= 1, got {k}")
    if not ea.index._names or not eb.index._names:
        raise ValueError("discovery on an empty index: add vectors first")
    ca, pa = ea._prepare()
    cb, pb = eb._prepare()
    sa, sb = ea._summaries, eb._summaries
    names_a = ea.index._names if names_a is None else names_a
    names_b = eb.index._names if names_b is None else names_b
    T = ea.tile

    ceil = ea._ceiling_matrix(eb)
    if symmetric:
        uu, vv = np.triu_indices(sa.n_tiles)
    else:
        uu, vv = np.indices(ceil.shape).reshape(2, -1)
    order = np.argsort(-ceil[uu, vv], kind="stable")
    uu, vv = uu[order], vv[order]

    stats = ScanStats(
        tiles_total=uu.size,
        summary_tiles_refreshed=sa.refreshes + (0 if symmetric
                                                else sb.refreshes))
    heap: list = []
    audit_log: Optional[list] = [] if audit else None
    tile_bytes = 0
    n_visited = 0
    for u, v, c in zip(uu, vv, ceil[uu, vv]):
        c = float(c)
        if len(heap) == k and c < heap[0][0]:
            break
        n_visited += 1
        rows_u, rows_v = sa.tile_rows(int(u)), sb.tile_rows(int(v))
        est = np.asarray(estimate_tile_rows(
            ca.idx, ca.val, pa, cb.idx, cb.val, pb,
            jnp.asarray(ea._pad_rows(rows_u)),
            jnp.asarray(eb._pad_rows(rows_v)),
            use_pallas=ea._use_pallas))
        stats.kernel_launches += 1
        score = np.abs(est) if absolute else est
        valid = np.zeros((T, T), bool)
        valid[: rows_u.size, : rows_v.size] = True
        if symmetric and u == v:
            # same tile both sides: strict original-id order dedupes and
            # drops self-pairs (u < v tiles have disjoint member sets)
            valid[: rows_u.size, : rows_v.size] = \
                rows_u[:, None] < rows_v[None, :]
        tile_bytes = max(tile_bytes,
                         3 * est.nbytes + valid.nbytes)
        flat = np.flatnonzero(valid.ravel())
        if flat.size:
            sflat = score.ravel()[flat]
            if flat.size > k:
                keep = np.argpartition(-sflat, k - 1)[:k]
                flat, sflat = flat[keep], sflat[keep]
            payloads = []
            for fi in flat:
                i, j = divmod(int(fi), T)
                aid, bid = int(rows_u[i]), int(rows_v[j])
                if symmetric and aid > bid:
                    aid, bid = bid, aid
                payloads.append((aid, bid, float(est[i, j])))
            _push_candidates(heap, k, sflat, payloads)
        if audit_log is not None:
            audit_log.append({"u": int(u), "v": int(v), "ceiling": c,
                              "launched": True})
    if audit_log is not None:
        for u, v, c in zip(uu[n_visited:], vv[n_visited:],
                           ceil[uu[n_visited:], vv[n_visited:]]):
            audit_log.append({"u": int(u), "v": int(v), "ceiling": float(c),
                              "launched": False})
    stats.tiles_launched = n_visited
    stats.tiles_pruned = stats.tiles_total - n_visited
    stats.threshold = heap[0][0] if len(heap) == k else float("-inf")
    corpus_bytes = ea._corpus_nbytes() + (0 if symmetric
                                          else eb._corpus_nbytes())
    stats.peak_bytes = (corpus_bytes + sa.nbytes()
                        + (0 if symmetric else sb.nbytes())
                        + ceil.nbytes + uu.nbytes + vv.nbytes
                        + tile_bytes + 80 * max(len(heap), 1))
    items = [(names_a[aid] if not symmetric else names_a[aid],
              names_b[bid], est)
             for _, aid, bid, est in _drain(heap)]
    return DiscoveryResult(items=items, stats=stats, audit=audit_log)


def _merge_stats(parts: list) -> ScanStats:
    out = ScanStats()
    for s in parts:
        out.tiles_total += s.tiles_total
        out.tiles_launched += s.tiles_launched
        out.tiles_pruned += s.tiles_pruned
        out.kernel_launches += s.kernel_launches
        out.peak_bytes += s.peak_bytes
        out.summary_tiles_refreshed += s.summary_tiles_refreshed
    return out


class ShardedDiscoveryEngine:
    """Guarded async fan-out of pruned scans over a
    :class:`~repro.serve.sketch_service.ShardedSketchIndex`.

    Shard-pair tasks (s <= t: within-shard pairs plus each cross-shard
    combination once) run concurrently; each task keeps a partial top-k
    heap, merged at the coordinator.  Every task is guarded by
    :class:`repro.serve.resilience.RetryPolicy` semantics — retry with
    exponential backoff under a per-call deadline, ``TimeoutError``
    terminal immediately — so a slow shard costs its own pairs (reported
    as ``coverage`` < 1 and ``lost_pairs``), never the whole answer
    (DESIGN.md §16, §17).  ``call_wrapper(shards, fn)`` is the
    fault-injection hook; ``kill_shard`` administratively drops a shard.
    """

    def __init__(self, sharded, *, tile: int = DEFAULT_TILE,
                 use_pallas: Optional[bool] = None,
                 ceiling: str = "admissible", delta: float = 0.05,
                 retry: Optional[RetryPolicy] = None,
                 call_wrapper: Optional[Callable] = None,
                 sleep: Callable[[float], None] = time.sleep,
                 clock: Callable[[], float] = time.monotonic,
                 max_workers: Optional[int] = None):
        self.sharded = sharded
        self.retry = retry if retry is not None else RetryPolicy()
        self.health = ShardHealth(sharded.num_shards, clock=clock)
        self._call_wrapper = call_wrapper
        self._sleep = sleep
        self._clock = clock
        self._max_workers = max_workers
        self._engines = [DiscoveryEngine(s, tile=tile, use_pallas=use_pallas,
                                         ceiling=ceiling, delta=delta)
                         for s in sharded._shards]

    def kill_shard(self, shard: int, reason: str = "killed") -> None:
        self.health.mark_down(shard, reason)

    def revive_shard(self, shard: int) -> None:
        self.health.beat(shard)

    def _guarded(self, shards: tuple, fn: Callable):
        """One task under RetryPolicy semantics (mirrors
        ``resilience._GuardedFanout._shard_call``, keyed by the shard
        tuple so cross-shard tasks degrade independently)."""
        policy = self.retry
        t0 = self._clock()
        delay = policy.base_delay
        last: Optional[BaseException] = None
        with obs.span("serve.discovery.task") as tsp:
            tsp.set("shards", list(shards))
            for attempt in range(max(policy.attempts, 1)):
                try:
                    obs.counter("repro_retry_attempts_total",
                                "guarded-call attempts",
                                ("surface",)).labels("discovery").inc()
                    if self._call_wrapper is not None:
                        out = self._call_wrapper(shards, fn)
                    else:
                        out = fn()
                    for p in shards:
                        self.health.beat(p)
                    return out
                except Exception as e:  # noqa: BLE001 — fault boundary
                    last = e
                    timed_out = isinstance(e, TimeoutError) or (
                        policy.deadline is not None
                        and self._clock() - t0 >= policy.deadline)
                    if timed_out:
                        obs.counter("repro_deadline_hits_total",
                                    "guarded calls terminated by timeout "
                                    "or deadline",
                                    ("surface",)).labels("discovery").inc()
                    if timed_out or attempt >= policy.attempts - 1:
                        break
                    obs.counter("repro_retry_backoffs_total",
                                "backoff sleeps between retries",
                                ("surface",)).labels("discovery").inc()
                    self._sleep(delay)
                    delay = min(delay * 2.0, policy.max_delay)
            obs.counter("repro_shard_down_total",
                        "guarded tasks that exhausted their retries",
                        ("surface",)).labels("discovery").inc()
            raise ShardDownError(
                f"discovery task over shards {shards} failed after "
                f"{attempt + 1} attempt(s): {last}") from last

    def _fan_out(self, tasks: dict):
        """Run ``{shards_tuple: thunk}`` concurrently; returns
        ``(results, lost)`` dicts."""
        live = {key: fn for key, fn in tasks.items()
                if all(self.health.is_up(p) for p in key)}
        lost = {key: "shard marked down" for key in tasks if key not in live}
        results: dict = {}
        if live:
            workers = self._max_workers or min(8, len(live))
            with ThreadPoolExecutor(max_workers=workers) as pool:
                futs = {key: pool.submit(self._guarded, key, fn)
                        for key, fn in live.items()}
                for key, fut in futs.items():
                    try:
                        results[key] = fut.result()
                    except ShardDownError as e:
                        lost[key] = str(e)
        return results, lost

    def top_pairs(self, k: int = 10, *, absolute: bool = False
                  ) -> DiscoveryResult:
        sharded = self.sharded
        if not sharded._names:
            raise ValueError("discovery on an empty index: add vectors "
                             "first")
        shards = sharded._shards
        # prepare serially: scans then only read shared per-engine state
        for s, e in enumerate(self._engines):
            if len(shards[s]):
                e._prepare()
        tasks = {}
        for s in range(sharded.num_shards):
            if not len(shards[s]):
                continue
            for t in range(s, sharded.num_shards):
                if not len(shards[t]):
                    continue
                ea, eb = self._engines[s], self._engines[t]
                tasks[(s, t)] = (
                    lambda ea=ea, eb=eb: _pair_scan(ea, eb, k,
                                                    absolute=absolute))
        results, lost = self._fan_out(tasks)
        # cross-shard scans emit (shard-s name, shard-t name): canonicalize
        # to global insertion order so results match all_pairs() + sort
        pos = {name: i for i, name in enumerate(sharded._names)}
        merged: list = []
        for r in results.values():
            for a, b, est in r.items:
                if pos[a] > pos[b]:
                    a, b = b, a
                merged.append((a, b, est))
        score = (lambda it: -abs(it[2])) if absolute else (lambda it: -it[2])
        merged.sort(key=lambda it: (score(it), pos[it[0]], pos[it[1]]))
        items = merged[:k]
        stats = _merge_stats([r.stats for r in results.values()])
        total = covered = 0
        sizes = [len(s) for s in shards]
        for s in range(sharded.num_shards):
            for t in range(s, sharded.num_shards):
                n = sizes[s] * (sizes[s] - 1) // 2 if s == t \
                    else sizes[s] * sizes[t]
                total += n
                if (s, t) in results or (s, t) not in lost:
                    covered += n
        down = self.health.down_shards()
        res = DiscoveryResult(
            items=items, stats=stats, degraded=bool(lost),
            coverage=covered / total if total else 1.0,
            lost_pairs=tuple(sorted(lost)),
            lost_shards=tuple(sorted(down)))
        self._publish_result(res, "pairs", publish_stats=True)
        return res

    def _publish_result(self, res: DiscoveryResult, scan: str,
                        *, publish_stats: bool) -> None:
        """Coverage / shard-health exposition for one fan-out (leaf query
        scans publish their own ScanStats; pair tasks bypass the engine
        wrappers, so the merged stats are published here once)."""
        if not obs.enabled():
            return
        if publish_stats:
            _publish_scan(res.stats, scan)
        obs.quality_monitor().observe_coverage(res.coverage, "discovery." + scan)
        obs.gauge("repro_shards_down",
                  "shards currently marked down",
                  ("surface",)).labels("discovery").set(
                      len(res.lost_shards))
        if res.degraded:
            obs.counter("repro_degraded_results_total",
                        "fan-out answers served with coverage < 1",
                        ("surface",)).labels("discovery." + scan).inc()

    def top_k_for_query(self, vector, k: int = 10, *,
                        absolute: bool = False) -> DiscoveryResult:
        sharded = self.sharded
        if not sharded._names:
            raise ValueError("discovery on an empty index: add vectors "
                             "first")
        shards = sharded._shards
        tasks = {}
        for s in range(sharded.num_shards):
            if not len(shards[s]):
                continue
            e = self._engines[s]
            tasks[(s,)] = (lambda e=e: e.top_k_for_query(vector, k,
                                                         absolute=absolute))
        results, lost = self._fan_out(tasks)
        pos = {name: i for i, name in enumerate(sharded._names)}
        merged: list = []
        for r in results.values():
            merged.extend(r.items)
        score = (lambda it: -abs(it[1])) if absolute else (lambda it: -it[1])
        merged.sort(key=lambda it: (score(it), pos[it[0]]))
        stats = _merge_stats([r.stats for r in results.values()])
        lost_rows = sum(len(shards[key[0]]) for key in lost)
        D = len(sharded)
        down = self.health.down_shards()
        res = DiscoveryResult(
            items=merged[:k], stats=stats, degraded=bool(lost),
            coverage=(D - lost_rows) / D if D else 1.0,
            lost_pairs=tuple(sorted(lost)),
            lost_shards=tuple(sorted(down)))
        self._publish_result(res, "query", publish_stats=False)
        return res
