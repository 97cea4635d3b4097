"""Sketch index service: the O(D^2 m) / query-vs-corpus serving path of the
paper's introduction, backed by the bucketized Pallas kernels.

Vectors are sketched once on ingestion (O(N) per vector — the paper's
headline construction cost, now actually linear via the fused batched build
pipeline, DESIGN.md §13) and bucketized *immediately* into pre-allocated
(capacity, B, S) blocks: each ``add`` is an amortized O(m) append, not a
full corpus rebuild.  ``add_many`` ingests a whole (D, n) block with one
batched build + one vmapped bucketize, feeding the bucketized blocks
directly — the heavy-ingestion path.  Sparse columns can skip the dense
materialization entirely by passing ``(indices, values)`` to ``add``.
Capacity grows by doubling and is always a power of two, so the jit'd
kernels see a fixed corpus shape between growth events — no recompilation
on each ingestion flush (DESIGN.md §4, §12).

A query answers all D inner-product estimates with one kernel launch;
``all_pairs`` emits the full D x D estimate matrix with one launch of the
tiled all-pairs kernel.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro import obs
from repro.core import INVALID_IDX, priority_sketch
from repro.serve.validation import (check_finite, check_nonfinite_policy,
                                    check_sparse, check_unique_name,
                                    check_unique_names, check_vector)
from repro.kernels import (BucketizedSketch, bucketize, bucketize_corpus,
                           build_priority_corpus,
                           estimate_all_pairs_bucketized,
                           merge_bucketized_corpora, round_up_pow2,
                           sketch_and_query)
from repro.matrix import (MatrixSketch, estimate_matrix_product,
                          estimate_matrix_products, priority_matrix_sketch)


def _row_summaries(val: np.ndarray, tau: np.ndarray):
    """Numpy twin of :func:`repro.core.variance.rescaled_kept_norms` for the
    ingest path: (R, B, S) values + (R,) taus -> per-row (G, N) ceiling
    summaries (DESIGN.md §17) without a device round-trip per add."""
    w = np.asarray(val, np.float32) ** 2
    tw = np.multiply(np.asarray(tau, np.float32)[:, None, None], w,
                     where=w > 0, out=np.ones_like(w))  # inf tau * 0 pad
    p = np.where(w > 0, np.minimum(1.0, tw), 1.0)
    g = np.sqrt(np.sum(w / (p * p), axis=(1, 2)))
    n = np.sqrt(np.sum(w, axis=(1, 2)))
    return g.astype(np.float32), n.astype(np.float32)


def _top_k_desc(est: np.ndarray, k: int) -> np.ndarray:
    """Indices of the ``k`` largest entries, descending, via partial
    selection (``np.argpartition``) — O(D + k log k), not a full O(D log D)
    sort of every estimate.  Deterministic tie contract: equal scores rank
    by ascending index, including ties that straddle the selection
    boundary (DESIGN.md §17)."""
    D = est.shape[0]
    k = min(int(k), D)
    if k <= 0:
        return np.empty((0,), np.int64)
    if k < D:
        part = np.argpartition(-est, k - 1)[:k]
        kth = est[part].min()
        # argpartition breaks boundary ties arbitrarily: rebuild the
        # selection as (everything strictly above the kth value) + (ties at
        # the kth value, lowest index first)
        above = np.flatnonzero(est > kth)
        tied = np.flatnonzero(est == kth)
        sel = np.concatenate([above, tied[: k - above.size]])
    else:
        sel = np.arange(D)
    # lexsort: primary descending score, secondary ascending index
    return sel[np.lexsort((sel, -est[sel]))]


class SketchIndex:
    """Incremental priority-sketch index.

    ``m``: samples per indexed vector; ``n_buckets``/``slots``: the
    bucketized serving layout (``n_buckets >= 2 m`` keeps overflow drops
    near zero, DESIGN.md §4); ``seed``: the shared coordination seed —
    indexes can only be queried against / merged with same-seed sketches;
    ``initial_capacity``: starting row allocation (grows by doubling);
    ``nonfinite``: ``"raise"`` (default) rejects NaN/Inf input with a clear
    error, ``"sanitize"`` zeroes it (weight-0 entries are never sampled) —
    the input-hardening contract of DESIGN.md §16.

    Estimation modes (DESIGN.md §20): ``query(..., mode=...)`` selects

    - ``"plain"`` — the Algorithm-2 bucketized kernel path (default);
    - ``"bias_aware"`` — the kernel path plus an exact-head correction:
      each row's top-``head_h`` coordinates (tracked at ingest) contribute
      their exact product with the known query vector instead of the
      sampled Horvitz-Thompson term, taming heavy-coordinate variance;
    - ``"private"`` — estimates against a differentially-private corpus
      release (``dp=DPParams(...)`` required).  The release is built
      lazily, charged **once** on the index's
      :class:`~repro.private.accountant.PrivacyAccountant` (disjoint rows
      compose in parallel), cached until the corpus mutates, and repeated
      queries against the cached release are free post-processing.
      ``privacy_budget`` pins a finite epsilon budget; overdrawing raises
      :class:`~repro.private.accountant.PrivacyBudgetExceeded` *before*
      any release is produced.  Release randomness is drawn from OS
      entropy, never from the public coordination ``seed`` (a
      seed-deriving reader could replay and invert the mechanism);
      ``dp_rng`` injects a deterministic generator for tests only.
    """

    def __init__(self, m: int = 256, *, n_buckets: int = 512, slots: int = 4,
                 seed: int = 11, initial_capacity: int = 64,
                 nonfinite: str = "raise", head_h: int = 16,
                 dp=None, privacy_budget: Optional[float] = None,
                 dp_rng=None):
        from repro.private import PrivacyAccountant
        self.m = m
        self.n_buckets = n_buckets
        self.slots = slots
        self.seed = seed
        self.nonfinite = check_nonfinite_policy(nonfinite)
        # unlike bias_aware_sketch (where the head eats into the m budget),
        # the serving head rides *beside* the sketch, so any h >= 0 is legal
        if head_h < 0:
            raise ValueError(f"need head_h >= 0, got {head_h}")
        self.head_h = int(head_h)
        self.dp = dp.validate() if dp is not None else None
        # DP release randomness is SECRET curator state: default to OS
        # entropy.  It must never be derived from the public sketch seed —
        # a reader knowing the seed could replay the survival coins /
        # decoys / noise and invert the release.  ``dp_rng`` is a
        # deterministic override for tests only.
        self._dp_rng = dp_rng
        self.accountant = PrivacyAccountant(epsilon_budget=privacy_budget)
        self._dim: Optional[int] = None  # universe size, fixed on first add
        self._name_set: set = set()
        self._names: list = []
        self._cap = round_up_pow2(initial_capacity)
        self._idx = np.full((self._cap, n_buckets, slots), INVALID_IDX,
                            np.int32)
        self._val = np.zeros((self._cap, n_buckets, slots), np.float32)
        # padding rows get tau=1 so their (all-INVALID) estimates are inert
        self._tau = np.ones((self._cap,), np.float32)
        self._dropped = np.zeros((self._cap,), np.int32)
        self._device_corpus: Optional[BucketizedSketch] = None
        # discovery ceiling summaries (DESIGN.md §17): per-row rescaled /
        # plain kept norms, maintained incrementally per touched row
        self._g = np.zeros((self._cap,), np.float32)
        self._kn = np.zeros((self._cap,), np.float32)
        self._stats_epoch = 0
        self._stats_rows_computed = 0  # introspection: dirty-row accounting
        self._discovery = None         # lazy DiscoveryEngine (tile caches)
        # bias-aware head state (§20): per-row exact top-head_h coords,
        # values, and whether each landed in the bucketized kept set
        self._head_idx = np.full((self._cap, self.head_h), -1, np.int64)
        self._head_val = np.zeros((self._cap, self.head_h), np.float32)
        self._head_kept = np.zeros((self._cap, self.head_h), bool)
        # private release cache: (PrivateSketch over rows [0, D)) or None
        self._private_release = None
        self._release_count = 0

    def __len__(self):
        return len(self._names)

    @property
    def capacity(self) -> int:
        return self._cap

    @property
    def total_dropped(self) -> int:
        """Entries lost to bucket overflow across all indexed vectors."""
        return int(self._dropped[: len(self._names)].sum())

    def _grow(self) -> None:
        new_cap = self._cap * 2

        def extend(arr, fill):
            out = np.full((new_cap,) + arr.shape[1:], fill, arr.dtype)
            out[: self._cap] = arr
            return out

        self._idx = extend(self._idx, INVALID_IDX)
        self._val = extend(self._val, 0)
        self._tau = extend(self._tau, 1)
        self._dropped = extend(self._dropped, 0)
        self._g = extend(self._g, 0)
        self._kn = extend(self._kn, 0)
        self._head_idx = extend(self._head_idx, -1)
        self._head_val = extend(self._head_val, 0)
        self._head_kept = extend(self._head_kept, False)
        self._cap = new_cap

    def _set_head_row(self, d: int, coords: np.ndarray,
                      vals: np.ndarray) -> None:
        """Record row ``d``'s exact head: the top-``head_h`` nonzero
        candidates by l2 weight, sorted by coordinate, plus whether each
        landed in the row's bucketized kept set (the bias-aware correction
        needs to know what the kernel will match).  Must run *after* the
        row's bucketized blocks are written."""
        h = self.head_h
        if h == 0:
            return
        coords = np.asarray(coords, np.int64)
        vals = np.asarray(vals, np.float32)
        live = vals != 0
        coords, vals = coords[live], vals[live]
        if coords.size > h:
            part = np.argpartition(-(vals.astype(np.float64) ** 2),
                                   h - 1)[:h]
            coords, vals = coords[part], vals[part]
        order = np.argsort(coords)
        coords, vals = coords[order], vals[order]
        k = coords.size
        self._head_idx[d, :k] = coords
        self._head_idx[d, k:] = -1
        self._head_val[d, :k] = vals
        self._head_val[d, k:] = 0
        row = self._idx[d].ravel()
        self._head_kept[d, :k] = np.isin(coords, row[row != INVALID_IDX])
        self._head_kept[d, k:] = False

    def _refresh_row_stats(self, lo: int, hi: int) -> None:
        """Recompute the ceiling summaries for rows [lo, hi) only — the
        dirty-row half of DESIGN.md §17's invalidation contract (tile maxima
        refresh lazily in :class:`repro.serve.discovery.TileSummaries`)."""
        if hi <= lo:
            return
        self._g[lo:hi], self._kn[lo:hi] = _row_summaries(
            self._val[lo:hi], self._tau[lo:hi])
        self._stats_rows_computed += hi - lo
        self._stats_epoch += 1

    def row_summaries(self):
        """Current per-row (G, N) ceiling summaries over the occupied
        prefix (read-only views; see DESIGN.md §17)."""
        D = len(self._names)
        return self._g[:D], self._kn[:D]

    @property
    def summary_epoch(self) -> int:
        """Bumps on every mutation that touches row summaries; consumers
        (tile-maxima caches) skip refresh entirely when unchanged."""
        return self._stats_epoch

    def add(self, name, vector: Optional[np.ndarray] = None, *,
            indices: Optional[np.ndarray] = None,
            values: Optional[np.ndarray] = None) -> None:
        """Sketch + bucketize one vector and append it in place: amortized
        O(m) — no re-bucketize of the existing corpus.

        Accepts either a dense ``vector`` or a pre-sparsified column as
        ``(indices, values)`` (ascending coordinates, e.g. np.nonzero
        order), which skips the dense materialization: the sketch hashes
        the given coordinates directly, so ingestion is O(nnz) not O(n).
        Sparse inputs are padded to the next power of two (padding weight 0
        can never be sampled) to bound jit recompiles across nnz values.
        """
        if (vector is None) == (indices is None and values is None):
            raise ValueError("pass either a dense vector or (indices, values)")
        check_unique_name(name, self._name_set)
        with obs.op("serve.index.add") as sp:
            if vector is not None:
                vector = check_vector(vector, f"vector {name!r}",
                                      dim=self._dim,
                                      nonfinite=self.nonfinite)
                self._dim = vector.shape[0]
                sk = priority_sketch(jnp.asarray(vector), self.m, self.seed)
            else:
                if indices is None or values is None:
                    raise ValueError(
                        "sparse input needs both indices and values")
                indices, values = check_sparse(indices, values, dim=self._dim,
                                               nonfinite=self.nonfinite)
                nnz = indices.shape[0]
                pad = round_up_pow2(max(nnz, 1)) - nnz
                # padding: value 0 -> weight 0 -> rank +inf, never selected
                vals_p = jnp.asarray(np.pad(values, (0, pad)))
                idx_p = jnp.asarray(np.pad(indices, (0, pad)))
                sk = priority_sketch(vals_p, self.m, self.seed, indices=idx_p)
                sp.set("sparse", True)
            b = bucketize(sk, n_buckets=self.n_buckets, slots=self.slots)
            if len(self._names) == self._cap:
                self._grow()
            d = len(self._names)
            self._idx[d] = np.asarray(b.idx)
            self._val[d] = np.asarray(b.val)
            self._tau[d] = float(b.tau)
            self._dropped[d] = int(b.dropped)
            if vector is not None:
                nz = np.flatnonzero(vector)
                self._set_head_row(d, nz, vector[nz])
            else:
                self._set_head_row(d, indices, values)
            self._names.append(name)
            self._name_set.add(name)
            self._refresh_row_stats(d, d + 1)
            self._device_corpus = None  # re-upload (not re-bucketize) lazily
            self._private_release = None  # corpus mutated: next release pays
            if obs.enabled():
                obs.quality_monitor().observe_ingest(self._tau[d], self._dropped[d])

    def add_many(self, names: Sequence, matrix: np.ndarray, *,
                 use_pallas: Optional[bool] = None) -> None:
        """Batch-ingest a (D, n) block: one fused linear-time build for all
        D vectors (``kernels.sketch_build``) + one vmapped bucketize, written
        straight into the pre-allocated bucketized blocks.

        Equivalent to D ``add`` calls (same sketches, same layout) but the
        construction is a single batched pipeline — no per-vector sort, no
        per-vector dispatch (DESIGN.md §13).  ``use_pallas`` picks the build
        kernels or their XLA formulation (None: ``kernels.dispatch``).
        """
        with obs.op("serve.index.add_many") as sp:
            with obs.span("serve.index.add_many.validate"):
                matrix = np.asarray(matrix, np.float32)
                if matrix.ndim != 2 or matrix.shape[0] != len(names):
                    raise ValueError("matrix must be (len(names), n)")
                check_unique_names(names, self._name_set)
                if self._dim is not None and matrix.shape[1] != self._dim:
                    raise ValueError(
                        f"matrix has {matrix.shape[1]} coordinates but "
                        f"this index was built over {self._dim}")
                matrix = check_finite(matrix, "ingest matrix",
                                      nonfinite=self.nonfinite)
            D = matrix.shape[0]
            if D == 0:
                return
            sp.set("rows", D)
            self._dim = matrix.shape[1]
            with obs.span("serve.index.add_many.upload"):
                dense = jnp.asarray(matrix)
            with obs.span("serve.index.add_many.dispatch"):
                sk = build_priority_corpus(dense, self.m, self.seed,
                                           use_pallas=use_pallas)
                bc = bucketize_corpus(sk, n_buckets=self.n_buckets,
                                      slots=self.slots)
            with obs.span("serve.index.add_many.fetch"):
                while len(self._names) + D > self._cap:
                    self._grow()
                d0 = len(self._names)
                self._idx[d0:d0 + D] = np.asarray(bc.idx)
                self._val[d0:d0 + D] = np.asarray(bc.val)
                self._tau[d0:d0 + D] = np.asarray(bc.tau)
                self._dropped[d0:d0 + D] = np.asarray(bc.dropped)
            with obs.span("serve.index.add_many.head"):
                for k in range(D):
                    nz = np.flatnonzero(matrix[k])
                    self._set_head_row(d0 + k, nz, matrix[k, nz])
                self._names.extend(names)
                self._name_set.update(names)
                self._refresh_row_stats(d0, d0 + D)
                self._device_corpus = None
                self._private_release = None
                if obs.enabled():
                    obs.quality_monitor().observe_ingest(
                        self._tau[d0:d0 + D], self._dropped[d0:d0 + D])

    def _rollback_last(self, k: int) -> None:
        """Undo the last ``k`` appended rows, restoring padding state
        (INVALID ids, tau=1) so the blocks stay inert.  Used by multi-shard
        ingest paths to unwind a partially-applied write — an all-or-nothing
        contract a caller cannot restore from outside (DESIGN.md §16)."""
        for _ in range(k):
            name = self._names.pop()
            self._name_set.discard(name)
            d = len(self._names)
            self._idx[d] = INVALID_IDX
            self._val[d] = 0
            self._tau[d] = 1
            self._dropped[d] = 0
            self._g[d] = 0
            self._kn[d] = 0
            self._head_idx[d] = -1
            self._head_val[d] = 0
            self._head_kept[d] = False
        self._stats_epoch += 1
        self._device_corpus = None
        self._private_release = None

    def _corpus(self) -> BucketizedSketch:
        """Occupied corpus prefix on device, rounded up to a power of two so
        the kernels see at most 2x the live rows.  Shape still only changes
        on doublings, so kernels never recompile per add."""
        if self._device_corpus is None:
            c = min(self._cap, max(round_up_pow2(max(len(self._names), 1)), 8))
            self._device_corpus = BucketizedSketch(
                jnp.asarray(self._idx[:c]), jnp.asarray(self._val[:c]),
                jnp.asarray(self._tau[:c]), jnp.asarray(self._dropped[:c]))
        return self._device_corpus

    def query(self, vector: np.ndarray, top_k: Optional[int] = None, *,
              mode: str = "plain", use_pallas: bool = True):
        """Inner-product estimates of ``vector`` against every indexed
        vector; one compiled program per corpus shape (query sketch,
        bucketize, one bucketized kernel launch).  ``mode`` selects the plain
        Algorithm-2 path, the bias-aware exact-head correction, or the
        DP-released corpus (class docstring; DESIGN.md §20).
        ``use_pallas=False`` runs the jnp oracle, as in :meth:`all_pairs`."""
        if mode not in ("plain", "bias_aware", "private"):
            raise ValueError(f"unknown mode {mode!r}; expected "
                             "'plain'|'bias_aware'|'private'")
        if not self._names:
            raise ValueError("query on an empty index: add vectors before "
                             "querying")
        with obs.op("serve.index.query") as sp:
            sp.set("rows", len(self._names))
            sp.set("mode", mode)
            with obs.span("serve.index.query.validate"):
                vector = check_vector(vector, "query vector", dim=self._dim,
                                      nonfinite=self.nonfinite)
            if mode == "private":
                est = self._query_private(vector)
            else:
                with obs.span("serve.index.query.upload"):
                    dense = jnp.asarray(vector)
                with obs.span("serve.index.query.dispatch"):
                    # uint32 on the host: the seeds the eager hash took,
                    # not only those a traced int32 holds
                    est, q = sketch_and_query(
                        dense, self._corpus(), np.uint32(self.seed), m=self.m,
                        n_buckets=self.n_buckets, slots=self.slots,
                        use_pallas=use_pallas)
                with obs.span("serve.index.query.fetch"):
                    est = np.asarray(est, np.float64)[: len(self._names)]
            with obs.span("serve.index.query.rank"):
                if mode == "bias_aware":
                    est = est + self._bias_aware_correction(
                        q, float(q.tau), vector)
                if top_k is None:
                    return list(zip(self._names, est.tolist()))
                order = _top_k_desc(est, top_k)
                return [(self._names[i], float(est[i])) for i in order]

    def _bias_aware_correction(self, q, tau_q: float,
                               vector: np.ndarray) -> np.ndarray:
        """Exact-head correction (DESIGN.md §20): per row, subtract the
        kernel's sampled Horvitz-Thompson contribution of the row's head
        coordinates (present only when a coordinate is kept in *both*
        bucketized structures) and add the exact product with the known
        query vector.  Unbiased for any ``head_h`` — the kernel term over
        non-head coordinates is untouched Algorithm 2."""
        D = len(self._names)
        if self.head_h == 0:
            return np.zeros(D)
        hi = self._head_idx[:D]
        valid = hi >= 0
        hic = np.where(valid, hi, 0)
        hv = self._head_val[:D].astype(np.float64)
        qv = np.where(valid, np.asarray(vector, np.float64)[hic], 0.0)
        exact = hv * qv
        # the kernel matched a head coord only if both bucketized kept sets
        # hold it (bucket placement is a pure function of the coordinate)
        q_idx = np.asarray(q.idx).ravel()
        kept_q = np.isin(hic, q_idx[q_idx != INVALID_IDX]) & valid
        kept = kept_q & self._head_kept[:D]
        wq, wr = qv * qv, hv * hv
        tau_r = self._tau[:D, None].astype(np.float64)
        with np.errstate(over="ignore", invalid="ignore"):
            p_q = np.where(wq > 0, np.minimum(1.0, tau_q * wq), 1.0)
            p_r = np.where(wr > 0, np.minimum(1.0, tau_r * wr), 1.0)
        p_min = np.minimum(p_q, p_r)
        sampled = np.where(kept & (exact != 0),
                           exact / np.where(p_min > 0, p_min, 1.0), 0.0)
        if obs.enabled():
            n_valid = int(valid.sum())
            obs.gauge("repro_biasaware_head_fraction",
                      "fraction of head entries the plain sketch kept").set(
                          float(kept[valid].mean()) if n_valid else 0.0)
        return (exact - sampled).sum(axis=1)

    def _ensure_private_release(self):
        """Lazy cached DP release of the whole corpus: one accountant
        charge per release epoch (rows are disjoint records — parallel
        composition); invalidated by any corpus mutation.  Strict: raises
        :class:`~repro.private.accountant.PrivacyBudgetExceeded` before
        producing anything when the budget would be overdrawn."""
        if self.dp is None:
            raise ValueError("private mode needs the index constructed "
                             "with dp=DPParams(...)")
        if self._private_release is None:
            from repro.private import private_release_corpus
            D = len(self._names)
            flat_idx = self._idx[:D].reshape(D, -1)
            flat_val = self._val[:D].reshape(D, -1)
            # compact the (B, S) blocks to m slots: valid coords sort ahead
            # of the INVALID sentinel (int32 max) and a row keeps <= m
            order = np.argsort(flat_idx, axis=1, kind="stable")
            idx_c = np.take_along_axis(flat_idx, order, axis=1)[:, : self.m]
            val_c = np.take_along_axis(flat_val, order, axis=1)[:, : self.m]
            self._release_count += 1
            rng = (self._dp_rng if self._dp_rng is not None
                   else np.random.default_rng())   # OS entropy, unseeded
            self._private_release = private_release_corpus(
                idx_c, val_c, self._tau[:D], self._dim, self.dp, rng=rng,
                accountant=self.accountant,
                label=f"index-release-{self._release_count}")
        return self._private_release

    def _query_private(self, vector: np.ndarray) -> np.ndarray:
        from repro.private import estimate_private_dense
        rel = self._ensure_private_release()
        est = np.asarray(estimate_private_dense(rel, vector))
        if obs.enabled():
            obs.gauge("repro_dp_epsilon_spent",
                      "cumulative epsilon charged on this index's "
                      "accountant").set(self.accountant.spent_epsilon)
        return est

    def all_pairs(self, *, use_pallas: bool = True) -> np.ndarray:
        """(D, D) inner-product estimate matrix over the indexed vectors in
        one tiled all-pairs kernel launch."""
        with obs.op("serve.index.all_pairs") as sp:
            c = self._corpus()
            est = np.asarray(estimate_all_pairs_bucketized(
                c, c, use_pallas=use_pallas))
            D = len(self._names)
            sp.set("rows", D)
            return est[:D, :D]

    def top_pairs(self, k: int = 10, **kw):
        """Streaming top-k most-similar pairs via the bound-pruned tile
        scan — O(D m) working set, never the (D, D) matrix (DESIGN.md §17).
        Returns a :class:`repro.serve.discovery.DiscoveryResult`."""
        from repro.serve.discovery import DiscoveryEngine
        if self._discovery is None:
            self._discovery = DiscoveryEngine(self)
        return self._discovery.top_pairs(k, **kw)

    def top_k_for_query(self, vector: np.ndarray, k: int = 10, **kw):
        """Bound-pruned top-k scan of one query against the corpus: corpus
        tiles whose ceiling falls below the running k-th score are never
        launched (DESIGN.md §17)."""
        from repro.serve.discovery import DiscoveryEngine
        if self._discovery is None:
            self._discovery = DiscoveryEngine(self)
        return self._discovery.top_k_for_query(vector, k, **kw)

    def merge_from(self, other: "SketchIndex", *,
                   use_pallas: Optional[bool] = None) -> None:
        """Merge a partition-peer index into this one, row by row, without
        leaving the bucketized layout (DESIGN.md §14).

        ``other`` must index the *same names in the same order*, each row
        sketching a disjoint coordinate partition of the same logical vector
        (e.g. two ingestion hosts each sketching half the rows of every
        column).  One ``kernels/sketch_merge`` launch merges all rows; raw
        vectors are never touched.  Exact up to bucket-overflow drops on
        either side (counted in ``total_dropped``; rare for the default
        ``n_buckets >= 2 m`` sizing, DESIGN.md §4) — an entry already lost
        to a full bucket cannot re-enter the union.  ``use_pallas`` picks
        the merge kernel or its jnp oracle (None: ``kernels.dispatch``).
        """
        if (other.m, other.n_buckets, other.slots, other.seed) != \
                (self.m, self.n_buckets, self.slots, self.seed):
            raise ValueError("indexes must share m/n_buckets/slots/seed "
                             "to merge")
        if other._names != self._names:
            raise ValueError("row names must align for a partition merge")
        D = len(self._names)
        if D == 0:
            return
        # a merged release would reveal both inputs' randomness: compose the
        # peer's privacy ledger sequentially (strict — raises, mutating
        # nothing, if the combined spend does not fit this budget)
        self.accountant.merge_from(other.accountant)
        with obs.op("serve.index.merge_from") as sp:
            sp.set("rows", D)
            mine = BucketizedSketch(
                jnp.asarray(self._idx[:D]), jnp.asarray(self._val[:D]),
                jnp.asarray(self._tau[:D]), jnp.asarray(self._dropped[:D]))
            theirs = BucketizedSketch(
                jnp.asarray(other._idx[:D]), jnp.asarray(other._val[:D]),
                jnp.asarray(other._tau[:D]), jnp.asarray(other._dropped[:D]))
            merged = merge_bucketized_corpora(mine, theirs, self.seed,
                                              m=self.m, use_pallas=use_pallas)
            self._idx[:D] = np.asarray(merged.idx)
            self._val[:D] = np.asarray(merged.val)
            self._tau[:D] = np.asarray(merged.tau)
            self._dropped[:D] = np.asarray(merged.dropped)
            if self.head_h:
                # disjoint coordinate partitions: the merged head is the
                # top-head_h of the union of both slices' heads, values
                # exact (a coord is nonzero in exactly one partition);
                # kept flags recompute against the merged blocks
                for d in range(D):
                    hm, ho = self._head_idx[d], other._head_idx[d]
                    coords = np.concatenate([hm[hm >= 0], ho[ho >= 0]])
                    vals = np.concatenate(
                        [self._head_val[d][hm >= 0],
                         other._head_val[d][ho >= 0]])
                    self._set_head_row(d, coords, vals)
            # every row's kept set / tau changed: all D rows are dirty
            self._refresh_row_stats(0, D)
            self._device_corpus = None
            self._private_release = None


class MatrixSketchStore:
    """Corpus of matrix sketches answering ``A^T B`` estimates
    (DESIGN.md §15).

    Matrices (n, d) with a shared column count ``d`` are row-sampled once on
    ingestion (``m`` rows each, the linear-time ``repro.matrix`` builders)
    and stored in pre-allocated ``(capacity, m)`` id / ``(capacity, m, d)``
    row blocks — amortized O(m d) per add, capacity doubling like
    :class:`SketchIndex`, so the batched estimators see a fixed corpus shape
    between growth events.  Reads:

    - ``product(a, b)`` — one stored-vs-stored ``A^T B`` estimate;
    - ``products(pairs)`` — a batch of stored pairs in one launch
      (``estimate_matrix_products``: the fused kernel on TPU, the vmapped
      join off-TPU);
    - ``query(matrix)`` — one query matrix against *every* stored sketch
      (the corpus-level workload: gradient co-occurrence, covariance and
      attention-score blocks against a library of feature matrices).

    All matrices must share ``d`` and the coordination ``seed``.
    """

    def __init__(self, m: int = 128, *, dim: int, seed: int = 11,
                 initial_capacity: int = 8, nonfinite: str = "raise"):
        if dim < 1:
            raise ValueError("dim must be >= 1")
        self.m = m
        self.dim = dim
        self.seed = seed
        self.nonfinite = check_nonfinite_policy(nonfinite)
        self._name_set: set = set()
        self._names: list = []
        self._cap = round_up_pow2(initial_capacity)
        self._idx = np.full((self._cap, m), INVALID_IDX, np.int32)
        self._rows = np.zeros((self._cap, m, dim), np.float32)
        # padding sketches get tau=1: all-INVALID ids match nothing
        self._tau = np.ones((self._cap,), np.float32)
        self._device: Optional[MatrixSketch] = None

    def __len__(self):
        return len(self._names)

    @property
    def capacity(self) -> int:
        return self._cap

    def _grow(self) -> None:
        new_cap = self._cap * 2

        def extend(arr, fill):
            out = np.full((new_cap,) + arr.shape[1:], fill, arr.dtype)
            out[: self._cap] = arr
            return out

        self._idx = extend(self._idx, INVALID_IDX)
        self._rows = extend(self._rows, 0)
        self._tau = extend(self._tau, 1)
        self._cap = new_cap

    def _sketch(self, matrix: np.ndarray) -> MatrixSketch:
        matrix = np.asarray(matrix, np.float32)
        if matrix.ndim != 2 or matrix.shape[1] != self.dim:
            raise ValueError(f"expected an (n, {self.dim}) matrix, got "
                             f"shape {matrix.shape}")
        matrix = check_finite(matrix, "matrix", nonfinite=self.nonfinite)
        return priority_matrix_sketch(jnp.asarray(matrix), self.m, self.seed)

    def add(self, name, matrix: np.ndarray) -> None:
        """Row-sample one (n, d) matrix and append it in place: amortized
        O(m d) storage writes, no re-layout of the existing corpus."""
        check_unique_name(name, self._name_set, what="store")
        sk = self._sketch(matrix)
        if len(self._names) == self._cap:
            self._grow()
        c = len(self._names)
        self._idx[c] = np.asarray(sk.row_idx)
        self._rows[c] = np.asarray(sk.rows)
        self._tau[c] = float(sk.tau)
        self._names.append(name)
        self._name_set.add(name)
        self._device = None   # re-upload (not re-sketch) lazily

    def _rollback_last(self, k: int) -> None:
        """Undo the last ``k`` appended sketches (multi-shard ingest
        rollback; see :meth:`SketchIndex._rollback_last`)."""
        for _ in range(k):
            name = self._names.pop()
            self._name_set.discard(name)
            c = len(self._names)
            self._idx[c] = INVALID_IDX
            self._rows[c] = 0
            self._tau[c] = 1
        self._device = None

    def _corpus(self) -> MatrixSketch:
        """Occupied corpus prefix on device, rounded to a power of two so
        batched estimators recompile only on doublings."""
        if self._device is None:
            c = min(self._cap, max(round_up_pow2(max(len(self._names), 1)),
                                   4))
            self._device = MatrixSketch(jnp.asarray(self._idx[:c]),
                                        jnp.asarray(self._rows[:c]),
                                        jnp.asarray(self._tau[:c]))
        return self._device

    def _pick(self, name) -> int:
        try:
            return self._names.index(name)
        except ValueError:
            raise KeyError(f"unknown matrix {name!r}") from None

    def product(self, name_a, name_b) -> np.ndarray:
        """(d, d) estimate of ``A^T B`` for two stored matrices."""
        ia, ib = self._pick(name_a), self._pick(name_b)
        sa = MatrixSketch(jnp.asarray(self._idx[ia]),
                          jnp.asarray(self._rows[ia]),
                          jnp.asarray(self._tau[ia]))
        sb = MatrixSketch(jnp.asarray(self._idx[ib]),
                          jnp.asarray(self._rows[ib]),
                          jnp.asarray(self._tau[ib]))
        return np.asarray(estimate_matrix_product(sa, sb))

    def products(self, pairs: Sequence) -> np.ndarray:
        """(len(pairs), d, d) estimates for a batch of stored-name pairs in
        one launch."""
        ia = np.array([self._pick(a) for a, _ in pairs], np.int64)
        ib = np.array([self._pick(b) for _, b in pairs], np.int64)
        SA = MatrixSketch(jnp.asarray(self._idx[ia]),
                          jnp.asarray(self._rows[ia]),
                          jnp.asarray(self._tau[ia]))
        SB = MatrixSketch(jnp.asarray(self._idx[ib]),
                          jnp.asarray(self._rows[ib]),
                          jnp.asarray(self._tau[ib]))
        return np.asarray(estimate_matrix_products(SA, SB))

    def query(self, matrix: np.ndarray) -> list:
        """Estimate ``Q^T A_c`` against every stored matrix in one launch;
        returns ``[(name, (d, d) ndarray), ...]`` in insertion order."""
        from repro.kernels.dispatch import resolve_use_pallas
        if not self._names:
            raise ValueError("query on an empty store: add matrices before "
                             "querying")
        sq = self._sketch(matrix)
        corpus = self._corpus()
        if resolve_use_pallas(None):
            # TPU kernel path: the batched kernel wants a materialized
            # (C, ...) query side; C identical copies is the v1 trade
            C = corpus.row_idx.shape[0]
            SQ = MatrixSketch(
                jnp.broadcast_to(sq.row_idx[None], (C,) + sq.row_idx.shape),
                jnp.broadcast_to(sq.rows[None], (C,) + sq.rows.shape),
                jnp.broadcast_to(jnp.reshape(sq.tau, (1,)), (C,)))
            est = np.asarray(estimate_matrix_products(SQ, corpus))
        else:
            # off-TPU: hold the query fixed (O(m d) query memory, no copies)
            est = np.asarray(jax.vmap(
                lambda i, r, t: estimate_matrix_product(
                    sq, MatrixSketch(i, r, t)))(
                        corpus.row_idx, corpus.rows, corpus.tau))
        return [(name, est[i]) for i, name in enumerate(self._names)]


class ShardedSketchIndex:
    """Corpus-dim sharded serving: rows scatter round-robin over per-shard
    ``SketchIndex`` block sets (one per device/host in a real deployment),
    and reads run over the merged view — ``query`` fans out one kernel
    launch per shard and reassembles, ``all_pairs`` tiles the global (D, D)
    estimate matrix from per-shard-pair launches.  Each shard keeps its own
    pre-allocated power-of-two blocks, so ingestion scales shard-locally
    (amortized O(m) per add, no cross-shard traffic until read time).
    """

    def __init__(self, num_shards: int = 2, **index_kwargs):
        if num_shards < 1:
            raise ValueError("need at least one shard")
        self.num_shards = num_shards
        self._shards = [SketchIndex(**index_kwargs)
                        for _ in range(num_shards)]
        self._names: list = []
        self._homes: list = []   # global row -> (shard, row-in-shard)
        self._discovery = None   # lazy ShardedDiscoveryEngine

    def __len__(self):
        return len(self._names)

    @property
    def total_dropped(self) -> int:
        return sum(s.total_dropped for s in self._shards)

    def _route(self) -> int:
        return len(self._names) % self.num_shards

    def add(self, name, vector: Optional[np.ndarray] = None, *,
            indices: Optional[np.ndarray] = None,
            values: Optional[np.ndarray] = None) -> None:
        # names are global: a per-shard check alone would miss a duplicate
        # routed to a different shard
        check_unique_name(name, self._names)
        s = self._route()
        # delegate first: a rejected add must not leave a dangling home
        self._shards[s].add(name, vector, indices=indices, values=values)
        self._homes.append((s, len(self._shards[s]) - 1))
        self._names.append(name)

    def add_many(self, names: Sequence, matrix: np.ndarray) -> None:
        """Scatter a (D, n) block round-robin: one batched ``add_many`` per
        shard, preserving the global insertion order for reads."""
        matrix = np.asarray(matrix, np.float32)
        if matrix.ndim != 2 or matrix.shape[0] != len(names):
            raise ValueError("matrix must be (len(names), n)")
        check_unique_names(names, self._names)
        # validate before touching the global name/home lists: a shard-level
        # rejection after partial routing would desynchronize reads
        dim = next((s._dim for s in self._shards if s._dim is not None), None)
        if dim is not None and matrix.shape[1] != dim:
            raise ValueError(f"matrix has {matrix.shape[1]} coordinates but "
                             f"this index was built over {dim}")
        matrix = check_finite(matrix, "ingest matrix",
                              nonfinite=self._shards[0].nonfinite)
        rows_of = [[] for _ in range(self.num_shards)]
        for k, name in enumerate(names):
            s = self._route()
            self._homes.append((s, len(self._shards[s]) + len(rows_of[s])))
            self._names.append(name)
            rows_of[s].append(k)
        for s, rows in enumerate(rows_of):
            if rows:
                self._shards[s].add_many([names[k] for k in rows],
                                         matrix[rows])

    def query(self, vector: np.ndarray, top_k: Optional[int] = None, *,
              mode: str = "plain"):
        """Fan out one bucketized launch per shard, reassemble globally.
        ``mode`` forwards to each shard (each shard charges its *own*
        accountant for a private release — its rows are disjoint)."""
        if not self._names:
            raise ValueError("query on an empty index: add vectors before "
                             "querying")
        with obs.op("serve.sharded.query") as sp:
            sp.set("shards", self.num_shards)
            per = [s.query(vector, mode=mode) if len(s) else []
                   for s in self._shards]
            est = np.empty(len(self._names), np.float32)
            for g, (s, r) in enumerate(self._homes):
                est[g] = per[s][r][1]
            if top_k is None:
                return list(zip(self._names, est.tolist()))
            order = _top_k_desc(est, top_k)
            return [(self._names[i], float(est[i])) for i in order]

    def all_pairs(self, *, use_pallas: bool = True) -> np.ndarray:
        """Global (D, D) estimates assembled from shard-pair launches."""
        with obs.op("serve.sharded.all_pairs") as sp:
            sp.set("shards", self.num_shards)
            D = len(self._names)
            out = np.zeros((D, D), np.float32)
            gids = [[] for _ in range(self.num_shards)]
            for g, (s, _) in enumerate(self._homes):
                gids[s].append(g)
            for i in range(self.num_shards):
                if not gids[i]:
                    continue
                ci = self._shards[i]._corpus()
                for j in range(self.num_shards):
                    if not gids[j]:
                        continue
                    cj = self._shards[j]._corpus()
                    blk = np.asarray(estimate_all_pairs_bucketized(
                        ci, cj, use_pallas=use_pallas))
                    out[np.ix_(gids[i], gids[j])] = \
                        blk[: len(gids[i]), : len(gids[j])]
            return out

    def top_pairs(self, k: int = 10, **kw):
        """Global top-k pairs via guarded async fan-out of bound-pruned
        scans over shard pairs, partial heaps merged at the coordinator; a
        shard that fails its retries degrades the answer instead of
        stalling it (DESIGN.md §16, §17)."""
        from repro.serve.discovery import ShardedDiscoveryEngine
        if self._discovery is None:
            self._discovery = ShardedDiscoveryEngine(self)
        return self._discovery.top_pairs(k, **kw)

    def top_k_for_query(self, vector: np.ndarray, k: int = 10, **kw):
        """Top-k estimates for one query via per-shard pruned scans merged
        at the coordinator (DESIGN.md §17)."""
        from repro.serve.discovery import ShardedDiscoveryEngine
        if self._discovery is None:
            self._discovery = ShardedDiscoveryEngine(self)
        return self._discovery.top_k_for_query(vector, k, **kw)
