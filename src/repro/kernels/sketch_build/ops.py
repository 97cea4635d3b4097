"""Jit'd public wrappers for the linear-time batched sketch build pipeline.

Pipeline per (D, n) block (DESIGN.md §13):

1. **Fused hash/weight/rank pass** — one HBM read of the values
   (``hash_rank_hist_pallas``, the 2D extension of ``kernels/hash_rank``),
   which also emits the level-0 log-domain histogram of the rank bits.
2. **Linear-time rank-quantile selection** — the exact (m+1)-st smallest
   rank (priority tau), the overflow cut (threshold), and the top-m weight
   cutoff (adaptive tau) are all k-th order statistics of positive float32
   keys.  Positive IEEE-754 floats compare like their unsigned bit
   patterns, so each is resolved by histogram refinement over the bit
   space: 4 Pallas levels of 256 bins on TPU, or (off-TPU) a fused XLA
   binary descent over two 16-bit digest arrays.  Both are exact, so the
   two formulations agree bit for bit.
3. **Compaction scatter** — kept entries are packed into the fixed-capacity
   ``Sketch`` layout with a prefix-sum + gather (coordinates ascend, so the
   output is already idx-sorted; no argsort).

No step sorts all n elements — construction is O(n) per vector vs the
O(n log n) sort/top_k reference path, which remains the parity oracle
(``ref.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hashing import hash_unit
from repro.core.sketches import (INVALID_IDX, Sketch, default_capacity,
                                 sampling_ranks, weight)

from ..dispatch import interpret, resolve_use_pallas
from ..hash_rank.hash_rank import BLOCK, LANES
from ..hash_rank.ops import hash_rank_batched
from .sketch_build import hash_rank_hist_pallas, rank_hist_pallas


# ---------------------------------------------------------------------------
# Exact k-th smallest over positive-float keys (the rank-quantile pass)
# ---------------------------------------------------------------------------


def _kth_smallest_bits_xla(keys: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """Exact k-th smallest of each row of ``keys`` as a uint32 bit pattern.

    ``keys``: (D, n) nonnegative float32 (+inf allowed, no NaN); ``k``: (D,)
    int32 with 1 <= k <= n.  Binary histogram descent on two 16-bit digest
    arrays: 16 two-bin levels resolve the high half, a count rebases k, and
    16 more resolve the low half — O(n) work per level, no sort.
    """
    u = jax.lax.bitcast_convert_type(keys, jnp.uint32)
    hi = (u >> np.uint32(16)).astype(jnp.uint16)
    prefix_hi = jnp.zeros(keys.shape[:1], jnp.uint16)
    for b in range(15, -1, -1):
        cand = prefix_hi | np.uint16(1 << b)
        cnt = jnp.sum(hi < cand[:, None], axis=1, dtype=jnp.int32)
        prefix_hi = jnp.where(cnt >= k, prefix_hi, cand)
    below = jnp.sum(hi < prefix_hi[:, None], axis=1, dtype=jnp.int32)
    k_lo = k - below
    # Non-matching rows mask to 0xFFFF, which no candidate ever counts
    # (cand <= 0xFFFF), so the descent sees exactly the active multiset.
    lo = jnp.where(hi == prefix_hi[:, None],
                   (u & np.uint32(0xFFFF)).astype(jnp.uint16),
                   np.uint16(0xFFFF))
    prefix_lo = jnp.zeros(keys.shape[:1], jnp.uint16)
    for b in range(15, -1, -1):
        cand = prefix_lo | np.uint16(1 << b)
        cnt = jnp.sum(lo < cand[:, None], axis=1, dtype=jnp.int32)
        prefix_lo = jnp.where(cnt >= k_lo, prefix_lo, cand)
    return (prefix_hi.astype(jnp.uint32) << np.uint32(16)) \
        | prefix_lo.astype(jnp.uint32)


def _pad_keys3d(keys: jnp.ndarray) -> jnp.ndarray:
    """(D, n) keys -> (D, rows, 128) with +inf padding (never selected
    below the k-th statistic; identical when the statistic itself is inf)."""
    D, n = keys.shape
    n_pad = -(-n // BLOCK) * BLOCK
    v = jnp.pad(keys, ((0, 0), (0, n_pad - n)), constant_values=jnp.inf)
    return v.reshape(D, n_pad // LANES, LANES)


def _kth_smallest_bits_pallas(keys: jnp.ndarray, k: jnp.ndarray, *,
                              hist0: jnp.ndarray | None = None
                              ) -> jnp.ndarray:
    """Same statistic via 4 Pallas histogram levels of 256 bins each.

    ``hist0``: optional precomputed level-0 (log-domain) histogram from the
    fused build pass, saving one HBM pass."""
    keys3d = _pad_keys3d(keys)
    D = keys.shape[0]
    prefix = jnp.zeros((D,), jnp.uint32)
    remaining = k
    for shift in (24, 16, 8, 0):
        if shift == 24 and hist0 is not None:
            hist = hist0
        else:
            hist = rank_hist_pallas(keys3d, prefix, shift=shift,
                                    interpret=interpret())
        csum = jnp.cumsum(hist, axis=1)
        d_star = jnp.argmax(csum >= remaining[:, None], axis=1)
        below = jnp.where(
            d_star > 0,
            jnp.take_along_axis(csum, jnp.maximum(d_star - 1, 0)[:, None],
                                axis=1)[:, 0], 0)
        remaining = remaining - below
        prefix = (prefix << np.uint32(8)) | d_star.astype(jnp.uint32)
    return prefix


def kth_smallest_ranks(keys: jnp.ndarray, k, *,
                       use_pallas: bool | None = None,
                       hist0: jnp.ndarray | None = None) -> jnp.ndarray:
    """Exact per-row k-th smallest of (D, n) nonnegative float32 keys.

    The shared selection primitive of the build pipeline: priority tau is
    ``kth_smallest_ranks(ranks, m+1)``, the threshold overflow cut is the
    (cap+1)-st smallest included rank, and adaptive tau's weight cutoff is
    the (n-m+1)-st smallest weight.  Requires 1 <= k <= n.  ``use_pallas``
    resolves through ``kernels.dispatch`` (None: histogram kernels on TPU).
    """
    D, n = keys.shape
    k_arr = jnp.broadcast_to(jnp.asarray(k, jnp.int32), (D,))
    if resolve_use_pallas(use_pallas):
        bits = _kth_smallest_bits_pallas(keys, k_arr, hist0=hist0)
    else:
        bits = _kth_smallest_bits_xla(keys, k_arr)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


# ---------------------------------------------------------------------------
# Compaction: prefix-sum + gather into the fixed-capacity Sketch layout
# ---------------------------------------------------------------------------


def pack_kept(keep: jnp.ndarray, vals: jnp.ndarray, cap: int,
              indices: jnp.ndarray | None = None):
    """Pack kept entries of each row into (cap,) slots, idx-sorted.

    ``keep``/``vals``: (D, n); ``indices``: None (coordinates = positions),
    (n,) shared, or (D, n) per-row — must be ascending for the output to be
    idx-sorted (the public builders normalize sparse inputs via
    ``_sort_sparse`` before reaching here).
    Coordinates ascend within a row, so a prefix sum assigns each kept entry
    its output slot and the pack needs no sort.  Rows with more than ``cap``
    kept entries (the documented tie corner of the overflow cut, DESIGN.md
    §13) truncate in coordinate order.
    """
    D, n = keep.shape
    csum = jnp.cumsum(keep.astype(jnp.int32), axis=1)
    targets = jnp.arange(1, cap + 1, dtype=jnp.int32)
    src = jax.vmap(lambda c: jnp.searchsorted(c, targets, side="left"))(csum)
    valid = targets[None, :] <= csum[:, -1:]
    src_c = jnp.minimum(src, n - 1).astype(jnp.int32)
    gval = jnp.take_along_axis(vals.astype(jnp.float32), src_c, axis=1)
    if indices is None:
        gidx = src_c
    elif indices.ndim == 1:
        gidx = indices.astype(jnp.int32)[src_c]
    else:
        gidx = jnp.take_along_axis(indices.astype(jnp.int32), src_c, axis=1)
    out_idx = jnp.where(valid, gidx, INVALID_IDX)
    out_val = jnp.where(valid, gval, 0.0)
    return out_idx, out_val


def _overflow_cut(include: jnp.ndarray, scores: jnp.ndarray, cap: int, *,
                  use_pallas: bool) -> jnp.ndarray:
    """Evict largest-score included entries beyond ``cap`` (threshold
    sampling's overflow event, Lemma 4 probability < ~1e-4).

    The cut value is the (cap+1)-st smallest included score; strictly-below
    keeps exactly cap entries (score ties at the cut: DESIGN.md §13).  The
    selection runs under a scalar ``lax.cond`` so its O(n) histogram passes
    are only paid when some row actually overflows — amortized O(1).
    """
    D, n = include.shape
    if cap + 1 > n:
        return include
    counts = jnp.sum(include, axis=1)

    def cut(_):
        masked = jnp.where(include, scores, jnp.inf)
        sel = kth_smallest_ranks(masked, cap + 1, use_pallas=use_pallas)
        return include & (scores < sel[:, None])

    return jax.lax.cond(jnp.any(counts > cap), cut,
                        lambda _: include, operand=None)


# ---------------------------------------------------------------------------
# Adaptive tau (Algorithm 4) in linear time
# ---------------------------------------------------------------------------


def adaptive_tau_batched(W: jnp.ndarray, m: int, *,
                         use_pallas: bool = False) -> jnp.ndarray:
    """Per-row inclusion scale with E[sketch size] == min(m, nnz).

    Same closed form as ``repro.core.threshold.adaptive_tau`` but the valid
    cap count k* is < m, so only the top-m weights matter: a histogram
    selection finds the m-th largest weight, the (at most m) larger ones are
    compacted and sorted (O(m log m)), and the suffix sums the closed form
    needs come from one masked O(n) pass — no O(n log n) sort.  tau can
    differ from the reference by summation-order rounding only (the kept
    set and estimates are unaffected; parity-tested).
    """
    D, n = W.shape
    nnz = jnp.sum(W > 0, axis=1)
    Wsum = jnp.sum(W, axis=1)
    w_min_nz = jnp.min(jnp.where(W > 0, W, jnp.inf), axis=1)
    tau_all = jnp.where(jnp.isfinite(w_min_nz), 1.0 / w_min_nz, jnp.inf)
    if m >= n:
        # nnz <= n <= m: every entry is kept.
        return tau_all
    # m-th largest weight == (n-m+1)-st smallest; zeros sort first.
    c_cut = kth_smallest_ranks(W, n - m + 1, use_pallas=use_pallas)
    gt = W > c_cut[:, None]
    g_cnt = jnp.sum(gt, axis=1)
    eq_cnt = jnp.sum(W == c_cut[:, None], axis=1)
    # Descending top-m weight values: the > cutoff entries plus copies of
    # the cutoff (multiset-exact under ties at the cutoff).
    _, buf = pack_kept(gt, W, m)
    js = jnp.arange(m, dtype=jnp.int32)
    buf = jnp.where(js[None, :] < g_cnt[:, None], buf, c_cut[:, None])
    w_top = -jnp.sort(-buf, axis=1)
    rest_eq = (eq_cnt.astype(jnp.float32)
               - (m - g_cnt).astype(jnp.float32)) * c_cut
    s_rest = jnp.sum(jnp.where(W < c_cut[:, None], W, 0.0), axis=1) + rest_eq
    # suffix[k] = sum of all weights below the k largest, smallest-first.
    suffix = s_rest[:, None] + jnp.cumsum(w_top[:, ::-1], axis=1)[:, ::-1]
    ks = js.astype(jnp.float32)
    m_f = jnp.float32(m)
    tau_k = jnp.where(suffix > 0,
                      (m_f - ks[None, :]) / jnp.where(suffix > 0, suffix, 1.0),
                      jnp.inf)
    not_capped_next = tau_k * w_top < 1.0
    w_prev = jnp.concatenate([w_top[:, :1], w_top[:, :-1]], axis=1)
    capped_prev = jnp.where(js[None, :] > 0, tau_k * w_prev >= 1.0 - 1e-6,
                            True)
    valid = not_capped_next & capped_prev & (m_f - ks[None, :] > 0)
    k_star = jnp.argmax(valid, axis=1)
    tau = jnp.take_along_axis(tau_k, k_star[:, None], axis=1)[:, 0]
    any_valid = jnp.any(valid, axis=1)
    tau = jnp.where(~any_valid, jnp.where(Wsum > 0, m_f / Wsum, 0.0), tau)
    return jnp.where(nnz <= m, tau_all, tau)


# ---------------------------------------------------------------------------
# Fused hash/rank front end (shared by the builders)
# ---------------------------------------------------------------------------


def _sort_sparse(A: jnp.ndarray, indices: jnp.ndarray):
    """Normalize explicit coordinates to ascending order (with their values)
    so the prefix-sum pack emits an idx-sorted sketch for any input order.
    O(nnz log nnz) on the sparse path only; a no-op permutation for the
    already-sorted np.nonzero order."""
    indices = indices.astype(jnp.int32)
    if indices.ndim == 1:
        order = jnp.argsort(indices)
        return A[:, order], indices[order]
    order = jnp.argsort(indices, axis=1)
    return (jnp.take_along_axis(A, order, axis=1),
            jnp.take_along_axis(indices, order, axis=1))


def _front_end(A: jnp.ndarray, seed, variant: str,
               indices: jnp.ndarray | None, use_pallas: bool,
               want_hist: bool):
    """(h, ranks, W, hist0) for a (D, n) block.

    Dense blocks run the fused batched kernel (or its XLA oracle); sparse
    blocks (explicit ``indices``) hash the given coordinates directly — the
    positional kernel cannot reconstruct them from the grid.
    """
    W = weight(A.astype(jnp.float32), variant)
    if indices is not None:
        h = hash_unit(seed, indices.astype(jnp.int32))
        h2 = h if h.ndim == 2 else h[None, :]
        return h, sampling_ranks(W, h2), W, None
    if use_pallas and want_hist:
        D, n = A.shape
        n_pad = -(-n // BLOCK) * BLOCK
        v = jnp.pad(A.astype(jnp.float32), ((0, 0), (0, n_pad - n)))
        h, rank, hist = hash_rank_hist_pallas(
            v.reshape(D, n_pad // LANES, LANES),
            jnp.asarray(seed, jnp.int32), variant=variant,
            interpret=interpret())
        # padding ranks are +inf; fold their counts out of the inf bin so
        # hist matches the unpadded block exactly
        pad_bin = np.int32(np.float32(np.inf).view(np.int32) >> 24)
        hist = hist.at[:, pad_bin].add(-(n_pad - n))
        return h.reshape(-1)[:n], rank.reshape(D, -1)[:, :n], W, hist
    h, ranks = hash_rank_batched(A, seed, variant=variant,
                                 use_pallas=use_pallas)
    return h, ranks, W, None


# ---------------------------------------------------------------------------
# Builders — thin shims over the payload-generic engine (DESIGN.md §18).
# The selection primitives above (kth_smallest_ranks, pack_kept,
# _overflow_cut, adaptive_tau_batched, _front_end) stay here: the engine
# imports them at module scope, so this module must only import the engine
# inside function bodies.
# ---------------------------------------------------------------------------


def _selector(use_pallas: bool | None) -> str | None:
    """Legacy ``use_pallas`` flag -> engine selector (None stays auto)."""
    if use_pallas is None:
        return None
    return "pallas" if use_pallas else "xla"


@functools.partial(jax.jit, static_argnames=("method", "m", "variant", "cap",
                                             "adaptive", "selector"))
def _build_shim(A, seed, indices, *, method, m, variant, cap, adaptive,
                selector):
    """One-dispatch d=1 shim: the (D, n) -> (D, n, 1) payload lift and the
    payload -> val squeeze trace into the same program as the engine build,
    so ingestion hot paths (serving adds, WAL replay) pay a single jit call
    exactly like the pre-engine builders did."""
    from repro.engine.build import build_payload_corpus
    A = jnp.atleast_2d(jnp.asarray(A, jnp.float32))
    out = build_payload_corpus(A, m, seed, method=method, variant=variant,
                               cap=cap, adaptive=adaptive, indices=indices,
                               selector=selector)
    return Sketch(idx=out.idx, val=out.payload[..., 0], tau=out.tau)


def build_threshold_corpus(A: jnp.ndarray, m: int, seed, *,
                           variant: str = "l2", cap: int | None = None,
                           adaptive: bool = True,
                           indices: jnp.ndarray | None = None,
                           use_pallas: bool | None = None) -> Sketch:
    """Batched linear-time Threshold Sampling (Algorithms 1+4) over (D, n).

    Estimator-equivalent to ``vmap(threshold_sketch)``: identical kept sets
    and values; tau may differ by summation-order rounding in the adaptive
    suffix sums (see ``adaptive_tau_batched``).  d=1 shim over
    ``repro.engine.build_payload_corpus`` (bit-exact, ``tests/parity``).
    """
    if cap is None:
        cap = default_capacity(m)
    return _build_shim(A, seed, indices, method="threshold", m=m,
                       variant=variant, cap=cap, adaptive=adaptive,
                       selector=_selector(use_pallas))


def build_priority_corpus(A: jnp.ndarray, m: int, seed, *,
                          variant: str = "l2",
                          indices: jnp.ndarray | None = None,
                          use_pallas: bool | None = None) -> Sketch:
    """Batched linear-time Priority Sampling (Algorithm 3) over (D, n).

    Bit-exact against ``vmap(priority_sketch)``: tau is the exact (m+1)-st
    smallest rank (a pure bit-pattern statistic) and the kept set follows.
    d=1 shim over ``repro.engine.build_payload_corpus``.
    """
    return _build_shim(A, seed, indices, method="priority", m=m,
                       variant=variant, cap=None, adaptive=True,
                       selector=_selector(use_pallas))
