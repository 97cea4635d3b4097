"""Linear-time batched construction of join-correlation combined sketches.

The legacy builders (``repro.core.join_correlation``) are the parity
oracles.  ``combined_priority_sketch`` costs three full argsorts plus two
sorts per vector — the heaviest construction path in the repo;  here each
family's rank order is resolved by the shared histogram selection
(``kth_smallest_ranks``), the union position q_i = min_f pos_f(i) comes
from a searchsorted against the (m+1) smallest ranks per family, and m'
(= q_sorted[m]) is one more k-th statistic — O(n log m) total, no O(n)-size
sort.  ``combined_threshold_sketch``'s bisection is already linear; only
its top_k + argsort packing is replaced by the prefix-sum compaction.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hashing import hash_unit
from repro.core.join_correlation import CombinedSketch
from repro.core.sketches import default_capacity

from ..dispatch import resolve_use_pallas
from .ops import _overflow_cut, kth_smallest_ranks, pack_kept


def _normalized_weights_batched(A: jnp.ndarray):
    """Batched twin of join_correlation._normalized_weights (same formulas)."""
    scale = jnp.maximum(jnp.max(jnp.abs(A), axis=1), 1e-30)
    an = A / scale[:, None]
    w_ones = (A != 0).astype(jnp.float32)
    w_val = an * an
    w_sq = w_val * w_val
    return scale, w_ones, w_val, w_sq


def _ranks_of(h2: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    # legacy ranks_of: max(w, 1e-30) guard, not the sampling_ranks where-form
    return jnp.where(w > 0, h2 / jnp.maximum(w, 1e-30), jnp.inf)


@functools.partial(jax.jit, static_argnames=("m", "use_pallas"))
def _build_combined_priority(A, seed, *, m, use_pallas):
    D, n = A.shape
    scale, w1, wv, ws = _normalized_weights_batched(A)
    nnz = jnp.sum(w1 > 0, axis=1)
    h = hash_unit(seed, jnp.arange(n, dtype=jnp.int32))
    h2 = h[None, :]
    r1, rv, rs = _ranks_of(h2, w1), _ranks_of(h2, wv), _ranks_of(h2, ws)
    keep_all = nnz <= m
    inf = jnp.full((D,), jnp.inf, jnp.float32)
    if n < m + 1:
        # nnz <= n <= m: the keep-all branch always applies.
        tau1 = tauv = taus = inf
        include = w1 > 0
    else:
        K = m + 1
        ranks_all = jnp.concatenate([r1, rv, rs], axis=0)          # (3D, n)
        cuts = kth_smallest_ranks(ranks_all, K, use_pallas=use_pallas)
        # (m+1) smallest ranks per family, ascending: the < cut entries
        # padded with copies of the cut (multiset-exact under rank ties).
        lt = ranks_all < cuts[:, None]
        cnt_lt = jnp.sum(lt, axis=1)
        _, buf = pack_kept(lt, ranks_all, K)
        js = jnp.arange(K, dtype=jnp.int32)
        buf = jnp.where(js[None, :] < cnt_lt[:, None], buf, cuts[:, None])
        tops = jnp.sort(buf, axis=1)                               # (3D, K)
        # position of each entry in each family's rank order (exact for
        # distinct ranks; >= K beyond the tracked head, which min() caps)
        pos = jax.vmap(lambda t, r: jnp.searchsorted(t, r, side="left"))(
            tops, ranks_all).reshape(3, D, n)
        q = jnp.min(pos, axis=0).astype(jnp.float32)               # (D, n)
        mp = kth_smallest_ranks(q, m + 1,
                                use_pallas=use_pallas).astype(jnp.int32)
        tops3 = tops.reshape(3, D, K)
        mp_c = jnp.clip(mp, 0, K - 1)[None, :, None]
        fam_tau = jnp.take_along_axis(tops3, jnp.broadcast_to(
            mp_c, (3, D, 1)), axis=2)[:, :, 0]
        tau1 = jnp.where(keep_all, jnp.inf, fam_tau[0])
        tauv = jnp.where(keep_all, jnp.inf, fam_tau[1])
        taus = jnp.where(keep_all, jnp.inf, fam_tau[2])
        include = (w1 > 0) & ((r1 < tau1[:, None]) | (rv < tauv[:, None])
                              | (rs < taus[:, None]))
        include = jnp.where(keep_all[:, None], w1 > 0, include)
    kidx, kval = pack_kept(include, A, m)
    return CombinedSketch(kidx, kval, tau1, tauv, taus, scale)


def build_combined_priority_corpus(A: jnp.ndarray, m: int, seed, *,
                                   use_pallas: bool | None = None
                                   ) -> CombinedSketch:
    """Batched linear-time Algorithm 6 over (D, n) (see module docstring)."""
    A = jnp.atleast_2d(jnp.asarray(A, jnp.float32))
    return _build_combined_priority(
        A, seed, m=m, use_pallas=resolve_use_pallas(use_pallas))


@functools.partial(jax.jit, static_argnames=("m", "cap", "bisect_iters",
                                             "use_pallas"))
def _build_combined_threshold(A, seed, *, m, cap, bisect_iters, use_pallas):
    D, n = A.shape
    scale, w1, wv, ws = _normalized_weights_batched(A)
    nnz = jnp.sum(w1, axis=1)
    W1 = jnp.maximum(nnz, 1e-30)
    Wv = jnp.maximum(jnp.sum(wv, axis=1), 1e-30)
    Ws = jnp.maximum(jnp.sum(ws, axis=1), 1e-30)
    umax = jnp.maximum(w1 / W1[:, None],
                       jnp.maximum(wv / Wv[:, None], ws / Ws[:, None]))
    target = jnp.minimum(jnp.float32(m), nnz)

    def expected_size(mp):
        return jnp.sum(jnp.minimum(1.0, mp[:, None] * umax), axis=1)

    lo = jnp.zeros((D,), jnp.float32)
    hi = jnp.maximum(W1, 1.0)

    def body(_, lohi):
        lo, hi = lohi
        mid = 0.5 * (lo + hi)
        too_small = expected_size(mid) < target
        return jnp.where(too_small, mid, lo), jnp.where(too_small, hi, mid)

    lo, hi = jax.lax.fori_loop(0, bisect_iters, body, (lo, hi))
    mp = 0.5 * (lo + hi)
    h = hash_unit(seed, jnp.arange(n, dtype=jnp.int32))
    T = jnp.minimum(1.0, mp[:, None] * umax)
    include = (w1 > 0) & (h[None, :] <= T)
    scores = jnp.where(w1 > 0, h[None, :] / jnp.maximum(umax, 1e-30),
                       jnp.inf)
    keep = _overflow_cut(include, scores, cap, use_pallas=use_pallas)
    kidx, kval = pack_kept(keep, A, cap)
    return CombinedSketch(kidx, kval, mp / W1, mp / Wv, mp / Ws, scale)


def build_combined_threshold_corpus(A: jnp.ndarray, m: int, seed, *,
                                    cap: int | None = None,
                                    bisect_iters: int = 50,
                                    use_pallas: bool | None = None
                                    ) -> CombinedSketch:
    """Batched Algorithm 5 (adaptive m' bisection + linear compaction)."""
    A = jnp.atleast_2d(jnp.asarray(A, jnp.float32))
    if cap is None:
        cap = default_capacity(m)
    return _build_combined_threshold(
        A, seed, m=m, cap=cap, bisect_iters=bisect_iters,
        use_pallas=resolve_use_pallas(use_pallas))
