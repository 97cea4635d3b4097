"""Plain numpy reference of the sketch service's semantics.

Written from the published algorithm and the service's documented layout,
not from the program (it imports nothing of ``repro``):

- Priority sampling (paper Algorithm 3), l2 weights: rank ``h(i) / a_i^2``
  in float32, where ``h`` is the lowbias32 hash of the coordinate under the
  index seed, its top 24 bits made a float in (0, 1); keep the ``m``
  smallest ranks; ``tau`` is the (m+1)-st smallest rank (inf when at most
  ``m`` coordinates are nonzero); an entry is kept iff its rank is below
  ``tau``.
- Bucketized layout: a kept coordinate goes to bucket
  ``lowbias32(i) & (B - 1)`` under the layout's bucket seed, at most ``S``
  per bucket, the smallest coordinates first; the rest are dropped and
  counted.
- Estimator (Algorithm 2): the sum over coordinates kept on both sides of
  ``a_i b_i / min(p_a(i), p_b(i))`` with ``p(i) = min(1, tau a_i^2)``,
  here accumulated in float64.

``precision="bfloat16"`` is the control: every stored quantity (values,
weights, hashes, ranks, tau, probabilities and products) is rounded to
bfloat16, with float32 accumulation.  It has to fail the comparison.
"""
from __future__ import annotations

import dataclasses

import ml_dtypes
import numpy as np

INVALID = np.int32(np.iinfo(np.int32).max)
BUCKET_SEED = 0xB0C4
_GOLDEN = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x21F0AAAD)
_M2 = np.uint32(0x735A2D97)
_UNIT = np.float32(1.0 / (1 << 24))
PRECISIONS = ("float32", "bfloat16")


def _rounder(precision: str):
    if precision == "float32":
        return lambda x: np.asarray(x, np.float32)
    if precision == "bfloat16":
        return lambda x: np.asarray(x, np.float32).astype(
            ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}; one of {PRECISIONS}")


def hash_u32(seed: int, keys: np.ndarray) -> np.ndarray:
    """lowbias32 finalizer of ``keys * golden + seed`` (uint32 wrap-around)."""
    x = np.asarray(keys).astype(np.uint32) * _GOLDEN + np.uint32(seed)
    x = x ^ (x >> np.uint32(16))
    x = x * _M1
    x = x ^ (x >> np.uint32(15))
    x = x * _M2
    return x ^ (x >> np.uint32(15))


def hash_unit(seed: int, keys: np.ndarray) -> np.ndarray:
    """Uniform float32 in (0, 1): the top 24 hash bits plus half an ulp."""
    h = hash_u32(seed, keys) >> np.uint32(8)
    return (h.astype(np.float32) + np.float32(0.5)) * _UNIT


@dataclasses.dataclass
class Layout:
    """Bucketized sketches of D columns: ``idx``/``val`` (D, B, S) with
    INVALID / 0 padding, ``tau`` (D,) float32, ``dropped`` (D,) int32."""

    idx: np.ndarray
    val: np.ndarray
    tau: np.ndarray
    dropped: np.ndarray


def sketch_columns(indptr: np.ndarray, keys: np.ndarray, vals: np.ndarray, *,
                   m: int, seed: int, n_buckets: int, slots: int,
                   precision: str = "float32") -> Layout:
    """Priority sketch + bucketized layout of every CSR column."""
    if n_buckets & (n_buckets - 1):
        raise ValueError("n_buckets must be a power of two")
    rnd = _rounder(precision)
    D = indptr.size - 1
    nnz = np.diff(indptr)
    row = np.repeat(np.arange(D), nnz)
    pos = np.arange(keys.size) - indptr[row]
    v = rnd(vals)
    w = rnd(v * v)
    h = rnd(hash_unit(seed, keys))
    with np.errstate(divide="ignore", invalid="ignore"):
        rank = np.where(w > 0, rnd(h / np.where(w > 0, w, 1)), np.inf)
    rank = rank.astype(np.float32)
    width = max(int(nnz.max()) if D else 0, m + 1)
    grid = np.full((D, width), np.inf, np.float32)
    grid[row, pos] = rank
    tau = np.partition(grid, m, axis=1)[:, m]
    keep = rank < tau[row]
    kk, kv, kr = keys[keep], v[keep], row[keep]
    bucket = (hash_u32(BUCKET_SEED, kk) & np.uint32(n_buckets - 1)).astype(
        np.int64)
    order = np.lexsort((kk, bucket, kr))
    kk, kv, kr, bucket = kk[order], kv[order], kr[order], bucket[order]
    group = kr * n_buckets + bucket
    start = np.flatnonzero(np.r_[True, group[1:] != group[:-1]])
    slot = np.arange(group.size) - np.repeat(start, np.diff(
        np.r_[start, group.size]))
    fits = slot < slots
    idx = np.full((D, n_buckets, slots), INVALID, np.int32)
    val = np.zeros((D, n_buckets, slots), np.float32)
    idx[kr[fits], bucket[fits], slot[fits]] = kk[fits]
    val[kr[fits], bucket[fits], slot[fits]] = kv[fits]
    dropped = np.bincount(kr[~fits], minlength=D).astype(np.int32)
    return Layout(idx, val, tau.astype(np.float32), dropped)


def sketch_dense(rows: np.ndarray, **kw) -> Layout:
    """``sketch_columns`` of dense (D, n) rows."""
    r, k = np.nonzero(rows)
    indptr = np.concatenate([[0], np.cumsum(np.bincount(
        r, minlength=rows.shape[0]))])
    return sketch_columns(indptr, k, rows[r, k], **kw)


def estimates(q: Layout, corpus: Layout, universe: int, *,
              precision: str = "float32") -> np.ndarray:
    """(Q, D) inner-product estimates of each query row against every
    corpus row (float64; the bfloat16 control rounds its terms and sums
    in float32)."""
    rnd = _rounder(precision)
    D = corpus.idx.shape[0]
    cid = corpus.idx.reshape(D, -1)
    cval = corpus.val.reshape(D, -1).astype(np.float64)
    out = np.zeros((q.idx.shape[0], D))
    table = np.full((universe + 1,), -1, np.int64)
    cpos = np.where(cid == INVALID, universe, cid)
    for j in range(q.idx.shape[0]):
        qi = q.idx[j].ravel()
        live = qi != INVALID
        qk, qv = qi[live], q.val[j].ravel()[live].astype(np.float64)
        table[qk] = np.arange(qk.size)
        hit = table[cpos]
        table[qk] = -1
        r, c = np.nonzero(hit >= 0)
        a, b = qv[hit[r, c]], cval[r, c]
        if precision == "float32":
            pa = np.minimum(1.0, np.float64(q.tau[j]) * a * a)
            pb = np.minimum(1.0, corpus.tau[r].astype(np.float64) * b * b)
            terms = a * b / np.minimum(pa, pb)
            out[j] = np.bincount(r, weights=terms, minlength=D)
        else:
            pa = rnd(np.minimum(1.0, rnd(q.tau[j] * rnd(a * a))))
            pb = rnd(np.minimum(1.0, rnd(corpus.tau[r] * rnd(b * b))))
            terms = rnd(rnd(a * b) / np.minimum(pa, pb))
            out[j] = np.bincount(r, weights=terms, minlength=D).astype(
                np.float32)
    return out
