"""Bucketized sketch layout + jit'd estimation wrappers.

Layout (DESIGN.md §4): entry ``i`` of a sorted sketch lands in bucket
``hash(i) mod B`` with at most S slots per bucket; coordinated sketches
share the bucket seed so a shared index lands in the same bucket on both
sides.  ``bucketize_payloads`` scatters any number of per-entry payload
arrays through the same layout, which is how the join-correlation path
carries its precomputed inclusion probabilities alongside the values.

Estimation entry points:

- ``query_corpus``       one query vs a corpus
- ``sketch_and_query``   one dense query vector vs a corpus: sketch,
  bucketize and the ``query_corpus`` launch as one program (serving path)
- ``estimate_all_pairs_bucketized``  (D1, D2) estimate matrix in one launch
- ``allpairs_moments``   (D1, D2, 6) co-moment channels for join-correlation
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.hashing import hash_bucket
from repro.core.priority import priority_sketch
from repro.core.sketches import INVALID_IDX, Sketch

from ..dispatch import interpret
from .intersect_estimate import CT, QT, allpairs_estimate_pallas
from .ref import allpairs_estimate_ref, intersect_estimate_ref

DEFAULT_BUCKET_SEED = 0xB0C4


class BucketizedSketch(NamedTuple):
    idx: jnp.ndarray      # int32 (B, S) or (C, B, S)
    val: jnp.ndarray      # f32 same shape
    tau: jnp.ndarray      # f32 scalar or (C,)
    dropped: jnp.ndarray  # int32: entries lost to bucket overflow


def round_up_pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1)."""
    n = max(int(n), 1)
    return 1 << (n - 1).bit_length()


@functools.partial(jax.jit, static_argnames=("n_buckets", "slots"))
def bucketize_payloads(idx: jnp.ndarray, payloads: tuple, *,
                       n_buckets: int = 512, slots: int = 4,
                       bucket_seed: int = DEFAULT_BUCKET_SEED):
    """Re-layout a sorted index array and per-entry payloads into (B, S).

    Returns ``(out_idx (B,S) int32, out_payloads tuple of (B,S) f32,
    dropped int32)``.  Entries beyond S per bucket are dropped (counted);
    with B >= m the expected load per bucket is <= 1 and drops are rare
    (documented bias, DESIGN.md §4).
    """
    cap = idx.shape[-1]
    valid = idx != INVALID_IDX
    b = jnp.where(valid, hash_bucket(bucket_seed, idx, n_buckets),
                  n_buckets)  # invalid -> sentinel bucket
    order = jnp.argsort(b)
    b_sorted = b[order]
    idx_sorted = idx[order]
    # position within bucket = i - first index of this bucket value
    first = jnp.searchsorted(b_sorted, b_sorted, side="left")
    pos = jnp.arange(cap, dtype=jnp.int32) - first.astype(jnp.int32)
    keep = (b_sorted < n_buckets) & (pos < slots)
    # non-kept entries scatter out of bounds and are dropped (mode="drop");
    # redirecting them to a real cell would clobber that cell's entry
    bi = jnp.where(keep, b_sorted, n_buckets).astype(jnp.int32)
    pi = jnp.where(keep, pos, 0)
    out_idx = jnp.full((n_buckets, slots), INVALID_IDX, jnp.int32)
    out_idx = out_idx.at[bi, pi].set(idx_sorted, mode="drop")
    outs = []
    for payload in payloads:
        p_sorted = payload.astype(jnp.float32)[order]
        out = jnp.zeros((n_buckets, slots), jnp.float32)
        outs.append(out.at[bi, pi].set(p_sorted, mode="drop"))
    dropped = jnp.sum(valid) - jnp.sum(keep)
    return out_idx, tuple(outs), dropped.astype(jnp.int32)


def bucketize(sketch: Sketch, *, n_buckets: int = 512, slots: int = 4,
              bucket_seed: int = DEFAULT_BUCKET_SEED) -> BucketizedSketch:
    """Re-layout a sorted sketch into (B, S) buckets."""
    out_idx, (out_val,), dropped = bucketize_payloads(
        sketch.idx, (sketch.val,), n_buckets=n_buckets, slots=slots,
        bucket_seed=bucket_seed)
    return BucketizedSketch(out_idx, out_val, sketch.tau, dropped)


def bucketize_corpus(sketches: Sketch, **kw) -> BucketizedSketch:
    """vmapped bucketize over a corpus of sketches (leading dim C)."""
    return jax.vmap(lambda i, v, t: bucketize(Sketch(i, v, t), **kw))(
        sketches.idx, sketches.val, sketches.tau)


def slot_inclusion_probs(bc: BucketizedSketch, *, variant: str = "l2") -> jnp.ndarray:
    """Per-slot inclusion probability min(1, tau * w(val)) for a (C, B, S)
    bucketized corpus; 1.0 at padding slots (w == 0) so inf taus from the
    keep-everything case never produce NaN.  d=1 shim over the payload-
    generic ``repro.engine.bucketized.payload_slot_probs`` (DESIGN.md §18)."""
    from repro.engine.bucketized import payload_slot_probs
    from repro.engine.containers import BucketizedPayloads
    return payload_slot_probs(
        BucketizedPayloads(bc.idx, bc.val[..., None], bc.tau, bc.dropped),
        variant=variant)


def query_corpus(q: BucketizedSketch, corpus: BucketizedSketch, *,
                 use_pallas: bool = True) -> jnp.ndarray:
    """(C,) inner product estimates of one query against a corpus: the
    all-pairs kernel with a single query row (one launch over the corpus)."""
    return _query_corpus_jit(q, corpus, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _query_corpus_jit(q: BucketizedSketch, corpus: BucketizedSketch, *,
                      use_pallas: bool = True) -> jnp.ndarray:
    if not use_pallas:
        return intersect_estimate_ref(q.idx, q.val, q.tau,
                                      corpus.idx, corpus.val, corpus.tau)
    q1 = BucketizedSketch(q.idx[None], q.val[None],
                          jnp.reshape(q.tau, (1,)), q.dropped)
    return _allpairs_tiled(q1.idx, q1.val, slot_inclusion_probs(q1),
                           corpus.idx, corpus.val,
                           slot_inclusion_probs(corpus),
                           moments=False, qt=QT, ct=CT)[0]


@functools.partial(jax.jit, static_argnames=("m", "n_buckets", "slots",
                                             "use_pallas"))
def sketch_and_query(vector: jnp.ndarray, corpus: BucketizedSketch, seed, *,
                     m: int, n_buckets: int, slots: int,
                     use_pallas: bool = True):
    """Serve one dense query vector as one compiled program: the reference
    priority sketch of ``vector``, its bucketize, then ``_query_corpus_jit``
    (called, not inlined, so the kernel keeps that name in the program).

    Returns ``(est, q)``: the (C,) estimates and the bucketized query, whose
    ``tau`` the bias-aware correction needs.  Bit for bit the eager
    ``priority_sketch`` -> ``bucketize`` -> ``query_corpus`` chain.
    """
    # the body runs only while tracing: one bump per compile of this
    # program (jit boundary rule, DESIGN.md §19)
    obs.counter("repro_query_program_compiles_total",
                "compiles of the served query program").inc()
    q = bucketize(priority_sketch(vector, m, seed), n_buckets=n_buckets,
                  slots=slots)
    return _query_corpus_jit(q, corpus, use_pallas=use_pallas), q


def _pad_rows(idx, val, p, tile: int):
    """Pad the corpus dim up to a multiple of ``tile`` with inert rows."""
    D = idx.shape[0]
    pad = -(-D // tile) * tile - D
    if pad == 0:
        return idx, val, p
    widths = ((0, pad), (0, 0), (0, 0))
    return (jnp.pad(idx, widths, constant_values=INVALID_IDX),
            jnp.pad(val, widths),
            jnp.pad(p, widths, constant_values=1.0))


@functools.partial(jax.jit,
                   static_argnames=("moments", "qt", "ct", "use_pallas",
                                    "ref_chunk"))
def _allpairs_dispatch(a_idx, a_val, a_p, b_idx, b_val, b_p, *,
                       moments: bool, qt: int, ct: int, use_pallas: bool,
                       ref_chunk: int | None = None):
    D1, D2 = a_idx.shape[0], b_idx.shape[0]
    if not use_pallas:
        if ref_chunk:
            b_idx, b_val, b_p = _pad_rows(b_idx, b_val, b_p, ref_chunk)
        out = allpairs_estimate_ref(a_idx, a_val, a_p, b_idx, b_val, b_p,
                                    moments=moments, ct=ref_chunk)
        return out[:D1, :D2]
    return _allpairs_tiled(a_idx, a_val, a_p, b_idx, b_val, b_p,
                           moments=moments, qt=qt, ct=ct)


def _tile(rows: int, tile: int, align: int) -> int:
    """Block rows for one side: ``tile`` rows when the side is longer, else
    the whole side rounded up to ``align``; the TPU block rule wants a
    multiple of 8 sublanes / 128 lanes or the whole (padded) dim."""
    return tile if rows > tile else -(-rows // align) * align


def _allpairs_tiled(a_idx, a_val, a_p, b_idx, b_val, b_p, *, moments: bool,
                    qt: int, ct: int):
    """Pad both sides to whole tiles, run the all-pairs kernel, unpad.  A
    single-row A side (the query path) keeps one-row blocks."""
    D1, D2 = a_idx.shape[0], b_idx.shape[0]
    qt = _tile(D1, qt, 1 if D1 == 1 else 8)
    ct = _tile(D2, ct, 8)
    ai, av, ap = _pad_rows(a_idx, a_val, a_p, qt)
    bi, bv, bp = _pad_rows(b_idx, b_val, b_p, ct)
    out = allpairs_estimate_pallas(ai, av, ap, bi, bv, bp, qt=qt, ct=ct,
                                   moments=moments, interpret=interpret())
    return out[:D1, :D2]


def estimate_all_pairs_bucketized(A: BucketizedSketch, B: BucketizedSketch, *,
                                  variant: str = "l2", qt: int = QT,
                                  ct: int = CT, ref_chunk: int | None = None,
                                  use_pallas: bool = True) -> jnp.ndarray:
    """(D1, B, S) x (D2, B, S) bucketized corpora -> (D1, D2) estimates.

    One tiled kernel launch (or the fused XLA reference when
    ``use_pallas=False``) instead of D1*D2 searchsorted joins.  ``qt``/``ct``
    tile the Pallas grid; ``ref_chunk`` chunks the reference path's corpus
    dimension the same way (peak intermediates (D1, ref_chunk, B) instead of
    (D1, D2, B) — the knob the allpairs benchmark tunes per layout,
    DESIGN.md §17).
    """
    a_p = slot_inclusion_probs(A, variant=variant)
    b_p = slot_inclusion_probs(B, variant=variant)
    return _allpairs_dispatch(A.idx, A.val, a_p, B.idx, B.val, b_p,
                              moments=False, qt=qt, ct=ct,
                              ref_chunk=ref_chunk, use_pallas=use_pallas)


def estimate_tile_rows(a_idx, a_val, a_p, b_idx, b_val, b_p,
                       rows_a, rows_b, *, use_pallas: bool = True):
    """Estimate one (tq, tc) tile of the all-pairs matrix from *gathered*
    row subsets of two bucketized corpora — the discovery engine's
    tile-subset launch path (DESIGN.md §17).

    ``rows_a`` (tq,) / ``rows_b`` (tc,) are row ids into the (D, B, S)
    corpus arrays; out-of-range ids clamp (callers pad short tiles with any
    id and mask host-side).  The tile shapes are static, so every tile of a
    scan reuses one compiled launch regardless of *which* rows it gathers —
    that is what lets the engine visit an arbitrary, bound-ordered subset
    of tiles without recompiling or materializing the (D1, D2) matrix.
    """
    return _estimate_tile_rows_jit(a_idx, a_val, a_p, b_idx, b_val, b_p,
                                   rows_a, rows_b, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def _estimate_tile_rows_jit(a_idx, a_val, a_p, b_idx, b_val, b_p,
                            rows_a, rows_b, *, use_pallas: bool = True):
    gather = lambda arr, rows: jnp.take(arr, rows, axis=0, mode="clip")
    ai, av, ap = (gather(x, rows_a) for x in (a_idx, a_val, a_p))
    bi, bv, bp = (gather(x, rows_b) for x in (b_idx, b_val, b_p))
    if not use_pallas:
        return allpairs_estimate_ref(ai, av, ap, bi, bv, bp)
    return _allpairs_tiled(ai, av, ap, bi, bv, bp, moments=False,
                           qt=QT, ct=CT)


def allpairs_moments(a_idx, a_val, a_p, b_idx, b_val, b_p, *, qt: int = QT,
                     ct: int = CT, use_pallas: bool = True) -> jnp.ndarray:
    """(D1, D2, 6) co-moment channels (MOMENT_CHANNELS order) from bucketized
    corpora with caller-supplied per-slot inclusion probabilities — the
    join-correlation all-pairs path (DESIGN.md §7, §12)."""
    return _allpairs_dispatch(a_idx, a_val, a_p, b_idx, b_val, b_p,
                              moments=True, qt=qt, ct=ct,
                              use_pallas=use_pallas)
