"""Pallas TPU kernel: batched merge of two bucketized sketch corpora.

Coordinated sketches share the bucket seed, so a coordinate present in both
corpora lands in the *same bucket* on both sides — merging two bucketized
corpora (DESIGN.md §4 layout) is therefore a per-bucket problem: union the
2S candidate slots, drop b-side duplicates, keep entries whose recomputed
sampling rank beats the merged ``tau`` (computed once per row on the host
from the rank order statistic, see ops.py), and compact back to S slots in
coordinate order.  No sorting, no dynamic shapes: the dedupe is an S x S
lane-wise compare and the compaction a 2S x 2S position count — the same
static-slot-loop idiom as ``kernels/intersect_estimate``.

Sampling ranks are computed once, outside the kernel, by the same jnp
function the oracle and the merged-tau selection use (``ref.slot_ranks``):
the ``rank < tau`` cut then compares bits that came from one place on every
backend.  Inside the kernel each row is laid out slot-major, (S, B), so the
B buckets run along the lanes and every slot is one (1, B) row.

One launch merges all D rows of the corpora (grid over D), which is the
serving-layer ingredient for partition-merge ingestion: two ``SketchIndex``
block sets built over different row-partitions combine without ever leaving
the bucketized layout or touching the raw vectors (DESIGN.md §14).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.sketches import INVALID_IDX

from .ref import slot_ranks


def _merge_kernel(tau_ref, ai_ref, av_ref, ar_ref, bi_ref, bv_ref, br_ref,
                  oi_ref, ov_ref, drop_ref, *, slots: int):
    tau = tau_ref[0]                                    # (1, 1)
    rows = lambda ref: [ref[0, s:s + 1, :] for s in range(slots)]  # (1, B)
    ai, av, ar = rows(ai_ref), rows(av_ref), rows(ar_ref)
    bi, bv, br = rows(bi_ref), rows(bv_ref), rows(br_ref)

    keep_a = [(i != INVALID_IDX) & (r < tau) for i, r in zip(ai, ar)]
    # b-side duplicates: same coordinate hashes to the same bucket on both
    # sides, so an S x S slot compare within the bucket finds every one
    keep_b = []
    for i, r in zip(bi, br):
        dup = jnp.zeros(i.shape, bool)
        for a in ai:
            dup = dup | ((i == a) & (a != INVALID_IDX))
        keep_b.append((i != INVALID_IDX) & ~dup & (r < tau))

    cand_idx, cand_val, keep = ai + bi, av + bv, keep_a + keep_b
    # canonical coordinate order: a kept candidate's output slot is the
    # number of kept candidates with a smaller coordinate (keys are unique
    # after dedupe; dropped candidates carry INVALID = int32 max and sink)
    key = [jnp.where(k, c, INVALID_IDX) for k, c in zip(keep, cand_idx)]
    pos = [sum((kk < k).astype(jnp.int32) for kk in key) for k in key]
    for t in range(slots):
        col_i = jnp.full(key[0].shape, INVALID_IDX, jnp.int32)
        col_v = jnp.zeros(key[0].shape, jnp.float32)
        for k, p, c, v in zip(keep, pos, cand_idx, cand_val):
            sel = k & (p == t)
            col_i = jnp.where(sel, c, col_i)
            col_v = jnp.where(sel, v, col_v)
        oi_ref[0, t:t + 1, :] = col_i
        ov_ref[0, t:t + 1, :] = col_v
    # entries the merged bucket cannot hold (> S kept): counted like
    # bucketize's own overflow accounting (f32 lane sum: exact below 2^24)
    lost = sum((k & (p >= slots)).astype(jnp.float32)
               for k, p in zip(keep, pos))
    drop_ref[0] = jnp.sum(lost, axis=1, keepdims=True).astype(jnp.int32)


def merge_bucketized_pallas(a_idx, a_val, b_idx, b_val, tau, seed, *,
                            variant: str = "l2", interpret: bool):
    """Merge two (D, B, S) bucketized corpora under per-row merged ``tau``.

    Returns ``(out_idx (D,B,S), out_val (D,B,S), dropped (D,) int32)`` where
    ``dropped`` counts entries lost to bucket overflow *during the merge*
    (union needed more than S slots).  One launch for all D merges.
    """
    D, B, S = a_idx.shape
    assert b_idx.shape == (D, B, S), (a_idx.shape, b_idx.shape)
    a_val = jnp.asarray(a_val, jnp.float32)
    b_val = jnp.asarray(b_val, jnp.float32)
    ar = slot_ranks(a_idx, a_val, seed, variant)
    br = slot_ranks(b_idx, b_val, seed, variant)
    slot_major = lambda x: jnp.swapaxes(jnp.asarray(x), 1, 2)   # (D, S, B)
    row = pl.BlockSpec((1, S, B), lambda d: (d, 0, 0))
    one = pl.BlockSpec((1, 1, 1), lambda d: (d, 0, 0))
    kern = functools.partial(_merge_kernel, slots=S)
    oi, ov, drop = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((D, S, B), jnp.int32),
                   jax.ShapeDtypeStruct((D, S, B), jnp.float32),
                   jax.ShapeDtypeStruct((D, 1, 1), jnp.int32)),
        grid=(D,),
        in_specs=[one] + [row] * 6,
        out_specs=(row, row, one),
        interpret=interpret,
    )(jnp.asarray(tau, jnp.float32).reshape(D, 1, 1),
      *map(slot_major, (a_idx, a_val, ar, b_idx, b_val, br)))
    return (jnp.swapaxes(oi, 1, 2), jnp.swapaxes(ov, 1, 2),
            drop.reshape(D))
