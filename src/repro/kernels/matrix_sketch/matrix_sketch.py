"""Pallas TPU kernel: fused batched matrix-product estimation.

One launch estimates ``A_p^T B_p`` for a whole batch of P coordinated
matrix-sketch pairs (DESIGN.md §15).  Sketches arrive in the bucketized
layout of ``kernels/intersect_estimate`` — row id ``i`` lands in bucket
``hash(i) mod B`` on both sides, so the row-id intersection is a per-bucket
S x S lane-wise compare (no searchsorted, no dynamic shapes).  Per slot
pair the kernel fuses the three estimator stages in VMEM:

1. **intersect** — ``eq = (a_id == b_id)`` over the B buckets;
2. **rescale**   — coefficient ``1/min(p_a, p_b) == max(1/p_a, 1/p_b)``
   (reciprocal inclusion probabilities precomputed per slot on the host,
   the same variant-agnostic contract as the all-pairs kernel);
3. **matmul**    — ``acc += (a_rows * c)^T @ b_rows``, a (d_A, B) x (B, d_B)
   contraction that runs on the MXU.

The per-pair body is shared verbatim with the jnp oracle (``ref.py``), so
interpret-mode Pallas and the oracle execute identical per-pair HLO —
the parity tests assert bit-exact agreement.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

INVALID_IDX = np.int32(np.iinfo(np.int32).max)


def pair_product_body(ai, arows, ar, bi, brows, br, *, slots: int):
    """Fused estimate of one sketch pair: (B,S) ids (INVALID remapped to
    distinct negative sentinels by the caller), (B,S,d) rows, (B,S)
    reciprocal inclusion probabilities -> (d_a, d_b) estimate.

    Shared by the Pallas kernel and the jnp oracle so both execute the same
    op sequence (same shapes, same accumulation order) — the basis of the
    bit-exact parity claim.
    """
    da = arows.shape[-1]
    db = brows.shape[-1]
    acc = jnp.zeros((da, db), jnp.float32)
    for sa in range(slots):
        ai_s = ai[:, sa]                          # (B,)
        ar_s = ar[:, sa]
        arows_s = arows[:, sa, :]                 # (B, da)
        for sb in range(slots):
            eq = ai_s == bi[:, sb]
            c = jnp.where(eq, jnp.maximum(ar_s, br[:, sb]), 0.0)
            acc = acc + jax.lax.dot_general(
                arows_s * c[:, None], brows[:, sb, :],
                dimension_numbers=(((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    return acc


def _kernel(ai_ref, ar_ref, ap_ref, bi_ref, br_ref, bp_ref, out_ref, *,
            slots: int):
    ai = jnp.where(ai_ref[0] == INVALID_IDX, -1, ai_ref[0])      # (B, S)
    bi = jnp.where(bi_ref[0] == INVALID_IDX, -2, bi_ref[0])
    arows = ar_ref[0].astype(jnp.float32)                        # (B, S, da)
    brows = br_ref[0].astype(jnp.float32)
    ar = 1.0 / ap_ref[0]                      # p = min(1, tau w) in (0, 1]
    br = 1.0 / bp_ref[0]
    out_ref[0] = pair_product_body(ai, arows, ar, bi, brows, br, slots=slots)


def matrix_products_pallas(a_idx, a_rows, a_p, b_idx, b_rows, b_p, *,
                           interpret: bool) -> jnp.ndarray:
    """Batched fused estimator: (P, B, S) ids + (P, B, S, d) rows + (P, B, S)
    per-slot inclusion probabilities (1.0 at padding) per side -> the
    (P, d_a, d_b) estimate batch in one launch (grid over P)."""
    P, B, S = a_idx.shape
    da = a_rows.shape[-1]
    db = b_rows.shape[-1]
    assert b_idx.shape == (P, B, S), (a_idx.shape, b_idx.shape)
    kern = functools.partial(_kernel, slots=S)
    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((P, da, db), jnp.float32),
        grid=(P,),
        in_specs=[
            pl.BlockSpec((1, B, S), lambda p: (p, 0, 0)),
            pl.BlockSpec((1, B, S, da), lambda p: (p, 0, 0, 0)),
            pl.BlockSpec((1, B, S), lambda p: (p, 0, 0)),
            pl.BlockSpec((1, B, S), lambda p: (p, 0, 0)),
            pl.BlockSpec((1, B, S, db), lambda p: (p, 0, 0, 0)),
            pl.BlockSpec((1, B, S), lambda p: (p, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, da, db), lambda p: (p, 0, 0)),
        interpret=interpret,
    )(a_idx, a_rows, a_p, b_idx, b_rows, b_p)
