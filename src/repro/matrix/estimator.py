"""Unbiased ``A^T B`` estimation from coordinated row samples (DESIGN.md §15).

``est = sum_{i in K_A ∩ K_B} a_i b_i^T / min(1, tau_A w^A_i, tau_B w^B_i)``

The inclusion-probability algebra is Algorithm 2's verbatim: both sketch
kinds publish ``tau`` such that row ``i`` survives in *both* sketches iff
``h(i) <= min(tau_A w^A_i, tau_B w^B_i)`` (the hash is shared), so the
joint inclusion probability is the same ``min(1, tau_A w^A_i, tau_B w^B_i)``
as the vector estimator — only the per-match payload changes from a scalar
product to a rank-one outer product, which makes the whole sum one small
``(d_A, |K|) x (|K|, d_B)`` matmul over the matched rows.

This sorted-layout searchsorted join is the reference path (and the parity
oracle for ``kernels/matrix_sketch``); batched pairs run the fused
bucketized kernel instead (``kernels.matrix_products_bucketized``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.sketches import INVALID_IDX

from .containers import MatrixSketch


def _match(a_idx: jnp.ndarray, b_idx: jnp.ndarray):
    """Join two sorted row-id arrays; returns (match_mask, positions_in_b)."""
    cap_b = b_idx.shape[-1]
    pos = jnp.searchsorted(b_idx, a_idx)
    pos = jnp.clip(pos, 0, cap_b - 1)
    match = (jnp.take(b_idx, pos) == a_idx) & (a_idx != INVALID_IDX)
    return match, pos


def estimate_matrix_product(sa: MatrixSketch, sb: MatrixSketch, *,
                            variant: str = "l2") -> jnp.ndarray:
    """Unbiased (d_A, d_B) estimate of ``A^T B`` from two same-seed matrix
    sketches.  ``variant`` must match construction (weights are recomputed
    from the stored rows).

    Shim over the payload-generic ``repro.engine.estimate_product`` with
    the ``reduction="matmul"`` pin — the matrix contraction order, bit-for-
    bit the historical formulation (DESIGN.md §18, ``tests/parity``).
    """
    from repro.engine.estimate import estimate_product
    from repro.engine.containers import from_matrix
    return estimate_product(from_matrix(sa), from_matrix(sb),
                            variant=variant, reduction="matmul")


def matrix_intersection_size(sa: MatrixSketch, sb: MatrixSketch) -> jnp.ndarray:
    """Number of row ids present in both sketches (diagnostic)."""
    match, _ = _match(sa.row_idx, sb.row_idx)
    return jnp.sum(match, axis=-1)


def estimate_matrix_products(SA: MatrixSketch, SB: MatrixSketch, *,
                             variant: str = "l2",
                             n_buckets: int = 512, slots: int = 4,
                             use_pallas: bool | None = None) -> jnp.ndarray:
    """Batched pairs: (P, cap, d_a) x (P, cap, d_b) stacked sketches ->
    (P, d_a, d_b) estimates of every ``A_p^T B_p`` in one launch.

    ``use_pallas=None`` resolves like the build pipeline: on TPU the batch
    is bucketized and runs the fused ``kernels/matrix_sketch`` kernel
    (compare-based intersection, MXU matmuls — exact up to rare bucket
    drops); elsewhere the vmapped searchsorted join of
    :func:`estimate_matrix_product` is the better formulation (gathers are
    cheap on CPU) and is exact.  ``n_buckets``/``slots`` only apply to the
    kernel path.
    """
    from repro.kernels.dispatch import resolve_use_pallas
    if resolve_use_pallas(use_pallas):
        from repro.kernels.matrix_sketch import (bucketize_matrix_sketches,
                                                 matrix_products_bucketized)
        BA = bucketize_matrix_sketches(SA, n_buckets=n_buckets, slots=slots)
        BB = bucketize_matrix_sketches(SB, n_buckets=n_buckets, slots=slots)
        return matrix_products_bucketized(BA, BB, variant=variant,
                                          use_pallas=True)
    return jax.vmap(
        lambda i, r, t, i2, r2, t2: estimate_matrix_product(
            MatrixSketch(i, r, t), MatrixSketch(i2, r2, t2),
            variant=variant))(SA.row_idx, SA.rows, SA.tau,
                              SB.row_idx, SB.rows, SB.tau)
