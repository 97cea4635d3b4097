"""Jit'd public wrappers for the fused batched matrix-product estimator.

Since the engine unification (DESIGN.md §18) this package is the d>1 face
of ``repro.engine.bucketized``: the (P, B, S, d) layout, the position-
payload bucketize scatter, the per-slot probability map and the Pallas /
``lax.map``-oracle product dispatch all live there once (shared with the
d=1 vector surface), and these wrappers only translate between the legacy
``BucketizedMatrixSketch`` container and the engine's
``BucketizedPayloads``.  The Pallas kernel itself (``pair_product_body``,
``matrix_products_pallas``) stays in this package — it was payload-generic
from the start and is what the engine dispatches to.
"""
from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp

from repro.matrix.containers import MatrixSketch, stack_matrix_sketches

__all__ = ["BucketizedMatrixSketch", "bucketize_matrix_sketches",
           "matrix_products_bucketized", "matrix_slot_probs",
           "stack_matrix_sketches"]


class BucketizedMatrixSketch(NamedTuple):
    """Bucketized batch of matrix sketches (leading dim P)."""

    idx: jnp.ndarray      # int32 (P, B, S) row ids, INVALID_IDX padding
    rows: jnp.ndarray     # f32 (P, B, S, d) sampled rows, 0 at padding
    tau: jnp.ndarray      # f32 (P,)
    dropped: jnp.ndarray  # int32 (P,): rows lost to bucket overflow


def bucketize_matrix_sketches(sk: MatrixSketch, *, n_buckets: int = 512,
                              slots: int = 4) -> BucketizedMatrixSketch:
    """Re-lay a (P, cap, d) matrix-sketch batch (or one (cap, d) sketch —
    lifted to P=1) into the bucketized kernel format.  ``n_buckets >= 2 m``
    keeps overflow drops near zero, as for vector sketches (DESIGN.md §4)."""
    from repro.engine.bucketized import bucketize_payload_sketches
    from repro.engine.containers import from_matrix
    out = bucketize_payload_sketches(from_matrix(sk), n_buckets=n_buckets,
                                     slots=slots)
    return BucketizedMatrixSketch(out.idx, out.payload, out.tau, out.dropped)


def matrix_slot_probs(bc: BucketizedMatrixSketch, *,
                      variant: str = "l2") -> jnp.ndarray:
    """Per-slot inclusion probability min(1, tau * w(row)) for a bucketized
    batch; 1.0 at padding slots so reciprocals stay finite."""
    from repro.engine.bucketized import payload_slot_probs
    from repro.engine.containers import BucketizedPayloads
    return payload_slot_probs(
        BucketizedPayloads(bc.idx, bc.rows, bc.tau, bc.dropped),
        variant=variant)


def matrix_products_bucketized(A: BucketizedMatrixSketch,
                               B: BucketizedMatrixSketch, *,
                               variant: str = "l2",
                               use_pallas: bool | None = None) -> jnp.ndarray:
    """(P, B, S) x (P, B, S) bucketized matrix-sketch batches -> the
    (P, d_a, d_b) estimate of every ``A_p^T B_p`` in one fused launch.

    Exact against the sorted-layout ``estimate_matrix_product`` up to rare
    bucket-overflow drops (counted in ``dropped``).  ``use_pallas=None``
    resolves like the build pipeline: the Pallas kernel on TPU, the fused
    ``lax.map`` XLA formulation elsewhere.
    """
    from repro.engine.bucketized import bucketized_products
    from repro.engine.containers import BucketizedPayloads
    return bucketized_products(
        BucketizedPayloads(A.idx, A.rows, A.tau, A.dropped),
        BucketizedPayloads(B.idx, B.rows, B.tau, B.dropped),
        variant=variant, use_pallas=use_pallas)
