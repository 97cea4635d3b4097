"""Jit'd public wrappers for the batched bucketized-corpus merge.

Split mirrors the build pipeline (DESIGN.md §13/§14):

1. **Merged tau** — a per-row rank order statistic.  Ranks of every slot on
   both sides are recomputed from the stored (idx, val) (the hash is
   stateless), b-side duplicates are masked by the shared-bucket compare,
   and the (m+1)-st smallest of {ranks} ∪ {tau_a, tau_b} is resolved with
   the exact selection primitive ``kth_smallest_ranks`` — the same statistic
   the core ``merge_sketches`` uses, so the two paths agree.
2. **Block-wise union/compact** — the Pallas kernel (or its jnp oracle)
   merges all D rows in one launch without leaving the bucketized layout.

Threshold-style corpora can pass a caller-computed ``tau`` (e.g. the
adaptive merged tau from ``core.merge``) — the kernel itself is tau-agnostic.

Since the engine unification (DESIGN.md §18) the merged-tau order statistic
lives payload-generically in ``repro.engine.bucketized`` (shared with the
matrix surface); :func:`merged_tau_bucketized` is its d=1 shim.  The d=1
union/compact dispatch below stays here — the engine dispatches *to* it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..intersect_estimate.ops import BucketizedSketch
from ..dispatch import interpret, resolve_use_pallas
from .ref import merge_bucketized_ref
from .sketch_merge import merge_bucketized_pallas


def merged_tau_bucketized(A: BucketizedSketch, B: BucketizedSketch, seed, *,
                          m: int, variant: str = "l2",
                          use_pallas: bool | None = None) -> jnp.ndarray:
    """Per-row merged priority tau: the (m+1)-st smallest rank of the union
    candidates (kept ranks of both sides, b-duplicates masked, plus both
    published taus — DESIGN.md §14)."""
    from repro.engine.bucketized import merged_tau_bucketized_payloads
    from repro.engine.containers import BucketizedPayloads
    return merged_tau_bucketized_payloads(
        BucketizedPayloads(A.idx, A.val[..., None], A.tau, A.dropped),
        BucketizedPayloads(B.idx, B.val[..., None], B.tau, B.dropped),
        seed, m=m, variant=variant, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("variant", "use_pallas"))
def _merge_dispatch(a_idx, a_val, b_idx, b_val, tau, seed, *, variant: str,
                    use_pallas: bool):
    if use_pallas:
        return merge_bucketized_pallas(a_idx, a_val, b_idx, b_val, tau, seed,
                                       variant=variant,
                                       interpret=interpret())
    return merge_bucketized_ref(a_idx, a_val, b_idx, b_val, tau, seed,
                                variant=variant)


def merge_bucketized_corpora(A: BucketizedSketch, B: BucketizedSketch,
                             seed, *, m: int, variant: str = "l2",
                             tau: jnp.ndarray | None = None,
                             use_pallas: bool | None = None
                             ) -> BucketizedSketch:
    """Row-wise merge of two coordinated (D, B, S) bucketized corpora.

    Row ``d`` of the result is the bucketized sketch of the union of the two
    partitions row ``d`` was built from (priority semantics unless a
    caller-computed ``tau`` overrides the order statistic).  ``dropped``
    accumulates both inputs' counts plus entries lost where a merged bucket
    needed more than S slots.  ``use_pallas=None`` resolves like the build
    pipeline: Pallas on TPU, the fused XLA oracle elsewhere.
    """
    if A.idx.shape != B.idx.shape:
        raise ValueError(f"corpus shapes differ: {A.idx.shape} vs "
                         f"{B.idx.shape}")
    use_pallas = resolve_use_pallas(use_pallas)
    if tau is None:
        tau = merged_tau_bucketized(A, B, seed, m=m, variant=variant,
                                    use_pallas=use_pallas)
    out_idx, out_val, new_drop = _merge_dispatch(
        A.idx, A.val, B.idx, B.val, tau, seed, variant=variant,
        use_pallas=use_pallas)
    dropped = A.dropped + B.dropped + new_drop
    return BucketizedSketch(out_idx, out_val,
                            jnp.asarray(tau, jnp.float32), dropped)
