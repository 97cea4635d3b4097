"""Payload-generic bucketized layout + kernel dispatch (DESIGN.md §18).

One (P, B, S, d) bucket layout for every payload dimension, produced by
the position-payload scatter of ``kernels.intersect_estimate
.bucketize_payloads`` (positions ride through the scatter as an f32
payload — exact below 2^24 — and the d-dim rows follow with one gather).

Kernel dispatch:

- **products** — ``pair_product_body`` (``kernels/matrix_sketch``) is
  already generic in d: per-pair S x S bucket compare, joint-probability
  rescale ``max(1/p_a, 1/p_b)``, one MXU contraction.  d=1 runs the same
  kernel with (P, B, S, 1) payloads; the legacy vector *all-pairs* family
  (``kernels/intersect_estimate``) remains the d=1 specialization that
  broadcasts one corpus against another instead of pairing rows.
- **merge** — d=1 dispatches to the ``kernels/sketch_merge`` Pallas kernel
  / oracle pair; d>1 runs the payload-generalized jnp oracle below (same
  rank-keep masks, same insertion-position compaction, payload rows summed
  through the identical one-hot selection) — the seam where a future
  GPU/TPU lowering of the d>1 merge plugs in.

Both agree bit for bit with their d=1 legacy counterparts
(``tests/parity/test_bucketized_parity.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hashing import hash_unit
from repro.core.sketches import INVALID_IDX, sampling_ranks
from repro.kernels.dispatch import interpret, resolve_use_pallas

from .containers import BucketizedPayloads, PayloadSketch, payload_weight


@functools.partial(jax.jit, static_argnames=("n_buckets", "slots"))
def _bucketize_one_payload(idx, payload, *, n_buckets, slots):
    from repro.kernels.intersect_estimate.ops import (DEFAULT_BUCKET_SEED,
                                                      bucketize_payloads)
    cap = idx.shape[0]
    # positions ride through the scatter as a payload; the d-dim rows
    # follow with one gather (cap < 2^24, so the f32 payload is exact)
    pos = jnp.arange(cap, dtype=jnp.float32)
    out_idx, (out_pos,), dropped = bucketize_payloads(
        idx, (pos,), n_buckets=n_buckets, slots=slots,
        bucket_seed=DEFAULT_BUCKET_SEED)
    valid = out_idx != INVALID_IDX
    out_pay = jnp.where(valid[..., None],
                        payload[out_pos.astype(jnp.int32)], 0.0)
    return out_idx, out_pay, dropped


def bucketize_payload_sketches(sk: PayloadSketch, *, n_buckets: int = 512,
                               slots: int = 4) -> BucketizedPayloads:
    """Re-lay a (P, cap, d) payload-sketch batch (or one (cap, d) sketch —
    lifted to P=1) into the bucketized kernel format.  ``n_buckets >= 2 m``
    keeps overflow drops near zero (DESIGN.md §4)."""
    if sk.idx.ndim == 1:
        sk = PayloadSketch(sk.idx[None], sk.payload[None],
                           jnp.reshape(jnp.asarray(sk.tau, jnp.float32), (1,)))
    out_idx, out_pay, dropped = jax.vmap(
        lambda i, p: _bucketize_one_payload(i, p, n_buckets=n_buckets,
                                            slots=slots))(sk.idx, sk.payload)
    return BucketizedPayloads(out_idx, out_pay,
                              jnp.reshape(sk.tau, (-1,)).astype(jnp.float32),
                              dropped.astype(jnp.int32))


def payload_slot_probs(bc: BucketizedPayloads, *,
                       variant: str = "l2") -> jnp.ndarray:
    """Per-slot inclusion probability min(1, tau * w(payload)) for a
    (P, B, S, d) bucketized batch; 1.0 at padding slots (w == 0) so inf
    taus from the keep-everything case never produce NaN."""
    w = payload_weight(bc.payload, variant)               # (P, B, S)
    tau = jnp.reshape(bc.tau, (-1, 1, 1))
    return jnp.where(w > 0, jnp.minimum(1.0, tau * w), 1.0)


def bucketized_products(A: BucketizedPayloads, B: BucketizedPayloads, *,
                        variant: str = "l2",
                        use_pallas: bool | None = None) -> jnp.ndarray:
    """(P, B, S, d_a) x (P, B, S, d_b) bucketized batches -> the (P, d_a,
    d_b) estimate of every pair's payload product in one fused launch.

    d=1 yields (P, 1, 1) inner-product estimates.  Exact against the
    sorted-layout ``engine.estimate_product`` up to bucket-overflow drops
    (counted in ``dropped``).  ``use_pallas=None`` resolves like the build
    pipeline: the Pallas kernel on TPU, the fused ``lax.map`` oracle
    elsewhere — both run the shared ``pair_product_body``, so they agree
    bit for bit.
    """
    from repro.kernels.matrix_sketch.matrix_sketch import \
        matrix_products_pallas
    from repro.kernels.matrix_sketch.ref import matrix_products_ref
    if A.idx.shape != B.idx.shape:
        raise ValueError(f"batch layouts differ: {A.idx.shape} vs "
                         f"{B.idx.shape}")
    a_p = payload_slot_probs(A, variant=variant)
    b_p = payload_slot_probs(B, variant=variant)
    if resolve_use_pallas(use_pallas):
        return matrix_products_pallas(A.idx, A.payload, a_p,
                                      B.idx, B.payload, b_p,
                                      interpret=interpret())
    return matrix_products_ref(A.idx, A.payload, a_p, B.idx, B.payload, b_p)


# ---------------------------------------------------------------------------
# Generic bucketized merge (d=1 -> sketch_merge kernels; d>1 -> jnp oracle)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("m", "variant", "use_pallas"))
def merged_tau_bucketized_payloads(A: BucketizedPayloads,
                                   B: BucketizedPayloads, seed, *, m: int,
                                   variant: str = "l2",
                                   use_pallas: bool | None = None
                                   ) -> jnp.ndarray:
    """Per-row merged priority tau: the (m+1)-st smallest rank of the union
    candidates (kept ranks of both sides, b-duplicates masked, plus both
    published taus — DESIGN.md §14, payload-generic weights).
    ``use_pallas`` picks the selection kernels (``kth_smallest_ranks``)."""
    from repro.kernels.sketch_build.ops import kth_smallest_ranks
    D, Bk, S = A.idx.shape

    def ranks(idx, pay):
        w = payload_weight(pay.astype(jnp.float32), variant)
        r = sampling_ranks(w, hash_unit(seed, idx))
        return jnp.where(idx != INVALID_IDX, r, jnp.inf)

    ra = ranks(A.idx, A.payload)
    rb = ranks(B.idx, B.payload)
    dup = jnp.zeros(B.idx.shape, bool)
    for s in range(S):
        a_s = A.idx[:, :, s]
        dup = dup | ((B.idx == a_s[:, :, None])
                     & (a_s != INVALID_IDX)[:, :, None])
    rb = jnp.where(dup, jnp.inf, rb)
    cand = jnp.concatenate(
        [ra.reshape(D, -1), rb.reshape(D, -1),
         jnp.reshape(A.tau, (D, 1)), jnp.reshape(B.tau, (D, 1))], axis=1)
    return kth_smallest_ranks(cand, m + 1, use_pallas=use_pallas)


@functools.partial(jax.jit, static_argnames=("variant",))
def _merge_payloads_oracle(a_idx, a_pay, b_idx, b_pay, tau, seed, *,
                           variant: str):
    """(D, B, S, d) x2 -> merged (out_idx, out_payload, dropped (D,)) —
    ``kernels.sketch_merge.merge_bucketized_ref`` with the value one-hot
    selection broadcast over the payload axis (bit-equal at d=1)."""
    D, Bk, S, d = a_pay.shape

    def ranks(idx, pay):
        w = payload_weight(pay.astype(jnp.float32), variant)
        return sampling_ranks(w, hash_unit(seed, idx))

    tau3 = jnp.reshape(jnp.asarray(tau, jnp.float32), (D, 1, 1))
    keep_a = (a_idx != INVALID_IDX) & (ranks(a_idx, a_pay) < tau3)
    dup = jnp.zeros(b_idx.shape, bool)
    for s in range(S):
        a_s = a_idx[:, :, s]
        dup = dup | ((b_idx == a_s[:, :, None])
                     & (a_s != INVALID_IDX)[:, :, None])
    keep_b = (b_idx != INVALID_IDX) & ~dup & (ranks(b_idx, b_pay) < tau3)

    cand_idx = jnp.concatenate([a_idx, b_idx], axis=2)   # (D, B, 2S)
    cand_pay = jnp.concatenate([a_pay.astype(jnp.float32),
                                b_pay.astype(jnp.float32)], axis=2)
    keep = jnp.concatenate([keep_a, keep_b], axis=2)
    key = jnp.where(keep, cand_idx, INVALID_IDX)
    pos = jnp.sum(key[:, :, :, None] < key[:, :, None, :],
                  axis=2).astype(jnp.int32)              # (D, B, 2S)
    write = keep & (pos < S)
    sel = write[:, :, :, None] & (pos[:, :, :, None]
                                  == jnp.arange(S)[None, None, None, :])
    out_idx = jnp.sum(jnp.where(sel, cand_idx[:, :, :, None], 0), axis=2) \
        + jnp.where(jnp.any(sel, axis=2), 0, INVALID_IDX)
    out_pay = jnp.sum(jnp.where(sel[:, :, :, :, None],
                                cand_pay[:, :, :, None, :], 0.0), axis=2)
    dropped = jnp.sum((keep & (pos >= S)).astype(jnp.int32), axis=(1, 2))
    return out_idx.astype(jnp.int32), out_pay, dropped


def merge_bucketized_payloads(A: BucketizedPayloads, B: BucketizedPayloads,
                              seed, *, m: int, variant: str = "l2",
                              tau: jnp.ndarray | None = None,
                              use_pallas: bool | None = None
                              ) -> BucketizedPayloads:
    """Row-wise merge of two coordinated (D, B, S, d) bucketized batches.

    Same contract as ``kernels.sketch_merge.merge_bucketized_corpora``
    (priority semantics unless a caller-computed ``tau`` overrides the
    order statistic; ``dropped`` accumulates both inputs' counts plus
    merge-overflow losses).  d=1 dispatches to the sketch_merge Pallas
    kernel / oracle; d>1 runs the payload-generalized oracle.
    """
    if A.idx.shape != B.idx.shape or A.payload.shape != B.payload.shape:
        raise ValueError(
            f"batch layouts differ: {A.payload.shape} vs {B.payload.shape}")
    if A.payload.shape[-1] == 1:
        from repro.kernels.intersect_estimate.ops import BucketizedSketch
        from repro.kernels.sketch_merge.ops import merge_bucketized_corpora
        out = merge_bucketized_corpora(
            BucketizedSketch(A.idx, A.payload[..., 0], A.tau, A.dropped),
            BucketizedSketch(B.idx, B.payload[..., 0], B.tau, B.dropped),
            seed, m=m, variant=variant, tau=tau, use_pallas=use_pallas)
        return BucketizedPayloads(out.idx, out.val[..., None], out.tau,
                                  out.dropped)
    if tau is None:
        tau = merged_tau_bucketized_payloads(A, B, seed, m=m, variant=variant,
                                             use_pallas=use_pallas)
    out_idx, out_pay, new_drop = _merge_payloads_oracle(
        A.idx, A.payload, B.idx, B.payload, tau, seed, variant=variant)
    return BucketizedPayloads(out_idx, out_pay,
                              jnp.asarray(tau, jnp.float32),
                              A.dropped + B.dropped + new_drop)
