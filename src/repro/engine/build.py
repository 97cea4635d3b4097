"""Payload-generic batched sketch builders (DESIGN.md §18).

One builder family for every payload dimension: the (D, n, d) block is
reduced to per-entry sampling weights (``payload_weight``), hashed once,
and resolved with the linear-time selection primitives of
``kernels/sketch_build`` — ``adaptive_tau_batched`` for Algorithm 4's
scale, ``kth_smallest_ranks`` for the priority tau and the threshold
overflow cut — then compacted with the sort-free prefix-sum pack.

The d=1 specialization *is* the vector pipeline: the front end delegates
to ``kernels.sketch_build._front_end`` (fused hash/rank kernels, level-0
histogram reuse), the selection calls are the identical op sequence, and
the generic pack gathers through the same ``searchsorted`` targets — so
``build_payload_corpus(A[..., None], ...)`` is bit-exact against
``build_threshold_corpus(A, ...)`` / ``build_priority_corpus(A, ...)``
(the ``tests/parity`` contract).  d>1 is the matrix pipeline of
``repro.matrix.builders`` batched over D sketches.

``selector`` picks the order-statistic backend:

- ``"pallas"`` — 4-level Pallas histogram refinement (TPU / interpret);
- ``"xla"``    — fused XLA binary digest descent (default off-TPU);
- ``"sort"``   — the O(n log n) sort/top_k reference formulations
  (``core.threshold.adaptive_tau`` / ``lax.top_k``), kept as the legacy
  parity oracle behind ``matrix`` ``backend="reference"``.

All three are exact statistics; ``"pallas"``/``"xla"`` agree bit for bit,
``"sort"`` differs from them only in adaptive-tau summation order
(DESIGN.md §13, §18).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.hashing import hash_unit
from repro.core.sketches import INVALID_IDX, sampling_ranks
from repro.core.threshold import adaptive_tau
from repro.kernels.dispatch import resolve_use_pallas
from repro.kernels.sketch_build.ops import (_front_end, _overflow_cut,
                                            adaptive_tau_batched,
                                            kth_smallest_ranks)

from .containers import PayloadSketch, payload_capacity, payload_weight

SELECTORS = ("pallas", "xla", "sort")


def resolve_selector(selector: str | None) -> str:
    """None -> auto: Pallas selection on TPU, the XLA formulation elsewhere
    (``kernels.dispatch.resolve_use_pallas``)."""
    if selector is None:
        return "pallas" if resolve_use_pallas(None) else "xla"
    if selector not in SELECTORS:
        raise ValueError(f"unknown selector {selector!r}; "
                         f"expected one of {SELECTORS}")
    return selector


def _sort_sparse_payloads(P: jnp.ndarray, indices: jnp.ndarray):
    """Normalize explicit coordinates to ascending order (with their
    payload rows) so the prefix-sum pack emits an idx-sorted sketch for any
    input order — ``sketch_build._sort_sparse`` with a payload gather."""
    indices = indices.astype(jnp.int32)
    if indices.ndim == 1:
        order = jnp.argsort(indices)
        return P[:, order], indices[order]
    order = jnp.argsort(indices, axis=1)
    return (jnp.take_along_axis(P, order[:, :, None], axis=1),
            jnp.take_along_axis(indices, order, axis=1))


def pack_payloads(keep: jnp.ndarray, payloads: jnp.ndarray, cap: int,
                  indices: jnp.ndarray | None = None):
    """Pack kept entries of each row into (cap,) slots, idx-sorted.

    ``keep``: (D, n); ``payloads``: (D, n, d); same prefix-sum + gather as
    ``sketch_build.pack_kept`` with the value gather broadcast over the
    payload axis (bit-exact at d=1 — a gather is elementwise).
    """
    D, n = keep.shape
    csum = jnp.cumsum(keep.astype(jnp.int32), axis=1)
    targets = jnp.arange(1, cap + 1, dtype=jnp.int32)
    src = jax.vmap(lambda c: jnp.searchsorted(c, targets, side="left"))(csum)
    valid = targets[None, :] <= csum[:, -1:]
    src_c = jnp.minimum(src, n - 1).astype(jnp.int32)
    g = jnp.take_along_axis(payloads.astype(jnp.float32), src_c[:, :, None],
                            axis=1)
    if indices is None:
        gidx = src_c
    elif indices.ndim == 1:
        gidx = indices.astype(jnp.int32)[src_c]
    else:
        gidx = jnp.take_along_axis(indices.astype(jnp.int32), src_c, axis=1)
    out_idx = jnp.where(valid, gidx, INVALID_IDX)
    out_payload = jnp.where(valid[:, :, None], g, 0.0)
    return out_idx, out_payload


def _generic_front_end(P: jnp.ndarray, seed, variant: str,
                       indices: jnp.ndarray | None, use_pallas: bool,
                       want_hist: bool):
    """(h, ranks (D, n), W (D, n), hist0) for a (D, n, d) block.

    d=1 delegates to the fused vector front end (hash/rank kernels, hist
    reuse — the exact legacy op sequence); d>1 hashes the coordinate ids
    directly, as the matrix builders do (there is no dense positional
    kernel for row payloads).
    """
    if P.shape[-1] == 1:
        return _front_end(P[..., 0], seed, variant, indices, use_pallas,
                          want_hist)
    W = payload_weight(P.astype(jnp.float32), variant)
    if indices is None:
        ids = jnp.arange(P.shape[1], dtype=jnp.int32)
    else:
        ids = indices.astype(jnp.int32)
    h = hash_unit(seed, ids)
    h2 = h if h.ndim == 2 else h[None, :]
    return h, sampling_ranks(W, h2), W, None


@functools.partial(jax.jit, static_argnames=("m", "variant", "cap",
                                             "adaptive", "selector"))
def _build_threshold_payload(P, seed, indices, *, m, variant, cap, adaptive,
                             selector):
    use_pallas = selector == "pallas"
    if indices is not None:
        P, indices = _sort_sparse_payloads(P, indices)
    D, n, d = P.shape
    h, ranks, W, _ = _generic_front_end(P, seed, variant, indices, use_pallas,
                                        want_hist=False)
    if adaptive and selector == "sort":
        tau = jax.vmap(lambda w: adaptive_tau(w, m))(W)
    elif adaptive:
        tau = adaptive_tau_batched(W, m, use_pallas=use_pallas)
    else:
        Wsum = jnp.sum(W, axis=1)
        tau = jnp.where(Wsum > 0, m / Wsum, 0.0)
    h2 = h if h.ndim == 2 else h[None, :]
    include = (W > 0) & (h2 <= tau[:, None] * W)
    keep = _overflow_cut(include, ranks, cap, use_pallas=use_pallas)
    kidx, kpay = pack_payloads(keep, P, cap, indices)
    return PayloadSketch(idx=kidx, payload=kpay,
                         tau=tau.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("m", "variant", "selector"))
def _build_priority_payload(P, seed, indices, *, m, variant, selector):
    use_pallas = selector == "pallas"
    if indices is not None:
        P, indices = _sort_sparse_payloads(P, indices)
    D, n, d = P.shape
    h, ranks, W, hist0 = _generic_front_end(P, seed, variant, indices,
                                            use_pallas, want_hist=True)
    if n < m + 1:
        # fewer candidates than m+1: tau is the padded (m+1)-st rank == inf
        tau = jnp.full((D,), jnp.inf, jnp.float32)
    elif selector == "sort":
        # reference formulation: top_k over all n ranks (the legacy matrix
        # ``backend="reference"`` oracle)
        tau = -jax.lax.top_k(-ranks, m + 1)[0][:, m]
    else:
        tau = kth_smallest_ranks(ranks, m + 1, use_pallas=use_pallas,
                                 hist0=hist0)
    include = ranks < tau[:, None]
    kidx, kpay = pack_payloads(include, P, m, indices)
    return PayloadSketch(idx=kidx, payload=kpay,
                         tau=tau.astype(jnp.float32))


def build_payload_corpus(payloads: jnp.ndarray, m: int, seed, *,
                         method: str = "threshold", variant: str = "l2",
                         cap: int | None = None, adaptive: bool = True,
                         indices: jnp.ndarray | None = None,
                         selector: str | None = None) -> PayloadSketch:
    """Batched coordinated sampling of a (D, n, d) payload block.

    ``method="threshold"``: Algorithms 1+4 — entry kept iff
    ``h <= tau * w``; ``adaptive=True`` solves E[size] == min(m, nnz);
    ``cap`` defaults to the Lemma-4 sizing.  ``method="priority"``:
    Algorithm 3 — tau is the exact (m+1)-st smallest sampling rank, exactly
    ``min(m, nnz)`` entries kept.  ``indices`` passes explicit (global)
    coordinates — (n,) shared or (D, n) per-row — for sparse inputs and
    partitioned builds (any order; normalized internally).

    A (D, n) block is accepted as d=1 (lifted to (D, n, 1)); a single
    (n, d) payload matrix must be passed as ``payloads[None]``.
    """
    P = jnp.asarray(payloads, jnp.float32)
    if P.ndim == 2:
        P = P[..., None]
    if P.ndim != 3:
        raise ValueError(f"expected (D, n, d) payloads, got shape {P.shape}")
    # jit boundary rule (DESIGN.md §19): under tracing this records one
    # retrace tick and no span — the body must never be timed inside jit
    with obs.engine_op("build_payload_corpus",
                       isinstance(P, jax.core.Tracer)) as sp:
        sp.set("method", method)
        sel = resolve_selector(selector)
        if indices is not None:
            indices = jnp.asarray(indices, jnp.int32)
        if method == "threshold":
            if cap is None:
                cap = payload_capacity(m)
            return _build_threshold_payload(P, seed, indices, m=m,
                                            variant=variant, cap=cap,
                                            adaptive=adaptive, selector=sel)
        if method == "priority":
            return _build_priority_payload(P, seed, indices, m=m,
                                           variant=variant, selector=sel)
        raise ValueError(f"unknown method {method!r}; "
                         "expected 'threshold' or 'priority'")
