"""SketchDP: the paper's coordinated sampling sketches as a gradient
compressor for data-parallel training (DESIGN.md §3.1).

Each DP shard threshold/priority-samples its local gradient with a *shared
per-step seed* (coordination!), all-gathers only the (idx, val) sketch
payload — O(m) per shard instead of the O(P) dense all-reduce — and every
shard reconstructs the unbiased mean gradient locally:

    ghat_i = g_i / p_i  for sampled i        (unbiased: Thm 1 applies per shard)
    mean_g = (1/W) sum_w densify(sketch_w)

Because sampling probabilities are proportional to g_i^2 (the paper's l2
weighting), the estimator's variance obeys Theorem 1's bound with the
gradient's own norms — heavy coordinates are always transmitted.  An
optional error-feedback accumulator re-injects untransmitted mass on the
next step (standard for sparsified SGD).

The collective volume drops from 4P bytes (f32 all-reduce) to
8m * W bytes (idx+val all-gather); the roofline win is measured in
EXPERIMENTS.md §Perf.  Pure-DP composition (params replicated across the
compressed axes); TP x SketchDP composition is future work (DESIGN.md §5).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.priority import priority_sketch
from repro.core.sketches import INVALID_IDX
from repro.core.threshold import threshold_sketch


def _flatten(tree):
    leaves, treedef = jax.tree.flatten(tree)
    sizes = [x.size for x in leaves]
    flat = jnp.concatenate([x.reshape(-1).astype(jnp.float32) for x in leaves])
    return flat, (treedef, [x.shape for x in leaves], [x.dtype for x in leaves], sizes)


def _unflatten(flat, meta):
    treedef, shapes, dtypes, sizes = meta
    out = []
    off = 0
    for shape, dtype, size in zip(shapes, dtypes, sizes):
        out.append(flat[off:off + size].reshape(shape).astype(dtype))
        off += size
    return jax.tree.unflatten(treedef, out)


def sketch_gradient(flat_grad: jnp.ndarray, m: int, seed, *,
                    method: str = "threshold",
                    backend: str = "pallas"):
    """Sketch a flat gradient; returns (idx, val, tau).

    ``backend="pallas"`` (the default) routes through the fused linear-time
    build pipeline of ``kernels/sketch_build`` — gradients are the ingestion
    hot path, so the per-step sort of the legacy reference builders was pure
    overhead (DESIGN.md §13).  Kept sets and values are identical;
    parity is asserted in ``tests/test_distributed.py``.
    """
    fn = threshold_sketch if method == "threshold" else priority_sketch
    sk = fn(flat_grad, m, seed, backend=backend)
    return sk.idx, sk.val, sk.tau


def densify_mean(idx, val, tau, n: int):
    """Reconstruct the mean of W gathered sketches.
    idx/val: (W, cap); tau: (W,)."""
    W = idx.shape[0]
    wgt = val * val
    p = jnp.minimum(1.0, tau[:, None] * wgt)
    valid = idx != INVALID_IDX
    contrib = jnp.where(valid & (p > 0), val / jnp.where(p > 0, p, 1.0), 0.0)
    flat_idx = jnp.where(valid, idx, 0).reshape(-1)
    out = jnp.zeros((n,), jnp.float32)
    out = out.at[flat_idx].add(jnp.where(valid, contrib, 0.0).reshape(-1))
    return out / W


def make_sketchdp_grad_fn(mesh: Mesh, loss_fn: Callable, m: int, *,
                          method: str = "threshold",
                          error_feedback: bool = True,
                          axes: tuple = ("data",)) -> Callable:
    """Builds grad_fn(params, batch, ef_state, step) ->
    (loss, mean_grads, new_ef_state).

    Runs under shard_map over the DP axes: params/ef replicated, batch
    sharded on dim 0.  The only cross-shard communication is the all-gather
    of the m-sized sketches.
    """
    axes = tuple(a for a in axes if a in mesh.shape)

    def local_grads(params, batch, ef, step):
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        flat, meta = _flatten(grads)
        n = flat.shape[0]
        if error_feedback:
            flat = flat + ef
        seed = jnp.uint32(0x5EED) + step.astype(jnp.uint32)
        idx, val, tau = sketch_gradient(flat, m, seed, method=method)
        # transmitted part (what densify() reconstructs from OUR sketch)
        wgt = val * val
        p = jnp.minimum(1.0, tau * wgt)
        valid = idx != INVALID_IDX
        sent = jnp.zeros((n,), jnp.float32).at[
            jnp.where(valid, idx, 0)].add(
            jnp.where(valid & (p > 0), val / jnp.where(p > 0, p, 1.0), 0.0))
        new_ef = (flat - sent) if error_feedback else jnp.zeros_like(flat)
        # all-gather sketches across DP shards (THE communication step)
        for ax in axes:
            idx = jax.lax.all_gather(idx, ax).reshape(-1, idx.shape[-1]) \
                if idx.ndim == 1 else jax.lax.all_gather(idx, ax, axis=0).reshape(-1, idx.shape[-1])
            val = jax.lax.all_gather(val, ax, axis=0).reshape(-1, val.shape[-1])
            tau = jax.lax.all_gather(tau, ax, axis=0).reshape(-1)
        mean_flat = densify_mean(idx, val, tau, n)
        loss = jax.lax.pmean(loss, axes)
        return loss, _unflatten(mean_flat, meta), new_ef

    def grad_fn(params, batch, ef_state, step):
        pspec = jax.tree.map(lambda _: P(), params)
        bspec = jax.tree.map(lambda _: P(axes), batch)
        fn = jax.shard_map(local_grads, mesh=mesh,
                           in_specs=(pspec, bspec, P(axes), P()),
                           out_specs=(P(), pspec, P(axes)),
                           check_vma=False)
        return fn(params, batch, ef_state, step)

    return grad_fn


def init_ef_state(mesh: Mesh, params, axes: tuple = ("data",)) -> jnp.ndarray:
    """Per-shard error-feedback accumulator: a (W*n_flat,) global array whose
    shards are each worker's residual (sharded over the DP axes)."""
    n = sum(x.size for x in jax.tree.leaves(params))
    w = 1
    for a in axes:
        if a in mesh.shape:
            w *= mesh.shape[a]
    return jnp.zeros((w * n,), jnp.float32)


def compression_ratio(params, m: int, cap_overhead: float = 1.3) -> float:
    """Dense all-reduce bytes / sketch all-gather bytes (per shard)."""
    n = sum(x.size for x in jax.tree.leaves(params))
    dense = 4.0 * n
    sketch = 8.0 * m * cap_overhead  # idx (4B) + val (4B) per slot
    return dense / sketch


# ---------------------------------------------------------------------------
# Matrix mode: row-sampled compression of 2-D gradient tensors
# ---------------------------------------------------------------------------


def sketch_matrix_gradient(G: jnp.ndarray, m: int, seed, *,
                           method: str = "priority"):
    """Row-sample a 2-D gradient tensor (n, d) -> (row_idx, rows, tau).

    The matrix-mode compressor (DESIGN.md §15): instead of flattening a
    weight-matrix gradient and sampling scalars, sample whole *rows* with
    probability proportional to their squared norms
    (``repro.matrix`` builders).  Row structure is what downstream
    consumers want — optimizer blocks, per-row adapters, and coordinated
    sketches of two shards' gradients estimate the co-occurrence
    ``G_1^T G_2`` directly via ``estimate_matrix_product``.  The payload is
    ``m (d + 1)`` words vs ``n d`` dense — same coordination/seed contract
    as the flat path.
    """
    from repro.matrix import priority_matrix_sketch, threshold_matrix_sketch
    if method == "priority":
        sk = priority_matrix_sketch(G, m, seed)
    elif method == "threshold":
        sk = threshold_matrix_sketch(G, m, seed)
    else:
        raise ValueError(f"unknown method {method!r}")
    return sk.row_idx, sk.rows, sk.tau


def densify_matrix_mean(row_idx, rows, tau, n_rows: int):
    """Reconstruct the unbiased mean of W gathered matrix sketches.

    ``row_idx``: (W, cap); ``rows``: (W, cap, d); ``tau``: (W,).  Row ``i``
    of shard ``w`` contributes ``rows_w[i] / p_i`` with
    ``p_i = min(1, tau_w ||rows_w[i]||^2)`` — the matrix analogue of
    :func:`densify_mean` (Theorem 1 applies per shard and per column).
    """
    W = row_idx.shape[0]
    wgt = jnp.sum(rows * rows, axis=-1)               # (W, cap)
    p = jnp.minimum(1.0, tau[:, None] * wgt)
    valid = row_idx != INVALID_IDX
    scale = jnp.where(valid & (p > 0), 1.0 / jnp.where(p > 0, p, 1.0), 0.0)
    contrib = rows * scale[..., None]
    flat_idx = jnp.where(valid, row_idx, 0).reshape(-1)
    out = jnp.zeros((n_rows, rows.shape[-1]), jnp.float32)
    out = out.at[flat_idx].add(contrib.reshape(-1, rows.shape[-1]))
    return out / W


def matrix_compression_ratio(shape, m: int, *,
                             method: str = "priority") -> float:
    """Dense 2-D grad bytes / matrix-sketch payload bytes (per shard).

    Priority sketches carry exactly ``m`` row slots; threshold sketches
    carry the Lemma-4 capacity ``m + 4 ceil(sqrt(m))`` (the same overhead
    the vector :func:`compression_ratio` folds in as ``cap_overhead``).
    """
    from repro.matrix import matrix_capacity
    n, d = shape
    slots = m if method == "priority" else matrix_capacity(m)
    dense = 4.0 * n * d
    sketch = 4.0 * slots * (d + 1)    # d f32 row values + 1 int32 row id
    return dense / sketch
