"""Pure-jnp oracle for the bucketized merge kernel (bit-exact contract).

Same math as ``sketch_merge.py`` vectorized over the corpus dim with plain
XLA ops; the tests assert the Pallas kernel (interpret mode off-TPU) agrees
bit for bit, and that merging in the bucketized layout matches bucketizing
the core ``merge_sketches`` output when no bucket overflows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.hashing import hash_unit
from repro.core.sketches import INVALID_IDX, sampling_ranks, weight


def slot_ranks(idx, val, seed, variant: str):
    """Sampling rank h(idx)/w(val) of every slot; +inf at padding (val 0 ->
    weight 0).  The one rank formula of the merge: the kernel's wrapper and
    this oracle both call it, so their ``rank < tau`` cuts see equal bits."""
    w = weight(jnp.asarray(val).astype(jnp.float32), variant)
    return sampling_ranks(w, hash_unit(seed, idx))


@functools.partial(jax.jit, static_argnames=("variant",))
def merge_bucketized_ref(a_idx, a_val, b_idx, b_val, tau, seed, *,
                         variant: str = "l2"):
    """(D, B, S) x2 -> merged (out_idx, out_val, dropped (D,))."""
    D, B, S = a_idx.shape
    ranks = lambda idx, val: slot_ranks(idx, val, seed, variant)

    tau3 = jnp.reshape(jnp.asarray(tau, jnp.float32), (D, 1, 1))
    keep_a = (a_idx != INVALID_IDX) & (ranks(a_idx, a_val) < tau3)
    dup = jnp.zeros(b_idx.shape, bool)
    for s in range(S):
        a_s = a_idx[:, :, s]
        dup = dup | ((b_idx == a_s[:, :, None])
                     & (a_s != INVALID_IDX)[:, :, None])
    keep_b = (b_idx != INVALID_IDX) & ~dup & (ranks(b_idx, b_val) < tau3)

    cand_idx = jnp.concatenate([a_idx, b_idx], axis=2)   # (D, B, 2S)
    cand_val = jnp.concatenate([a_val.astype(jnp.float32),
                                b_val.astype(jnp.float32)], axis=2)
    keep = jnp.concatenate([keep_a, keep_b], axis=2)
    key = jnp.where(keep, cand_idx, INVALID_IDX)
    pos = jnp.sum(key[:, :, :, None] < key[:, :, None, :],
                  axis=2).astype(jnp.int32)              # (D, B, 2S)
    write = keep & (pos < S)
    sel = write[:, :, :, None] & (pos[:, :, :, None]
                                  == jnp.arange(S)[None, None, None, :])
    out_idx = jnp.sum(jnp.where(sel, cand_idx[:, :, :, None], 0), axis=2) \
        + jnp.where(jnp.any(sel, axis=2), 0, INVALID_IDX)
    out_val = jnp.sum(jnp.where(sel, cand_val[:, :, :, None], 0.0), axis=2)
    dropped = jnp.sum((keep & (pos >= S)).astype(jnp.int32), axis=(1, 2))
    return out_idx.astype(jnp.int32), out_val, dropped
