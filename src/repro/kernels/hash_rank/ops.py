"""Jit'd public wrapper for the hash_rank kernel: pad/reshape to the TPU
layout, dispatch to the Pallas kernel (``kernels.dispatch``), unpad."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..dispatch import interpret
from .hash_rank import (BLOCK, LANES, hash_rank_batched_pallas,
                        hash_rank_pallas)
from .ref import hash_rank_batched_ref, hash_rank_ref


@functools.partial(jax.jit, static_argnames=("variant", "use_pallas"))
def hash_rank(values: jnp.ndarray, seed, *, variant: str = "l2",
              use_pallas: bool = True):
    """(h, rank) for a flat vector; the fused O(N) pass of Algs. 1/3."""
    if not use_pallas:
        return hash_rank_ref(values, seed, variant=variant)
    n = values.shape[0]
    n_pad = -(-n // BLOCK) * BLOCK
    v = jnp.pad(values.astype(jnp.float32), (0, n_pad - n))
    v2 = v.reshape(n_pad // LANES, LANES)
    seed_arr = jnp.asarray(seed, jnp.int32)
    h, rank = hash_rank_pallas(v2, seed_arr, variant=variant,
                               interpret=interpret())
    return h.reshape(-1)[:n], rank.reshape(-1)[:n]


@functools.partial(jax.jit, static_argnames=("variant", "use_pallas"))
def hash_rank_batched(values: jnp.ndarray, seed, *, variant: str = "l2",
                      use_pallas: bool = True):
    """Fused (h, rank) for a (D, n) corpus block in one HBM pass.

    Returns ``h (n,)`` (shared by all rows — the hash depends only on the
    coordinate) and ``rank (D, n)``.  Padding columns (to the kernel BLOCK)
    get value 0 -> weight 0 -> rank +inf, so they can never be selected.
    """
    if not use_pallas:
        return hash_rank_batched_ref(values, seed, variant=variant)
    D, n = values.shape
    n_pad = -(-n // BLOCK) * BLOCK
    v = jnp.pad(values.astype(jnp.float32), ((0, 0), (0, n_pad - n)))
    v3 = v.reshape(D, n_pad // LANES, LANES)
    seed_arr = jnp.asarray(seed, jnp.int32)
    h, rank = hash_rank_batched_pallas(v3, seed_arr, variant=variant,
                                       interpret=interpret())
    return h.reshape(-1)[:n], rank.reshape(D, -1)[:, :n]
