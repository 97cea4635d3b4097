"""Jit'd wrapper: pad, dispatch Pallas CountSketch, slice to m buckets."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..dispatch import interpret
from .countsketch import L, M_TILE, countsketch_pallas
from .ref import countsketch_ref


@functools.partial(jax.jit, static_argnames=("m", "use_pallas"))
def countsketch(values: jnp.ndarray, m: int, seed_bucket, seed_sign, *,
                use_pallas: bool = True) -> jnp.ndarray:
    if not use_pallas:
        return countsketch_ref(values, seed_bucket, seed_sign, m)
    n = values.shape[0]
    n_pad = -(-n // L) * L
    v = jnp.pad(values.astype(jnp.float32), (0, n_pad - n))
    m_pad = -(-m // M_TILE) * M_TILE
    seeds = jnp.stack([jnp.asarray(seed_bucket, jnp.int32),
                       jnp.asarray(seed_sign, jnp.int32)])
    out = countsketch_pallas(v, seeds, m_pad, m=m, interpret=interpret())
    return out[:m]
