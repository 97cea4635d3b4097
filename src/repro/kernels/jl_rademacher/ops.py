"""Jit'd wrapper for the matrix-free JL projection."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..dispatch import interpret
from .jl_rademacher import M_TILE, N_TILE, jl_pallas
from .ref import jl_ref


@functools.partial(jax.jit, static_argnames=("m", "use_pallas"))
def jl_project(values: jnp.ndarray, m: int, seed, *, use_pallas: bool = True) -> jnp.ndarray:
    """S(a) = Pi a / sqrt(m), Pi regenerated from ``seed`` (never stored)."""
    if not use_pallas:
        return jl_ref(values, m, seed)
    n = values.shape[0]
    n_pad = -(-n // N_TILE) * N_TILE
    v = jnp.pad(values.astype(jnp.float32), (0, n_pad - n))
    m_pad = -(-m // M_TILE) * M_TILE
    out = jl_pallas(v, jnp.asarray(seed, jnp.int32), m_pad,
                    interpret=interpret())
    return out[:m] / jnp.sqrt(jnp.float32(m))
