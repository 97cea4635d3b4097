"""Pallas TPU kernel: CountSketch construction as one-hot MXU matmuls.

CountSketch on CPU is a scatter-add (``S[bucket(i)] += sign(i) * a_i``).
TPUs have no fast scatter, so we *rethink the primitive for the MXU*: each
(1, L) tile of signed values is multiplied by an (L, m_tile) one-hot bucket
matrix generated in-register from the hash — a dense matmul that the MXU
executes at full rate.  The grid iterates m-tiles in the outer dimension and
input tiles in the inner dimension so each output tile stays resident in
VMEM while every input tile accumulates into it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

L = 1024          # input lanes per grid step
M_TILE = 512      # output buckets per grid step

_GOLDEN = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x21F0AAAD)
_M2 = np.uint32(0x735A2D97)


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 15)
    return x


def _kernel(seeds_ref, val_ref, out_ref, *, m: int):
    j = pl.program_id(0)   # output tile (outer)
    t = pl.program_id(1)   # input tile (inner)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, L), 1)
    gidx = (t * L + lane).astype(jnp.uint32)
    seed_b = seeds_ref[0, 0].astype(jnp.uint32)
    seed_s = seeds_ref[0, 1].astype(jnp.uint32)
    hb = _mix32(gidx * _GOLDEN + seed_b)
    if m & (m - 1) == 0:
        bucket = (hb & np.uint32(m - 1)).astype(jnp.int32)
    else:
        bucket = (hb % np.uint32(m)).astype(jnp.int32)
    hs = _mix32(gidx * _GOLDEN + seed_s)
    sign = jnp.where((hs & np.uint32(1)) == 0, np.float32(1.0), np.float32(-1.0))

    contrib = val_ref[...].astype(jnp.float32) * sign          # (1, L)
    cols = jax.lax.broadcasted_iota(jnp.int32, (L, M_TILE), 1) + j * M_TILE
    onehot = (bucket.reshape(L, 1) == cols).astype(jnp.float32)  # (L, M_TILE)
    out_ref[...] += jnp.dot(contrib, onehot,
                            preferred_element_type=jnp.float32)  # (1, M_TILE)


def countsketch_pallas(values: jnp.ndarray, seeds: jnp.ndarray, m_pad: int,
                       *, m: int, interpret: bool) -> jnp.ndarray:
    """values: (n,) f32 with n % L == 0; m_pad % M_TILE == 0.
    Returns (m_pad,) bucket array (only the first ``m`` buckets are live)."""
    n = values.shape[0]
    assert n % L == 0 and m_pad % M_TILE == 0
    grid = (m_pad // M_TILE, n // L)
    kern = functools.partial(_kernel, m=m)
    out = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((1, m_pad), jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 2), lambda j, t: (0, 0)),
                  pl.BlockSpec((1, L), lambda j, t: (0, t))],
        out_specs=pl.BlockSpec((1, M_TILE), lambda j, t: (0, j)),
        interpret=interpret,
    )(seeds.reshape(1, 2).astype(jnp.int32), values.reshape(1, n))
    return out.reshape(m_pad)
