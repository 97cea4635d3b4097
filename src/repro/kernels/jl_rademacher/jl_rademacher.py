"""Pallas TPU kernel: matrix-free Johnson-Lindenstrauss projection.

The JL/AMS baseline computes ``S(a) = Pi a / sqrt(m)`` with a dense
Rademacher matrix Pi.  Materializing Pi costs O(nm) HBM; on TPU we instead
regenerate each (n_tile x m_tile) +-1 tile *in VMEM from the hash* and feed
it straight to the MXU.  The projection becomes compute-bound instead of
memory-bound: O(nm) MACs but only O(n + m) HBM traffic — the TPU-native
version of "linear sketching is slow because it multiplies by a dense
matrix" (Section 1.1).

Row seeds: sign(j, i) = lowbit(mix32(i * GOLDEN + mix32(seed + j * GOLDEN))).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

N_TILE = 1024   # input elements per step
M_TILE = 256    # output rows per step

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


def _kernel(seed_ref, val_ref, out_ref):
    j = pl.program_id(0)   # output row tile (outer)
    t = pl.program_id(1)   # input tile (inner)

    @pl.when(t == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    seed = seed_ref[0, 0].astype(jnp.uint32)
    rows = (jax.lax.broadcasted_iota(jnp.int32, (N_TILE, M_TILE), 1)
            + j * M_TILE).astype(jnp.uint32)
    cols = (jax.lax.broadcasted_iota(jnp.int32, (N_TILE, M_TILE), 0)
            + t * N_TILE).astype(jnp.uint32)
    row_seed = _mix32(seed + rows * _GOLDEN)
    h = _mix32(cols * _GOLDEN + row_seed)
    sign = jnp.where((h & np.uint32(1)) == 0, np.float32(1.0), np.float32(-1.0))
    v = val_ref[...].astype(jnp.float32)                       # (1, N_TILE)
    out_ref[...] += jnp.dot(v, sign, preferred_element_type=jnp.float32)


def jl_pallas(values: jnp.ndarray, seed: jnp.ndarray, m_pad: int, *,
              interpret: bool) -> jnp.ndarray:
    n = values.shape[0]
    assert n % N_TILE == 0 and m_pad % M_TILE == 0
    grid = (m_pad // M_TILE, n // N_TILE)
    out = pl.pallas_call(
        _kernel,
        out_shape=jax.ShapeDtypeStruct((1, m_pad), jnp.float32),
        grid=grid,
        in_specs=[pl.BlockSpec((1, 1), lambda j, t: (0, 0)),
                  pl.BlockSpec((1, N_TILE), lambda j, t: (0, t))],
        out_specs=pl.BlockSpec((1, M_TILE), lambda j, t: (0, j)),
        interpret=interpret,
    )(seed.reshape(1, 1).astype(jnp.int32), values.reshape(1, n))
    return out.reshape(m_pad)
