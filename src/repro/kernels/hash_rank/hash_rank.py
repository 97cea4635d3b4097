"""Pallas TPU kernel: fused hash + sampling-rank computation.

This is the O(N) hot loop shared by Algorithm 1 (threshold test
``h(i) <= tau * w_i``) and Algorithm 3 (rank ``R_i = h(i) / w_i``).  On TPU
we fuse (a) the integer hash of the global coordinate, (b) the weight
``w_i`` (a_i^2 / |a_i| / 1), and (c) the rank division into one VMEM pass so
the vector is read from HBM exactly once and nothing is materialized in
between — the CPU implementation's hash-then-filter does three passes.

Layout: the vector is viewed as (rows, 128) lanes, processed in tiles of up
to ``MAX_ROWS`` rows per grid step; the global coordinate is reconstructed
from the grid position, so no index array is ever stored.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

SUBLANES = 8
LANES = 128
BLOCK = SUBLANES * LANES  # padding granule: vectors pad to whole (8, 128) tiles
MAX_ROWS = 256            # rows of 128 lanes per grid step (32K elements)

_GOLDEN = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x21F0AAAD)
_M2 = np.uint32(0x735A2D97)
_UNIT = np.float32(1.0 / (1 << 24))


def row_tile(rows: int) -> int:
    """Rows per grid step: the largest power of two <= MAX_ROWS dividing
    ``rows`` (a multiple of SUBLANES, so the tile is at least one vreg)."""
    assert rows % SUBLANES == 0, rows
    t = MAX_ROWS
    while rows % t:
        t //= 2
    return t


def _mix32(x):
    x = x ^ (x >> 16)
    x = x * _M1
    x = x ^ (x >> 15)
    x = x * _M2
    x = x ^ (x >> 15)
    return x


def _weight(v, variant: str):
    if variant == "l2":
        return v * v
    if variant == "l1":
        return jnp.abs(v)
    if variant == "uniform":
        return (v != 0).astype(v.dtype)
    raise ValueError(variant)


def _block_hash_rank(seed_ref, v, block_j, variant: str):
    """Shared fused body: (h, rank) for one (R, LANES) value block at block
    index ``block_j`` along the vector.  The single source of the
    hash/rank formula for every kernel that must stay bit-coordinated
    (scalar, batched, and sketch_build's histogram-fused variant)."""
    rows = v.shape[0]
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    c = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    gidx = ((block_j * rows + r) * LANES + c).astype(jnp.uint32)
    seed = seed_ref[0, 0].astype(jnp.uint32)
    h = _mix32(gidx * _GOLDEN + seed)
    # h >> 8 < 2^24: the int32 hop is exact (Mosaic has no u32 -> f32 cast)
    top = (h >> np.uint32(8)).astype(jnp.int32).astype(jnp.float32)
    hu = (top + np.float32(0.5)) * _UNIT
    w = _weight(v.astype(jnp.float32), variant)
    rank = jnp.where(w > 0, hu / jnp.where(w > 0, w, 1.0), jnp.inf)
    return hu, rank


def seed_spec():
    """The (1, 1) int32 seed lives in SMEM: a scalar read, no vector load."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _kernel(seed_ref, val_ref, h_ref, rank_ref, *, variant: str):
    hu, rank = _block_hash_rank(seed_ref, val_ref[...], pl.program_id(0),
                                variant)
    h_ref[...] = hu
    rank_ref[...] = rank


def hash_rank_pallas(values2d: jnp.ndarray, seed: jnp.ndarray, *,
                     variant: str = "l2", interpret: bool):
    """values2d: (rows, 128) f32, rows % 8 == 0.  Returns (h, rank), same shape."""
    rows = values2d.shape[0]
    assert values2d.shape[1] == LANES
    rt = row_tile(rows)
    kern = functools.partial(_kernel, variant=variant)
    h, rank = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((rows, LANES), jnp.float32)),
        grid=(rows // rt,),
        in_specs=[seed_spec(),
                  pl.BlockSpec((rt, LANES), lambda i: (i, 0))],
        out_specs=(pl.BlockSpec((rt, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((rt, LANES), lambda i: (i, 0))),
        interpret=interpret,
    )(seed.reshape(1, 1).astype(jnp.int32), values2d)
    return h, rank


def _batched_kernel(seed_ref, val_ref, h_ref, rank_ref, *, variant: str):
    """One (vector d, block j) grid cell of the batched 2D pass.

    The global coordinate is the position *within the row* (all vectors of a
    coordinated corpus share the hash stream), reconstructed from the block
    grid position j — no index array is materialized.  The hash output is a
    single (rows, 128) array shared by every d (its block is revisited once
    per vector; every visit writes the same bits, so the revisit is benign).
    """
    hu, rank = _block_hash_rank(seed_ref, val_ref[0], pl.program_id(1),
                                variant)
    h_ref[...] = hu
    rank_ref[0] = rank


def hash_rank_batched_pallas(values3d: jnp.ndarray, seed: jnp.ndarray, *,
                             variant: str = "l2", interpret: bool):
    """Batched fused pass: values3d (D, rows, 128) f32, rows % 8 == 0.

    Returns (h (rows, 128), rank (D, rows, 128)): hash + weight + rank for a
    whole (D, n) corpus block in one HBM pass — the 2D extension of
    ``hash_rank_pallas`` that feeds the sketch_build pipeline.
    """
    D, rows, lanes = values3d.shape
    assert lanes == LANES
    rt = row_tile(rows)
    kern = functools.partial(_batched_kernel, variant=variant)
    h, rank = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((D, rows, LANES), jnp.float32)),
        grid=(D, rows // rt),
        in_specs=[seed_spec(),
                  pl.BlockSpec((1, rt, LANES), lambda d, j: (d, j, 0))],
        out_specs=(pl.BlockSpec((rt, LANES), lambda d, j: (j, 0)),
                   pl.BlockSpec((1, rt, LANES), lambda d, j: (d, j, 0))),
        interpret=interpret,
    )(seed.reshape(1, 1).astype(jnp.int32), values3d)
    return h, rank
