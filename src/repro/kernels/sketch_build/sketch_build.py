"""Pallas TPU kernels: fused batched sketch construction (the O(N) build).

The construction hot loop of Algorithms 1/3 is (a) hash every coordinate,
(b) weight every value, (c) divide into sampling ranks, (d) find a rank
order statistic (the (m+1)-st smallest rank for priority sampling / the
overflow cut for threshold sampling), and (e) compact the kept entries.
The legacy path does (d) with a full per-row sort or ``top_k`` over all n —
O(n log n) — and (a)-(c) in separate HBM passes per vector.

Two kernels make the whole build linear time (DESIGN.md §13):

- ``hash_rank_hist_pallas``: one HBM pass over a (D, n) block that fuses
  hash + weight + rank (the 2D extension of ``kernels/hash_rank``) and, in
  the same pass, accumulates a per-row **log-domain histogram** of the rank
  bit patterns: the top 8 bits of a positive float32 are its sign (always 0
  for ranks) and exponent, so the 256 fixed-width bins partition ranks by
  powers of two.  IEEE-754 positive floats compare like their unsigned bit
  patterns, so bin counts are exactly the level-0 refinement of any rank
  order statistic.
- ``rank_hist_pallas``: one refinement level — counts the next 8 bits of
  every rank whose higher bits match a per-row prefix.  Four levels resolve
  all 32 bits, i.e. the *exact* k-th smallest rank, in O(n) work per level
  with no sort and no data-dependent shapes.

Both kernels count into a (NBINS, 8, 128) VMEM accumulator — one
compare-and-add per bin over the value tile, lanes kept apart — and fold it
into the row's (1, NBINS) histogram once, on the row's last grid step.  Per
row scalars (the refinement prefix) arrive as (D, 1, 1) arrays with
(1, 1, 1) blocks, and histograms leave as (D, 1, NBINS): the TPU block
rule wants the last two block dims to be whole array dims or (8, 128)
multiples.

Off-TPU the same selection runs as a fused XLA formulation (see ops.py);
both are bit-exact because the k-th order statistic is a pure bit-pattern
question.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..hash_rank.hash_rank import (LANES, SUBLANES, _block_hash_rank,
                                   row_tile, seed_spec)

NBINS = 256  # one level resolves 8 bits of the rank's bit pattern


def _count_bins(acc_ref, digits: jnp.ndarray) -> None:
    """acc[b] += per-lane count of ``digits == b`` for every bin b.

    ``digits``: (R, LANES) int32; inactive keys carry NBINS and match no
    bin.  Rows fold onto one (SUBLANES, LANES) tile per bin, so the update
    is whole-vreg compares and adds with no cross-lane work."""
    rows = digits.shape[0]

    def body(b, carry):
        hit = (digits == b).astype(jnp.int32)
        acc_ref[b] += hit.reshape(rows // SUBLANES, SUBLANES, LANES).sum(0)
        return carry

    jax.lax.fori_loop(0, NBINS, body, 0)


def _histogram_step(acc_ref, hist_ref, digits: jnp.ndarray) -> None:
    """One grid step of a per-row histogram over the row's value tiles."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _count_bins(acc_ref, digits)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        per_lane = jnp.sum(acc_ref[...], axis=1)              # (NBINS, LANES)
        hist_ref[0] = jnp.sum(per_lane.T, axis=0, keepdims=True)


def _acc_scratch():
    return [pltpu.VMEM((NBINS, SUBLANES, LANES), jnp.int32)]


def _hash_rank_hist_kernel(seed_ref, val_ref, h_ref, rank_ref, hist_ref,
                           acc_ref, *, variant: str):
    hu, rank = _block_hash_rank(seed_ref, val_ref[0], pl.program_id(1),
                                variant)
    h_ref[...] = hu
    rank_ref[0] = rank
    # log-domain level: top 8 bits = sign (0) + exponent of the rank
    u = jax.lax.bitcast_convert_type(rank, jnp.uint32)
    _histogram_step(acc_ref, hist_ref,
                    (u >> np.uint32(32 - 8)).astype(jnp.int32))


def hash_rank_hist_pallas(values3d: jnp.ndarray, seed: jnp.ndarray, *,
                          variant: str = "l2", interpret: bool):
    """One fused HBM pass over values3d (D, rows, 128), rows % 8 == 0.

    Returns ``h (rows, 128)``, ``rank (D, rows, 128)`` and the level-0
    log-domain histogram ``hist (D, NBINS)`` of the rank bit patterns.
    """
    D, rows, lanes = values3d.shape
    assert lanes == LANES
    rt = row_tile(rows)
    kern = functools.partial(_hash_rank_hist_kernel, variant=variant)
    h, rank, hist = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((D, rows, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((D, 1, NBINS), jnp.int32)),
        grid=(D, rows // rt),
        in_specs=[seed_spec(),
                  pl.BlockSpec((1, rt, LANES), lambda d, j: (d, j, 0))],
        out_specs=(pl.BlockSpec((rt, LANES), lambda d, j: (j, 0)),
                   pl.BlockSpec((1, rt, LANES), lambda d, j: (d, j, 0)),
                   pl.BlockSpec((1, 1, NBINS), lambda d, j: (d, 0, 0))),
        scratch_shapes=_acc_scratch(),
        interpret=interpret,
    )(seed.reshape(1, 1).astype(jnp.int32), values3d)
    return h, rank, hist.reshape(D, NBINS)


def _rank_hist_kernel(prefix_ref, keys_ref, hist_ref, acc_ref, *,
                      shift: int):
    u = jax.lax.bitcast_convert_type(keys_ref[0], jnp.uint32)
    digits = ((u >> np.uint32(shift)) & np.uint32(0xFF)).astype(jnp.int32)
    if shift < 24:
        prefix = prefix_ref[0].astype(jnp.uint32)             # (1, 1)
        active = (u >> np.uint32(shift + 8)) == prefix
        digits = jnp.where(active, digits, NBINS)
    _histogram_step(acc_ref, hist_ref, digits)


def rank_hist_pallas(keys3d: jnp.ndarray, prefix: jnp.ndarray, *, shift: int,
                     interpret: bool) -> jnp.ndarray:
    """One histogram refinement level over rank keys (D, rows, 128) f32.

    Counts ``(bits(key) >> shift) & 0xFF`` for every key whose higher bits
    equal the per-row ``prefix (D,) uint32``; returns ``(D, NBINS) int32``.
    ``shift`` descends 24 -> 16 -> 8 -> 0 to resolve the full 32-bit pattern.
    """
    D, rows, lanes = keys3d.shape
    assert lanes == LANES
    rt = row_tile(rows)
    kern = functools.partial(_rank_hist_kernel, shift=shift)
    hist = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((D, 1, NBINS), jnp.int32),
        grid=(D, rows // rt),
        in_specs=[pl.BlockSpec((1, 1, 1), lambda d, j: (d, 0, 0)),
                  pl.BlockSpec((1, rt, LANES), lambda d, j: (d, j, 0))],
        out_specs=pl.BlockSpec((1, 1, NBINS), lambda d, j: (d, 0, 0)),
        scratch_shapes=_acc_scratch(),
        interpret=interpret,
    )(prefix.reshape(D, 1, 1).astype(jnp.int32), keys3d)
    return hist.reshape(D, NBINS)
