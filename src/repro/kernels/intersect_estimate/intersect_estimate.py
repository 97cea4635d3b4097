"""Pallas TPU kernel: batched sketch-intersection estimation.

The estimator (Algorithm 2) intersects K_a with K_b.  On CPU that is a hash
join / sorted merge — data-dependent control flow that TPUs hate.  We
*bucketize* sketches instead: entry ``i`` lands in bucket ``hash(i) mod B``
(the hash is shared, so coordinated sketches agree on the bucket), with at
most S slots per bucket.  Intersection then becomes, per bucket, an S x S
lane-wise equality compare — no sorting, no dynamic shapes, O(m S^2 / B)
work per pair, fully vectorizable over a corpus tile.  This is the TPU
analogue of the paper's O(m) merge (DESIGN.md §4) and is what makes the
O(D^2 m) all-pairs workload of Section 1 MXU/VPU-friendly.

``allpairs_estimate_pallas`` runs a (QT x CT) grid over *two* bucketized
corpora and emits the full (D1, D2) estimate matrix in one launch — the
all-pairs join/correlation-discovery workload (DESIGN.md §12), and with
D1 = 1 the one-query serving path.  Inclusion probabilities are
precomputed per slot on the host (O(D B S), trivial next to the
O(D^2 B S^2) kernel work), which keeps the kernel agnostic of the weight
variant and lets the join-correlation path reuse it with its
max-of-three-families probabilities (DESIGN.md §7).  With ``moments=True``
the kernel accumulates all six co-moment channels of Eq. (9) —
(1,a,a^2) x (1,b,b^2) — in one pass over the intersection.

TPU layout: the wrapper re-lays both corpora slot-major, (S, D, B), so the
B buckets run along the 128 lanes and one slot of a tile is a (rows, B)
slab; the (B, S) layout would put S = 4 on the lanes and pad 32x.  Each
query row accumulates its S x S slot compares elementwise over an
(8, B) corpus chunk at a time, and one (1, B) x (ct, B)^T matmul against
ones sums the buckets of all ct corpus rows straight into an output row
whose corpus axis lies on the lanes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INVALID_IDX = np.int32(np.iinfo(np.int32).max)
CT = 128  # default corpus sketches per grid step (the output's lane width)
QT = 8    # default query-side sketches per grid step
CHUNK = 8  # corpus rows per inner accumulation step (one sublane tile)

# channel order of the moments=True output (matches Eq. (9) notation)
MOMENT_CHANNELS = ("n", "sum_x", "sum_y", "xy", "sum_x2", "sum_y2")

_NT = (((1,), (1,)), ((), ()))  # contract the bucket (lane) axis of both


def _allpairs_kernel(ai_ref, av_ref, ar_ref, bi_ref, bv_ref, br_ref,
                     out_ref, acc_ref, *, moments: bool):
    """One (qt, ct) output tile: every A sketch in the tile vs every B sketch.

    Refs are slot-major: A (S, qt, B), B (S, ct, B); ``ar``/``br`` hold
    reciprocal inclusion probabilities.  Two algebraic moves keep the inner
    loop lean (DESIGN.md §12): 1/min(pa, pb) == max(1/pa, 1/pb), and the
    two sides' padding carries *distinct negative* sentinels (-1 / -2) —
    real indices are >= 0, so padding can match neither padding nor data
    and the loop needs no validity mask.
    """
    S, qt, B = ai_ref.shape
    ct = bi_ref.shape[1]
    chunk = math.gcd(ct, CHUNK)
    n_ch = len(MOMENT_CHANNELS) if moments else 1
    ones = jnp.ones((1, B), jnp.float32)

    def query_row(i, carry):
        q = pl.ds(i, 1)
        a = [(ai_ref[s, q, :], av_ref[s, q, :], ar_ref[s, q, :])
             for s in range(S)]                                # (1, B) each

        def corpus_chunk(c, carry):
            rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
            acc = [jnp.zeros((chunk, B), jnp.float32) for _ in range(n_ch)]
            for sc in range(S):
                bi = bi_ref[sc, rows, :]                       # (chunk, B)
                bv = bv_ref[sc, rows, :]
                br = br_ref[sc, rows, :]
                for ai, av, ar in a:
                    eq = ai == bi
                    if moments:
                        inv = jnp.where(eq, jnp.maximum(ar, br), 0.0)
                        acc[0] += inv                          # n
                        acc[1] += av * inv                     # sum_x
                        acc[2] += bv * inv                     # sum_y
                        acc[3] += av * bv * inv                # xy
                        acc[4] += av * av * inv                # sum_x2
                        acc[5] += bv * bv * inv                # sum_y2
                    else:
                        acc[0] += jnp.where(
                            eq, av * bv * jnp.maximum(ar, br), 0.0)
            for ch in range(n_ch):
                acc_ref[ch, rows, :] = acc[ch]
            return carry

        jax.lax.fori_loop(0, ct // chunk, corpus_chunk, 0)
        for ch in range(n_ch):
            est = jax.lax.dot_general(ones, acc_ref[ch], _NT,
                                      precision=jax.lax.Precision.HIGHEST,
                                      preferred_element_type=jnp.float32)
            if moments:
                out_ref[ch, q, :] = est                        # (1, ct)
            else:
                out_ref[q, :] = est
        return carry

    jax.lax.fori_loop(0, qt, query_row, 0)


def _slot_major(idx, val, p, sentinel: int):
    """(D, B, S) idx / val / inclusion probs -> the kernel's (S, D, B)
    idx (padding -> ``sentinel``), val and reciprocal probabilities."""
    idx = jnp.where(idx == INVALID_IDX, sentinel, idx)
    t = lambda x: jnp.transpose(x, (2, 0, 1))
    return (t(idx), t(val.astype(jnp.float32)),
            t(1.0 / p.astype(jnp.float32)))


def allpairs_estimate_pallas(a_idx, a_val, a_p, b_idx, b_val, b_p, *,
                             qt: int = QT, ct: int = CT,
                             moments: bool = False,
                             interpret: bool) -> jnp.ndarray:
    """Tiled all-pairs estimation over two bucketized corpora.

    a: (D1, B, S) idx/val plus per-slot inclusion probs ``a_p`` (same shape,
    values in (0, 1], 1.0 at padding); b: (D2, B, S) likewise.  D1 % qt == 0
    and D2 % ct == 0 (pad with INVALID_IDX rows — see ops.py).  On the TPU
    ``qt`` must be a multiple of 8 or all of D1, and ``ct`` a multiple of
    128 or all of D2.  Returns the (D1, D2) estimate matrix, or (D1, D2, 6)
    co-moment channels in ``MOMENT_CHANNELS`` order when ``moments=True``.
    """
    D1, B, S = a_idx.shape
    D2 = b_idx.shape[0]
    assert D1 % qt == 0 and D2 % ct == 0, (D1, qt, D2, ct)
    n_ch = len(MOMENT_CHANNELS) if moments else 1
    kern = functools.partial(_allpairs_kernel, moments=moments)
    a_spec = pl.BlockSpec((S, qt, B), lambda i, j: (0, i, 0))
    b_spec = pl.BlockSpec((S, ct, B), lambda i, j: (0, j, 0))
    if moments:
        out_shape = jax.ShapeDtypeStruct((n_ch, D1, D2), jnp.float32)
        out_spec = pl.BlockSpec((n_ch, qt, ct), lambda i, j: (0, i, j))
    else:
        out_shape = jax.ShapeDtypeStruct((D1, D2), jnp.float32)
        out_spec = pl.BlockSpec((qt, ct), lambda i, j: (i, j))
    out = pl.pallas_call(
        kern,
        out_shape=out_shape,
        grid=(D1 // qt, D2 // ct),
        in_specs=[a_spec] * 3 + [b_spec] * 3,
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((n_ch, ct, B), jnp.float32)],
        interpret=interpret,
    )(*_slot_major(a_idx, a_val, a_p, -1), *_slot_major(b_idx, b_val, b_p, -2))
    return jnp.moveaxis(out, 0, -1) if moments else out
