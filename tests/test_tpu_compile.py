"""AOT compiles of every main-path Pallas kernel for a TPU v5e.

Interpret mode accepts blocks and casts that Mosaic refuses, so each
kernel of the served path is compiled here with ``interpret=False`` for a
described (not attached) v5e chip, at the shapes ``chip_smoke.py`` runs:
a (256, 2^18) build block, an 8192-row corpus of (512, 4) buckets, a
512-row merge, 64-row discovery tiles; and the served query program at
the query cell's shapes (a 2^20 vector against 4096 rows of (1024, 4)).
Each compile must hold a Mosaic kernel (``tpu_custom_call``) and fit the
chip's memory.  The topology is described inside a fixture, so collection
never loads the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import BucketizedSketch, dispatch, sketch_and_query
from repro.kernels.hash_rank.hash_rank import (hash_rank_batched_pallas,
                                               hash_rank_pallas)
from repro.kernels.intersect_estimate.intersect_estimate import \
    allpairs_estimate_pallas
from repro.kernels.matrix_sketch.matrix_sketch import matrix_products_pallas
from repro.kernels.sketch_build.sketch_build import (hash_rank_hist_pallas,
                                                     rank_hist_pallas)
from repro.kernels.sketch_merge.sketch_merge import merge_bucketized_pallas

HBM_BYTES = 16 * 2**30          # one v5e chip
ROWS, N = 256, 1 << 18          # build block (columns x key universe)
D, B, S = 8192, 512, 4          # served corpus, SketchIndex layout
MERGE_ROWS, TILE, PAIRS = 512, 64, 512
f32, i32, u32 = jnp.float32, jnp.int32, jnp.uint32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip are written to the persistent cache but
    # cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, t, sharding=sharding) for s, t in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES
    return compiled


def _corpus(rows):
    return [((rows, B, S), i32), ((rows, B, S), f32), ((rows, B, S), f32)]


def test_hash_rank_compiles(one_chip):
    _compile(lambda v, s: hash_rank_pallas(v, s, interpret=False), one_chip,
             ((N // 128, 128), f32), ((), i32))


def test_hash_rank_batched_compiles(one_chip):
    _compile(lambda v, s: hash_rank_batched_pallas(v, s, interpret=False),
             one_chip, ((ROWS, N // 128, 128), f32), ((), i32))


def test_hash_rank_hist_compiles(one_chip):
    _compile(lambda v, s: hash_rank_hist_pallas(v, s, interpret=False),
             one_chip, ((ROWS, N // 128, 128), f32), ((), i32))


@pytest.mark.parametrize("shift", [24, 16, 8, 0])
def test_rank_hist_compiles(one_chip, shift):
    _compile(lambda k, p: rank_hist_pallas(k, p, shift=shift,
                                           interpret=False),
             one_chip, ((ROWS, N // 128, 128), f32), ((ROWS,), u32))


@pytest.mark.parametrize("moments", [False, True])
def test_allpairs_compiles(one_chip, moments):
    _compile(lambda *a: allpairs_estimate_pallas(*a, moments=moments,
                                                 interpret=False),
             one_chip, *_corpus(D), *_corpus(D))


def test_query_row_compiles(one_chip):
    # SketchIndex.query: one bucketized query row against the corpus
    _compile(lambda *a: allpairs_estimate_pallas(*a, qt=1, interpret=False),
             one_chip, *_corpus(1), *_corpus(D))


def test_served_query_program_compiles(one_chip, monkeypatch):
    # SketchIndex.query as one program at the query cell's shapes: sketch of
    # a 2^20 vector, bucketize, the all-pairs launch against 4096 rows of
    # (1024, 4); its kernel keeps the name the all-pairs roofline reads
    from bench.costs import KERNELS
    from repro.kernels.intersect_estimate import ops
    monkeypatch.setattr(dispatch, "on_tpu", lambda: True)
    sds = lambda shape, t: jax.ShapeDtypeStruct(shape, t, sharding=one_chip)
    rows, nb = 4096, 1024
    corpus = BucketizedSketch(sds((rows, nb, S), i32), sds((rows, nb, S), f32),
                              sds((rows,), f32), sds((rows,), i32))
    jits = (sketch_and_query, ops._query_corpus_jit)
    for fn in jits:       # no trace lowered for the CPU is reused, or kept
        fn.clear_cache()
    try:
        compiled = sketch_and_query.lower(
            sds((1 << 20,), f32), corpus, sds((), u32), m=512, n_buckets=nb,
            slots=S, use_pallas=True).compile()
    finally:
        for fn in jits:
            fn.clear_cache()
    kernels = [ln.strip() for ln in compiled.as_text().splitlines()
               if "tpu_custom_call" in ln]
    assert kernels and all(re.search(KERNELS["allpairs"], ln)
                           for ln in kernels), kernels
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


def test_discovery_tile_compiles(one_chip):
    # estimate_tile_rows: gathered (64, 64) tiles of the discovery scan
    _compile(lambda *a: allpairs_estimate_pallas(*a, qt=8, ct=TILE,
                                                 interpret=False),
             one_chip, *_corpus(TILE), *_corpus(TILE))


def test_merge_compiles(one_chip):
    _compile(lambda ai, av, bi, bv, t, s: merge_bucketized_pallas(
        ai, av, bi, bv, t, s, interpret=False), one_chip,
        ((MERGE_ROWS, B, S), i32), ((MERGE_ROWS, B, S), f32),
        ((MERGE_ROWS, B, S), i32), ((MERGE_ROWS, B, S), f32),
        ((MERGE_ROWS,), f32), ((), i32))


def test_matrix_products_compile(one_chip):
    side = [((PAIRS, B, S), i32), ((PAIRS, B, S, 1), f32),
            ((PAIRS, B, S), f32)]
    _compile(lambda *a: matrix_products_pallas(*a, interpret=False),
             one_chip, *side, *side)
