"""Map-reduce sketch construction over row-partitioned corpora (DESIGN.md §14).

Coordinated sketches merge (``repro.core.merge``), so a corpus whose rows
are split across partitions — a table sharded over hosts, a stream arriving
in chunks, a multi-device ``shard_map`` data axis — never needs the full
vectors in one place:

- **map**: each partition runs the linear-time fused builder
  (``repro.kernels.sketch_build``) on its column slice, hashing the *global*
  coordinates so the samples stay coordinated across partitions;
- **reduce**: the sketches fold together in one flat P-way union merge
  (associativity makes it equivalent to any pairwise merge tree, at one
  rank-selection pass total).  Priority merges are bit-exact against the
  single-shot build; threshold merges fold ``PartitionStats`` (additive
  O(1) state) alongside to recompute the adaptive tau.

Three entry points: :func:`tree_merge_sketches` (the reduce alone — also
the streaming re-ingestion primitive: rebuild one dirty partition, re-merge),
:func:`partitioned_sketch_corpus` (single-host map-reduce over column
slices), and :func:`partitioned_sketch_corpus_sharded` (the same program as
a ``shard_map`` over a mesh data axis, one partition per device; the only
cross-device communication is the all-gather of m-sized sketches).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.merge import (PartitionStats, merge_sketches_many,
                              partition_stats)
from repro.core.sketches import Sketch, default_capacity


def partition_bounds(n: int, num_partitions: int) -> list:
    """Contiguous [start, stop) column ranges covering ``n`` coordinates."""
    if not 1 <= num_partitions <= n:
        raise ValueError(f"need 1 <= num_partitions <= n, got "
                         f"{num_partitions} for n={n}")
    step = -(-n // num_partitions)
    return [(s, min(s + step, n)) for s in range(0, n, step)]


def tree_merge_sketches(parts, seed, *, m: int, method: str = "priority",
                        variant: str = "l2", cap: int | None = None,
                        adaptive: bool = True,
                        stats: PartitionStats | None = None,
                        dedupe: bool = True) -> Sketch:
    """Fold P partition sketches into the merged sketch.

    ``parts``: a list of same-seed sketches, or a stacked ``Sketch`` with a
    leading partition dim — (P, cap) single-vector parts or (P, D, cap)
    corpus parts.  The merge is associative, so any reduction tree yields
    the same result; this fold therefore runs as ONE flat P-way union
    (``merge_sketches_many``): one rank-selection pass and one compaction
    regardless of P, cheaper than pairwise rounds on sketch-sized data.
    ``stats`` (leading dim P, required for adaptive threshold) folds
    alongside.  Pass ``dedupe=False`` when the partitions are disjoint by
    construction (column slices) to skip the cross-part duplicate scan.
    """
    return merge_sketches_many(parts, seed, m=m, method=method,
                               variant=variant, cap=cap, adaptive=adaptive,
                               stats=stats, dedupe=dedupe)


def _build_partition(block, m, seed, *, method, variant, cap, adaptive,
                     indices, use_pallas=None):
    # local import: repro.kernels imports from repro.core at module scope
    from repro.kernels.sketch_build import (build_priority_corpus,
                                            build_threshold_corpus)
    if method == "priority":
        return build_priority_corpus(block, m, seed, variant=variant,
                                     indices=indices, use_pallas=use_pallas)
    if method == "threshold":
        return build_threshold_corpus(block, m, seed, variant=variant,
                                      cap=cap, adaptive=adaptive,
                                      indices=indices, use_pallas=use_pallas)
    raise ValueError(f"unknown method {method!r}")


def partitioned_sketch_corpus(A: jnp.ndarray, m: int, seed, *,
                              num_partitions: int, method: str = "priority",
                              variant: str = "l2", cap: int | None = None,
                              adaptive: bool = True,
                              use_pallas: bool | None = None) -> Sketch:
    """Single-host map-reduce build: sketch ``num_partitions`` column slices
    of (D, n) independently, then tree-merge.

    Estimator-equivalent to ``sketch_corpus(A, ...)`` — bit-exact for
    priority, summation-order tau rounding for threshold — while only ever
    touching one n/P-column slice at a time (the memory/streaming story) and
    hashing global coordinates via the builders' sparse ``indices`` path.
    """
    A = jnp.atleast_2d(jnp.asarray(A, jnp.float32))
    if method == "threshold" and cap is None:
        cap = default_capacity(m)
    parts, stats = [], []
    for (s, e) in partition_bounds(A.shape[1], num_partitions):
        block = A[:, s:e]
        idxs = jnp.arange(s, e, dtype=jnp.int32)
        parts.append(_build_partition(block, m, seed, method=method,
                                      variant=variant, cap=cap,
                                      adaptive=adaptive, indices=idxs,
                                      use_pallas=use_pallas))
        if method == "threshold":
            stats.append(partition_stats(block, variant=variant))
    st = None
    if stats:
        st = PartitionStats(
            total_weight=jnp.stack([s_.total_weight for s_ in stats]),
            nnz=jnp.stack([s_.nnz for s_ in stats]))
    # column slices are disjoint by construction: skip the duplicate scan
    return tree_merge_sketches(parts, seed, m=m, method=method,
                               variant=variant, cap=cap, adaptive=adaptive,
                               stats=st, dedupe=False)


def partitioned_matrix_sketch(A: jnp.ndarray, m: int, seed, *,
                              num_partitions: int, method: str = "priority",
                              variant: str = "l2", cap: int | None = None,
                              adaptive: bool = True):
    """Map-reduce build of a matrix sketch over ``num_partitions`` *row*
    slices of an (n, d) matrix (DESIGN.md §15).

    Each slice is sketched with the linear-time matrix builders hashing its
    *global* row ids (the builders' ``row_indices`` path), then one flat
    P-way union merge (``repro.matrix.merge_matrix_sketches``) folds the
    partition sketches — bit-exact against the single-shot
    ``priority_matrix_sketch`` of the full matrix; threshold folds
    ``matrix_partition_stats`` alongside to recompute the adaptive tau.
    Only one n/P-row slice is ever touched at a time (the streaming /
    multi-host ingestion story of §14, one level up).
    """
    from repro.matrix import (matrix_partition_stats, merge_matrix_sketches,
                              priority_matrix_sketch, threshold_matrix_sketch)
    from repro.core.merge import PartitionStats
    A = jnp.asarray(A, jnp.float32)
    if A.ndim != 2:
        raise ValueError(f"expected an (n, d) matrix, got shape {A.shape}")
    parts, stats = [], []
    for (s, e) in partition_bounds(A.shape[0], num_partitions):
        block = A[s:e]
        ids = jnp.arange(s, e, dtype=jnp.int32)
        if method == "priority":
            parts.append(priority_matrix_sketch(block, m, seed,
                                                variant=variant,
                                                row_indices=ids))
        elif method == "threshold":
            parts.append(threshold_matrix_sketch(block, m, seed,
                                                 variant=variant, cap=cap,
                                                 adaptive=adaptive,
                                                 row_indices=ids))
            stats.append(matrix_partition_stats(block, variant=variant))
        else:
            raise ValueError(f"unknown method {method!r}")
    st = None
    if stats:
        st = PartitionStats(
            total_weight=jnp.stack([s_.total_weight for s_ in stats]),
            nnz=jnp.stack([s_.nnz for s_ in stats]))
    # row slices are disjoint by construction: skip the duplicate scan (the
    # merge still raises if the output surfaces a duplicate id)
    return merge_matrix_sketches(parts, seed, m=m, method=method,
                                 variant=variant, cap=cap, adaptive=adaptive,
                                 stats=st, dedupe=False)


def partitioned_sketch_corpus_sharded(A: jnp.ndarray, m: int, seed, *,
                                      mesh: Mesh | None = None,
                                      axis_name: str = "data",
                                      method: str = "priority",
                                      variant: str = "l2",
                                      cap: int | None = None,
                                      adaptive: bool = True) -> Sketch:
    """The map-reduce build as one ``shard_map`` program over a mesh data
    axis: each device sketches its column shard with the fused builder, the
    m-sized sketches all-gather (the only communication), and every device
    folds the same merge tree — the result is replicated.

    ``n`` must divide by the axis size.  With no ``mesh`` given, a 1-D mesh
    over all local devices is built.
    """
    A = jnp.atleast_2d(jnp.asarray(A, jnp.float32))
    if mesh is None:
        mesh = jax.make_mesh((jax.device_count(),), (axis_name,))
    n_shards = mesh.shape[axis_name]
    D, n = A.shape
    if n % n_shards != 0:
        raise ValueError(f"n={n} must divide over {n_shards} shards")
    shard_n = n // n_shards
    if method == "threshold" and cap is None:
        cap = default_capacity(m)

    def local(block):
        i = jax.lax.axis_index(axis_name)
        idxs = (i * shard_n + jnp.arange(shard_n)).astype(jnp.int32)
        sk = _build_partition(block, m, seed, method=method, variant=variant,
                              cap=cap, adaptive=adaptive, indices=idxs)
        st = partition_stats(block, variant=variant) \
            if method == "threshold" else None
        gathered = jax.lax.all_gather(sk, axis_name)       # (P, D, cap)
        gst = jax.lax.all_gather(st, axis_name) if st is not None else None
        return tree_merge_sketches(gathered, seed, m=m, method=method,
                                   variant=variant, cap=cap,
                                   adaptive=adaptive, stats=gst,
                                   dedupe=False)

    fn = jax.shard_map(local, mesh=mesh, in_specs=P(None, axis_name),
                       out_specs=P(), check_vma=False)
    return fn(A)
