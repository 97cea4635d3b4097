"""Pallas TPU kernels for the paper's compute hot spots.

Each kernel ships three files: ``<name>.py`` (pl.pallas_call + BlockSpec
VMEM tiling), ``ops.py`` (jit'd public wrapper) and ``ref.py`` (pure-jnp
oracle the tests assert against).  ``dispatch.py`` holds the one rule for
where a kernel runs: compiled by Mosaic on a TPU, interpret mode elsewhere.

- ``hash_rank``          fused hash + sampling rank (the O(N) loop of Algs 1/3)
- ``sketch_build``       batched linear-time sketch construction: fused 2D
  hash/rank pass + log-domain histogram rank selection + prefix-sum
  compaction — replaces the O(n log n) sort/top_k build path (DESIGN.md §13)
- ``countsketch``        CountSketch as one-hot MXU matmuls (scatter-free)
- ``jl_rademacher``      matrix-free JL projection (Pi regenerated in VMEM)
- ``intersect_estimate`` bucketized batched estimator: the tiled all-pairs /
  co-moments kernel that emits the full (D1, D2) estimate matrix in one
  launch (the O(D^2 m) workload); one query row against a corpus is the
  serving path
- ``sketch_merge``       batched merge of two bucketized corpora: per-bucket
  union + dedupe + rank re-cut in one launch for all D rows — the serving
  half of the partition-merge subsystem (DESIGN.md §14)
- ``matrix_sketch``      fused batched matrix-product estimation: row-id
  intersection + inclusion-probability rescale + sampled-rows matmul for a
  whole batch of coordinated matrix-sketch pairs in one launch — the
  ``A^T B`` workload of the matrix subsystem (DESIGN.md §15)
"""
from .hash_rank import (hash_rank, hash_rank_batched, hash_rank_batched_ref,
                        hash_rank_ref)
from .sketch_build import (build_combined_priority_corpus,
                           build_combined_threshold_corpus,
                           build_priority_corpus, build_threshold_corpus,
                           kth_smallest_ranks)
from .countsketch import countsketch as countsketch_kernel
from .countsketch import countsketch_ref
from .jl_rademacher import jl_project, jl_ref
from .sketch_merge import (merge_bucketized_corpora, merge_bucketized_pallas,
                           merge_bucketized_ref, merged_tau_bucketized)
from .matrix_sketch import (BucketizedMatrixSketch, bucketize_matrix_sketches,
                            matrix_products_bucketized, matrix_products_ref,
                            matrix_slot_probs, stack_matrix_sketches)
from .intersect_estimate import (MOMENT_CHANNELS, BucketizedSketch,
                                 allpairs_estimate_ref, allpairs_moments,
                                 bucketize, bucketize_corpus,
                                 bucketize_payloads,
                                 estimate_all_pairs_bucketized,
                                 estimate_tile_rows,
                                 intersect_estimate_ref, query_corpus,
                                 round_up_pow2, sketch_and_query,
                                 slot_inclusion_probs)

__all__ = [
    "hash_rank", "hash_rank_batched", "hash_rank_batched_ref", "hash_rank_ref",
    "build_priority_corpus", "build_threshold_corpus",
    "build_combined_priority_corpus", "build_combined_threshold_corpus",
    "kth_smallest_ranks",
    "merge_bucketized_corpora", "merge_bucketized_pallas",
    "merge_bucketized_ref", "merged_tau_bucketized",
    "BucketizedMatrixSketch", "bucketize_matrix_sketches",
    "matrix_products_bucketized", "matrix_products_ref", "matrix_slot_probs",
    "stack_matrix_sketches",
    "countsketch_kernel", "countsketch_ref",
    "jl_project", "jl_ref",
    "BucketizedSketch", "bucketize", "bucketize_corpus", "bucketize_payloads",
    "intersect_estimate_ref", "query_corpus", "sketch_and_query",
    "allpairs_estimate_ref",
    "estimate_all_pairs_bucketized", "estimate_tile_rows",
    "allpairs_moments",
    "slot_inclusion_probs", "round_up_pow2", "MOMENT_CHANNELS",
]
