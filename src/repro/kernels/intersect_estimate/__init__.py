from .intersect_estimate import MOMENT_CHANNELS
from .ops import (BucketizedSketch, allpairs_moments, bucketize,
                  bucketize_corpus, bucketize_payloads,
                  estimate_all_pairs_bucketized, estimate_tile_rows,
                  query_corpus, round_up_pow2, sketch_and_query,
                  slot_inclusion_probs)
from .ref import allpairs_estimate_ref, intersect_estimate_ref

__all__ = ["BucketizedSketch", "bucketize", "bucketize_corpus",
           "bucketize_payloads", "query_corpus", "sketch_and_query",
           "intersect_estimate_ref",
           "allpairs_estimate_ref", "estimate_all_pairs_bucketized",
           "estimate_tile_rows",
           "allpairs_moments", "slot_inclusion_probs", "round_up_pow2",
           "MOMENT_CHANNELS"]
