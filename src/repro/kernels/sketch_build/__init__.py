from .combined import (build_combined_priority_corpus,
                       build_combined_threshold_corpus)
from .ops import (adaptive_tau_batched, build_priority_corpus,
                  build_threshold_corpus, kth_smallest_ranks, pack_kept)
from .ref import (build_combined_priority_corpus_ref,
                  build_combined_threshold_corpus_ref,
                  build_priority_corpus_ref, build_threshold_corpus_ref)
from .sketch_build import NBINS, hash_rank_hist_pallas, rank_hist_pallas

__all__ = [
    "adaptive_tau_batched", "build_priority_corpus", "build_threshold_corpus",
    "build_combined_priority_corpus", "build_combined_threshold_corpus",
    "build_priority_corpus_ref", "build_threshold_corpus_ref",
    "build_combined_priority_corpus_ref", "build_combined_threshold_corpus_ref",
    "kth_smallest_ranks", "pack_kept",
    "NBINS", "hash_rank_hist_pallas", "rank_hist_pallas",
]
