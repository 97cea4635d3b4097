"""Bytes and operations of the kernels, as functions of the shapes, and the
patterns that find each kernel's events in a trace.

The all-pairs and build kernels do VPU compares, hashes and counts, for
which the chip has no published peak, so their least time is bytes over the
HBM bandwidth of ``peaks.json``: their roofline shares are bound by bytes.
"""
from __future__ import annotations

# Regular expressions over a trace event's text (an XLA op's HLO line).  The
# Pallas kernels pass no ``name=``, so a kernel's custom call is named after
# the jitted function that launches it: the all-pairs kernel from the query
# and discovery-tile wrappers, the build kernels from the build shim.
KERNELS = {
    "allpairs": r"^%_(query_corpus_jit|estimate_tile_rows_jit|allpairs)\S* = "
                r".*tpu_custom_call",
    "build": r"^%_build_priority_payload\S* = .*tpu_custom_call",
}
PALLAS = r"custom_call_target=\"tpu_custom_call\""


def allpairs_corpus_bytes(rows: int, m: int) -> int:
    """Logical corpus read by one all-pairs launch against ``rows`` corpus
    sketches of ``m`` entries: an int32 id and a float32 value per kept
    entry, and tau, whatever the layout pads them to."""
    return rows * (m * 8 + 4)


def build_block_bytes(rows: int, universe: int) -> int:
    """The dense float32 (rows, universe) block, read once."""
    return rows * universe * 4


def roofline_pct(bytes_moved: float, seconds: float, peaks: dict,
                 flops: float = 0.0) -> float | None:
    """Least time (the larger of bytes over HBM bandwidth and operations
    over the bf16 peak) as a percentage of ``seconds``; None without time."""
    if seconds <= 0:
        return None
    least = max(bytes_moved / peaks["hbm_bytes_per_s"],
                flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
