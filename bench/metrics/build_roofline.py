"""Share of the build kernels' roofline (%): the dense block read once
(``costs.build_block_bytes``) per ``add_many`` call in the traced window,
over the HBM bandwidth, divided by the time of the build kernels
(``hash_rank_hist`` and the ``rank_hist`` refinement levels) in the trace.
Bound by bytes.  Layer: kernels."""
from bench import costs, trace as T


def read(rec):
    if rec.trace is None:
        return None
    evs = T.matching(rec.trace.ops(), costs.KERNELS["build"])
    calls = len(T.spans(rec.trace.host, "bench.add_many"))
    if not evs or not calls:
        return None
    per = costs.build_block_bytes(rec.facts["block_rows"],
                                  rec.facts["universe"])
    return costs.roofline_pct(calls * per, sum(e.dur for e in evs) * 1e-9,
                              rec.peaks)
