"""Share of the all-pairs kernel's roofline (%): the logical corpus bytes
each launch reads (``costs.allpairs_corpus_bytes``) over the HBM bandwidth,
divided by the kernel's time in the trace.  Bound by bytes.  Layer:
kernels."""
from bench import costs, trace as T


def read(rec):
    if rec.trace is None:
        return None
    evs = T.matching(rec.trace.ops(), costs.KERNELS["allpairs"])
    if not evs:
        return None
    per = costs.allpairs_corpus_bytes(rec.facts["corpus_rows"],
                                      rec.facts["m"])
    return costs.roofline_pct(len(evs) * per,
                              sum(e.dur for e in evs) * 1e-9, rec.peaks)
