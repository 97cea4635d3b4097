"""Device time of the ops that are not Pallas kernels (query sketch,
bucketize, probabilities, slot-major transposes), summed over the traced
window and divided by the requests served in it (ms).  Layer: engine / XLA
glue."""
import numpy as np

from bench import costs, trace as T


def read(rec):
    if rec.trace is None:
        return None
    ops = rec.trace.ops()
    kernel = set(map(id, T.matching(ops, costs.PALLAS)))
    glue = sum(e.dur for e in ops if id(e) not in kernel)
    served = sum(1 for r in rec.requests if np.isfinite(r["end"]))
    if not ops or not served:
        return None
    return glue * 1e-6 / served
