"""Median time inside the served call, on the harness clock, with no
queueing (ms).  Layer: service."""
import numpy as np


def read(rec):
    d = [r["end"] - r["start"] for r in rec.requests
         if np.isfinite(r["end"]) and np.isfinite(r["start"])]
    return float(np.median(d)) * 1e3 if d else None
