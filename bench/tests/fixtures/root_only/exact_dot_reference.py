"""Exact inner products of every query with every column, in float64;
``bfloat16`` rounds the inputs (float32 sums) for the control."""
import ml_dtypes
import numpy as np


def dots(corpus, queries, precision="float64"):
    if precision == "bfloat16":
        r = lambda x: x.astype(ml_dtypes.bfloat16).astype(np.float32)
        return (r(queries) @ r(corpus).T).astype(np.float64)
    return queries.astype(np.float64) @ corpus.astype(np.float64).T
