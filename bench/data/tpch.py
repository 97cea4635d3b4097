"""Skewed TPC-H join-key frequency vectors: one column per ``l_shipdate``
day, holding how many of that day's lineitems carry each ``l_partkey``.

Partkey popularity is Zipf(``zipf``) over ``parts`` keys, the skewed-dbgen
law of Chaudhuri and Narasayya, shared by every day through one
seed-derived permutation; key ids ``parts`` .. ``universe``-1 are never
drawn.  Draws past the last key fold onto it, as in
``repro.data.synthetic.zipf_frequency_tables`` (copied here).
Configuration keys (``data`` group): ``universe``, ``parts``,
``rows_per_day``, ``zipf``.
"""
from __future__ import annotations

import numpy as np

from . import Block, Csr


def _order(data: dict, seed: int) -> np.ndarray:
    """Partkey popularity order, shared by every day."""
    return np.random.default_rng([seed, 0]).permutation(data["parts"])


def _days(data: dict, rng, rows: int, order: np.ndarray) -> Block:
    n, parts = data["universe"], data["parts"]
    ranks = np.minimum(rng.zipf(data["zipf"], (rows, data["rows_per_day"]))
                       - 1, parts - 1)
    dense = np.zeros((rows, n), np.float32)
    indptr, keys, vals = [0], [], []
    for r in range(rows):
        k, c = np.unique(order[ranks[r]], return_counts=True)
        dense[r, k] = c
        keys.append(k.astype(np.int64))
        vals.append(c.astype(np.float32))
        indptr.append(indptr[-1] + k.size)
    return Block(dense, np.asarray(indptr, np.int64), np.concatenate(keys),
                 np.concatenate(vals))


def corpus_block(data: dict, seed: int, b: int, rows: int) -> Block:
    """Dense (rows, universe) block ``b``: ``rows`` days of lineitems."""
    return _days(data, np.random.default_rng([seed, 1, b]), rows,
                 _order(data, seed))


def query_pool(data: dict, seed: int, corpus: Csr, indexed: int,
               fresh: int) -> np.ndarray:
    """(indexed + fresh, universe) query days: ``indexed`` days of the
    corpus drawn from the seed, asked again, then ``fresh`` new days of the
    same law (days not in the index)."""
    n = data["universe"]
    rng = np.random.default_rng([seed, 2])
    cols = rng.choice(corpus.rows, indexed, replace=False)
    out = np.zeros((indexed + fresh, n), np.float32)
    for i, c in enumerate(cols):
        out[i] = corpus.dense_row(int(c), n)
    if fresh:
        out[indexed:] = _days(data, np.random.default_rng([seed, 3]), fresh,
                              _order(data, seed)).dense
    return out
