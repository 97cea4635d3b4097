"""Data generators, one module per configuration family, named by the
``data.generator`` key of a configuration file.

A module that the dense kinds of traffic (``open_query``, ``bulk_ingest``)
read offers ``corpus_block(data, seed, b, rows)`` -> ``Block`` (a dense
``(rows, universe)`` float32 block and the same columns as CSR), and, for
``open_query``, ``query_pool(data, seed, corpus, indexed, fresh)``.  That
contract binds only the kinds that call it: a new kind may read any
function of a new module, such as keyed CSR tables with no dense copy.
The generators are copies, kept here so that a change to the program
cannot move the yardstick.
"""
from __future__ import annotations

import dataclasses
import importlib

import numpy as np


@dataclasses.dataclass
class Block:
    """Columns as a dense (rows, universe) float32 block and as CSR
    (``indptr`` (rows+1,), ascending ``keys`` per row, ``vals`` float32)."""

    dense: np.ndarray
    indptr: np.ndarray
    keys: np.ndarray
    vals: np.ndarray


@dataclasses.dataclass
class Csr:
    """Many columns as CSR (no dense copy)."""

    indptr: np.ndarray
    keys: np.ndarray
    vals: np.ndarray

    @property
    def rows(self) -> int:
        return self.indptr.size - 1

    @classmethod
    def concat(cls, parts) -> "Csr":
        parts = list(parts)
        counts = np.concatenate([np.diff(p.indptr) for p in parts])
        indptr = np.concatenate([[0], np.cumsum(counts)]).astype(np.int64)
        return cls(indptr, np.concatenate([p.keys for p in parts]),
                   np.concatenate([p.vals for p in parts]))

    def row(self, i: int):
        lo, hi = self.indptr[i], self.indptr[i + 1]
        return self.keys[lo:hi], self.vals[lo:hi]

    def dense_row(self, i: int, universe: int) -> np.ndarray:
        out = np.zeros((universe,), np.float32)
        k, v = self.row(i)
        out[k] = v
        return out


def generator(name: str):
    """The generator module named by a configuration's ``data.generator``."""
    if not name.isidentifier():
        raise ValueError(f"bad generator name {name!r}")
    return importlib.import_module(f"{__name__}.{name}")
