"""What the kinds of traffic share: the harness's clock, the ``Window`` a
measured window returns, the relative gap the checks compare, and, for the
kinds that serve a ``SketchIndex``, the index built from a configuration
and the rows it stores.

A kind is named by the traffic file's ``kind`` and lives in
``kinds/<kind>.py`` under the cell's benchmark directory (``bench.kinds``
holds the contract); ``traffic_kind`` loads it from there.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import reference, spec

clock = time.perf_counter


def sleep_until(t: float) -> None:
    """Sleep to within half a millisecond of ``t``, then spin."""
    while True:
        left = t - clock()
        if left <= 0:
            return
        if left > 0.0015:
            time.sleep(left - 0.001)


def sketch_index(config: dict):
    """An empty ``SketchIndex`` with the configuration's ``index``
    parameters."""
    from repro.serve import SketchIndex
    p = config["index"]
    return SketchIndex(p["m"], n_buckets=p["n_buckets"], slots=p["slots"],
                       seed=p["seed"], head_h=p["head_h"])


def layout_kw(config: dict) -> dict:
    """The reference's sketch and layout parameters of the configuration's
    index."""
    p = config["index"]
    return dict(m=p["m"], seed=p["seed"], n_buckets=p["n_buckets"],
                slots=p["slots"])


def stored_rows(index, rows: np.ndarray) -> reference.Layout:
    """The index's stored bucketized rows, from its host master copy."""
    return reference.Layout(index._idx[rows], index._val[rows],
                            index._tau[rows], index._dropped[rows])


def gap(got, want, scale) -> np.ndarray:
    """``|got - want|`` over ``|want| + scale`` (the plain difference where
    that is 0)."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    den = np.abs(want) + scale
    return np.where(den > 0, np.abs(got - want) / np.where(den > 0, den, 1),
                    np.abs(got - want))


@dataclasses.dataclass
class Window:
    """What the measured window did, on the harness clock (seconds from its
    start)."""

    seconds: float          # length of the window, to the last completion
    attempted: int
    failed: int
    e2e: dict               # end-to-end metric name -> value
    requests: list          # per request or call: dict of its facts
    facts: dict             # sizes the metric readers need


def traffic_kind(cell: spec.Cell):
    """The ``Kind`` class of the cell's traffic kind, loaded from its file
    under the cell's benchmark directory (``spec.load_cell`` has checked
    that the file is there)."""
    return spec.load_file(cell.bench_dir,
                          f"kinds/{cell.traffic['kind']}.py").Kind
