"""``correct`` on CPU at a tiny size, over every fixture cell: the
reference agrees with the program, and the bfloat16 control fails the
cell's limits (a tiny copy of a real cell borrows the real cell's, a
root-only kind has its own); a whole run with the timed path broken
underneath reports ``correct`` false for each fault the cells can have."""
import numpy as np
import pytest

from bench import drive, spec
from bench.harness import run_cell
from bench.tests.tiny import CELLS, make_root

SEED = 2**33 + 5
ROOT_ONLY_CELL = "tiny-exact.dot"      # its kind's file is in the root only


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))


def run(root, name, **kw):
    cell = spec.load_cell(root, name)
    return run_cell(cell, SEED, 0.5, False, device_kind="TPU v5 lite", **kw)


@pytest.mark.parametrize("name", sorted(CELLS))
def test_sound_run_is_correct(root, name):
    r = run(root, name)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("name", sorted(CELLS))
def test_control_fails(root, name):
    cell = spec.load_cell(root, name)
    kind = drive.traffic_kind(cell)(cell, SEED)
    kind.setup(2.0, program=False)
    numbers = kind.control_numbers()
    assert any(numbers[k] > cell.limits[k] for k in cell.limits), numbers


def _alter_query(monkeypatch):
    from repro.serve import SketchIndex
    orig = SketchIndex.query

    def query(self, *a, **kw):
        out = orig(self, *a, **kw)
        name, est = out[0]
        return [(name, est * 1.01 + 1e-3)] + out[1:]
    monkeypatch.setattr(SketchIndex, "query", query)


def _alter_topk(monkeypatch):
    from repro.serve import SketchIndex
    orig = SketchIndex.top_k_for_query

    def top_k_for_query(self, *a, **kw):
        res = orig(self, *a, **kw)
        name, est = res.items[-1]
        res.items[-1] = (name, est * 0.99 - 1e-3)
        return res
    monkeypatch.setattr(SketchIndex, "top_k_for_query", top_k_for_query)


def _drop_answer(monkeypatch):
    from repro.serve import SketchIndex
    orig = SketchIndex.query

    def query(self, *a, **kw):
        return orig(self, *a, **kw)[1:]
    monkeypatch.setattr(SketchIndex, "query", query)


def _state_unchanged(monkeypatch):
    from repro.serve import SketchIndex
    monkeypatch.setattr(SketchIndex, "add_many", lambda self, *a, **kw: None)


def _half_left_out(monkeypatch):
    from repro.serve import SketchIndex
    orig = SketchIndex.add_many

    def add_many(self, names, matrix, **kw):
        half = len(names) // 2
        orig(self, names[:half], matrix[:half], **kw)
    monkeypatch.setattr(SketchIndex, "add_many", add_many)


def _altered_row(monkeypatch):
    from repro.serve import SketchIndex
    orig = SketchIndex.add_many

    def add_many(self, names, matrix, **kw):
        orig(self, names, matrix, **kw)
        d = len(self) - 1
        live = np.flatnonzero(self._val[d].ravel())
        self._val[d].ravel()[live[0]] *= np.float32(1.0 + 2**-20)
    monkeypatch.setattr(SketchIndex, "add_many", add_many)


def _altered_tau(monkeypatch):
    from repro.serve import SketchIndex
    orig = SketchIndex.add_many

    def add_many(self, names, matrix, **kw):
        orig(self, names, matrix, **kw)
        self._tau[len(self) - 1] *= np.float32(1.0 + 2**-10)
    monkeypatch.setattr(SketchIndex, "add_many", add_many)


@pytest.mark.parametrize("name,fault", [
    ("tiny-tpch.query", _alter_query),
    ("tiny-tpch.query", _drop_answer),
    ("tiny-tpch.topk", _alter_topk),
    ("tiny-tpch.ingest", _state_unchanged),
    ("tiny-tpch.ingest", _half_left_out),
    ("tiny-tpch.ingest", _altered_row),
    ("tiny-tpch.ingest", _altered_tau),
    (ROOT_ONLY_CELL, _alter_query),
])
def test_fault_is_not_correct(root, name, fault, monkeypatch):
    fault(monkeypatch)
    r = run(root, name)
    assert not r["correct"], r["checks"]
