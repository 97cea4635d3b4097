"""Kinds of traffic are found by name: a cell's kind is the file its
traffic names under the cell's benchmark directory, every kind has a CPU
fixture cell (``bench/tests/fixtures/``) and keeps the contract of
``bench.kinds`` there, and a kind with no file is refused."""
import json
import os
import subprocess
import sys

import pytest

from bench import drive, spec
from bench.tests.tiny import BENCH, CELLS, REPO, make_root

CONTRACT = ("setup", "window", "check", "control_numbers")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("bench")))


def cell_of_kind(root, kind):
    """The first fixture cell whose traffic is of ``kind``; none fails the
    test, naming the files that would give the kind one."""
    for name in CELLS:
        cell = spec.load_cell(root, name)
        if cell.traffic["kind"] == kind:
            return cell
    pytest.fail(f"kind {kind!r} has no CPU fixture: add "
                f"bench/tests/fixtures/cells/<cell>.json naming a mix "
                f"bench/tests/fixtures/traffic/<mix>.json of kind {kind!r} "
                f"(and its configuration under bench/tests/fixtures/configs/)")


@pytest.mark.parametrize("kind", spec.kinds(BENCH))
def test_every_kind_keeps_the_contract(root, kind):
    cell = cell_of_kind(root, kind)
    cls = drive.traffic_kind(cell)
    assert all(callable(getattr(cls, m, None)) for m in CONTRACT)
    obj = cls(cell, 2**40 + 3)
    obj.setup(1.0, program=False)       # the control's inputs, no program
    assert set(obj.control_numbers()) == set(cell.limits)


def test_a_kind_with_no_fixture_names_the_file_to_add(root):
    with pytest.raises(pytest.fail.Exception,
                       match="bench/tests/fixtures/cells/<cell>.json"):
        cell_of_kind(root, "no_fixture_kind")


def test_a_kind_in_the_root_only_is_found(root):
    assert not os.path.exists(spec.kind_path(BENCH, "exact_dot"))
    cell = spec.load_cell(root, "tiny-exact.dot")
    cls = drive.traffic_kind(cell)
    assert cls.__name__ == "ExactDot" and "exact_dot" in spec.kinds(
        cell.bench_dir)
    assert all(callable(getattr(cls, m, None)) for m in CONTRACT)


@pytest.mark.parametrize("kind", ["no_such_kind", "../drive", None])
def test_a_kind_with_no_file_is_refused(root, kind):
    path = os.path.join(root, "bench", "traffic", "tiny_exact.json")
    with open(path) as f:
        saved = f.read()
    traffic = json.loads(saved)
    if kind is None:
        del traffic["kind"]
    else:
        traffic["kind"] = kind
    try:
        with open(path, "w") as f:
            json.dump(traffic, f)
        with pytest.raises(spec.SpecError, match="open_query"):
            spec.load_cell(root, "tiny-exact.dot")
    finally:
        with open(path, "w") as f:
            f.write(saved)


def test_sweep_refuses_a_kind_with_no_closed_loop():
    p = subprocess.run(
        [sys.executable, "bench/sweep.py", "--workload",
         "tpch-z2-m512.ingest", "--seed", "1"],
        cwd=REPO, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "bulk_ingest" in p.stderr and "serve_one" in p.stderr
