"""BENCHMARK.json: every cell resolves to its files, the file meets the
benchmark's schema, and a cell is added by adding files and entries."""
import json
import os
import re
import subprocess
import sys

import pytest

from bench import spec
from bench.tests.tiny import BENCH, CELLS, REPO, make_root

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def _spec():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_schema():
    s = _spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert s["paths"] == ["bench"] and s["command"][1] == "bench/run.py"
    assert 1 <= s["run_seconds"] <= 51
    metrics = s["end_to_end"] + s["per_layer"]
    names = [m["name"] for m in metrics]
    assert len(set(names)) == len(names)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in s["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert {m["name"] for m in s["end_to_end"]} == {
        "query_p95_ms", "ingest_cols_per_s", "setup_s"}
    e2e = {m["name"] for m in s["end_to_end"]}
    for m in s["per_layer"]:
        assert m["moves"] in e2e and m["layer"] and m["workloads"]
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    pairs = [(w["config"], w["traffic"]) for w in s["workloads"]]
    assert len(set(pairs)) == len(pairs)
    used = {w["config"] for w in s["workloads"]}
    assert used == {c["name"] for c in s["configs"]}
    for w in s["workloads"]:
        assert w["chips"] in (1, 4) and NAME.match(w["name"])
        assert 1 <= len(w["why"]) <= 200


@pytest.mark.parametrize("workload", [w["name"] for w in _spec()["workloads"]])
def test_every_workload_resolves(workload):
    cell = spec.load_cell(REPO, workload)
    assert cell.config["name"] == cell.workload["config"]
    for key in ("source", "reduced", "assumed", "index", "data", "precision"):
        assert key in cell.config, key
    entry = next(c for c in _spec()["configs"]
                 if c["name"] == cell.config["name"])
    assert entry["reduced"] == cell.config["reduced"]
    assert entry["source"] == cell.config["source"]
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "every cell reports a per-layer metric"
    for m in cell.per_layer:
        assert callable(spec.load_reader(cell.bench_dir, m["name"]))


def test_a_cell_is_added_with_files_only(tmp_path):
    root = make_root(str(tmp_path))
    for name in CELLS:
        cell = spec.load_cell(root, name)
        assert cell.per_layer and cell.limits


def test_every_file_belongs_to_a_cell():
    s = _spec()
    cells = {w["name"] for w in s["workloads"]}
    traffic = {w["traffic"] for w in s["workloads"]}
    limits = {f[:-5] for f in os.listdir(os.path.join(BENCH, "limits"))}
    mixes = {f[:-5] for f in os.listdir(os.path.join(BENCH, "traffic"))}
    readers = {f[:-3] for f in os.listdir(os.path.join(BENCH, "metrics"))
               if f.endswith(".py")}
    named = set()
    for t in traffic:
        with open(os.path.join(BENCH, "traffic", t + ".json")) as f:
            named.add(json.load(f)["kind"])
    assert limits == cells and mixes == traffic
    assert readers == {m["name"] for m in s["per_layer"]}
    assert set(spec.kinds(BENCH)) == named


def test_tpch_sizes_follow_the_source():
    cfg = spec.load_cell(REPO, "tpch-z2-m512.ingest").config
    data = cfg["data"]
    assert cfg["columns"] == 2526 and cfg["reduced"] == []
    # SF5 lineitem: 30,006,075 rows over 2526 shipdate days
    assert abs(data["rows_per_day"] * cfg["columns"] - 30_006_075) < 2526
    assert data["parts"] == 5 * 200_000 <= data["universe"]
    assert data["universe"] & (data["universe"] - 1) == 0


def test_unknown_workload_and_device_kind():
    with pytest.raises(spec.SpecError):
        spec.load_cell(REPO, "no-such.cell")
    with pytest.raises(spec.SpecError):
        spec.peaks(BENCH, "TPU v99")
    assert spec.peaks(BENCH, "TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "tpch-z2-m512.query",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_result():
    p = _run(REPO)
    assert p.returncode != 0 and p.stdout.strip() == ""


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0 and p.stdout.strip() == ""
