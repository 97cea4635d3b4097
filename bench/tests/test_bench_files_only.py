"""A new kind of traffic joins the benchmark, and passes the benchmark's
own CPU tests, as new files and entries only.

A copy of ``BENCHMARK.json`` and ``bench/`` gains a kind (the ``exact_dot``
fixture under a new name, with its reference), a configuration, a traffic
mix, limits, a cell (entries in ``BENCHMARK.json``) and the cell's CPU
fixture.  No file that was in the copy changes, ``BENCHMARK.json`` only
gains entries, and the kinds, spec and correctness tests pass on the copy
with the new kind's cases among them.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import xml.etree.ElementTree as ET

from bench.tests.tiny import BENCH, REPO, ROOT_ONLY

KIND = "probe_dot"
CONFIG = "probe-exact"
CELL = "probe-exact.dot"
FIXTURE = "tiny-exact.probe"
PER_LAYER = "query_fetch_ms"           # one per-layer metric the cell reports
SUITES = ("test_bench_kinds.py", "test_bench_spec.py",
          "test_bench_correct.py")
# each starts a process of its own on the repository's files
SPAWNING = ("test_sweep_refuses_a_kind_with_no_closed_loop",
            "test_no_tpu_exits_nonzero_without_result",
            "test_benchmark_files_alone_exit_nonzero")
TIME_LIMIT_S = 240


def _write(path, text):
    assert not os.path.exists(path), f"{path} is not new"
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        f.write(text)


def _write_json(path, obj):
    _write(path, json.dumps(obj, indent=2) + "\n")


def add_probe_kind(root):
    """Add the probe kind and its cell under ``root`` (a checkout holding
    ``BENCHMARK.json`` and ``bench/``) as new files and entries."""
    b = os.path.join(root, "bench")
    reference = f"{KIND}_reference.py"
    with open(os.path.join(ROOT_ONLY, "kinds", "exact_dot.py")) as f:
        kind = f.read().replace("exact_dot_reference.py", reference)
    _write(os.path.join(b, "kinds", KIND + ".py"), kind)
    with open(os.path.join(ROOT_ONLY, "exact_dot_reference.py")) as f:
        _write(os.path.join(b, reference), f.read())

    source = "test probe: sparse vectors every sketch keeps whole"
    _write_json(os.path.join(b, "configs", CONFIG + ".json"), {
        "name": CONFIG, "source": source, "reduced": [],
        "assumed": {"nonzeros": "4 a column, below m: estimates are exact"},
        "precision": "float32", "columns": 12, "universe": 64,
        "nonzeros": 4, "data": {"values": "uniform 0.5 to 2"},
        "index": {"m": 8, "n_buckets": 16, "slots": 4, "head_h": 2,
                  "seed": 11}})
    _write_json(os.path.join(b, "traffic", KIND + ".json"),
                {"kind": KIND, "pool": 4})
    _write_json(os.path.join(b, "limits", CELL + ".json"), {"dot_gap": 1e-5})
    fx = os.path.join(b, "tests", "fixtures")
    _write_json(os.path.join(fx, "traffic", "tiny_probe.json"),
                {"kind": KIND, "pool": 4})
    _write_json(os.path.join(fx, "cells", FIXTURE + ".json"),
                {"config": "tiny-exact", "traffic": "tiny_probe",
                 "reports": "query_p95_ms", "limits_of": CELL})

    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        spec = json.load(f)
    spec["configs"].append({"name": CONFIG, "source": source,
                            "file": f"bench/configs/{CONFIG}.json",
                            "reduced": [], "why": "CPU probe"})
    spec["workloads"].append({"name": CELL, "config": CONFIG,
                              "traffic": KIND, "chips": 1,
                              "why": "CPU probe"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] in ("query_p95_ms", PER_LAYER):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(spec, f, indent=2)


def _without_probe(spec):
    """``spec`` with the probe's entries taken out again."""
    spec = json.loads(json.dumps(spec))
    spec["configs"] = [c for c in spec["configs"] if c["name"] != CONFIG]
    spec["workloads"] = [w for w in spec["workloads"] if w["name"] != CELL]
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w != CELL]
    return spec


def _hashes(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_kind_joins_as_files_only(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec_before = json.load(f)
    before = _hashes(root)

    add_probe_kind(root)
    after = _hashes(root)
    changed = [p for p in before
               if p != "BENCHMARK.json" and after.get(p) != before[p]]
    assert not changed, changed
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec_after = json.load(f)
    assert spec_after != spec_before
    assert _without_probe(spec_after) == spec_before

    report = str(tmp_path / "report.xml")
    env = {k: v for k, v in os.environ.items()
           if not k.startswith("PYTEST_")}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=os.pathsep.join(
        [root, os.path.join(REPO, "src")]))
    p = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
         "-p", "no:randomly", f"--junitxml={report}",
         "-k", " and ".join(f"not {t}" for t in SPAWNING),
         *(os.path.join("bench", "tests", s) for s in SUITES)],
        cwd=root, env=env, capture_output=True, text=True,
        timeout=TIME_LIMIT_S)
    assert p.returncode == 0, p.stdout[-4000:] + p.stderr[-2000:]

    outcome = {}
    for case in ET.parse(report).iter("testcase"):
        bad = [c.tag for c in case if c.tag in ("failure", "error", "skipped")]
        outcome[case.get("name")] = bad[0] if bad else "passed"
    for case in (f"test_every_kind_keeps_the_contract[{KIND}]",
                 f"test_every_workload_resolves[{CELL}]",
                 f"test_sound_run_is_correct[{FIXTURE}]",
                 f"test_control_fails[{FIXTURE}]"):
        assert outcome.get(case) == "passed", (case, outcome)
