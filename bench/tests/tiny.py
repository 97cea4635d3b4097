"""A tiny copy of the benchmark for CPU tests: the repository's benchmark
files beside tiny configurations, traffic mixes and cells, written as a
later PR would add a cell (files and entries only).

The tiny files are found by name under ``fixtures/``:

- ``configs/<config>.json``: a tiny configuration;
- ``traffic/<mix>.json``: a tiny traffic mix, with its ``kind``;
- ``cells/<cell>.json``: a tiny cell, naming its ``config`` and ``traffic``,
  the end-to-end metric it ``reports``, and either its own ``limits`` or
  ``limits_of``, the real cell whose limits file it borrows;
- ``root_only/``: files that only the tiny root holds, laid out as under
  ``bench/``: the kind ``exact_dot`` and its reference.

A new kind of traffic brings its tiny cell (and the mix naming the kind)
here as new files.
"""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
FIXTURES = os.path.join(BENCH, "tests", "fixtures")
ROOT_ONLY = os.path.join(FIXTURES, "root_only")


def _read(path: str):
    with open(path) as f:
        return json.load(f)


def _dump(path: str, obj) -> None:
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def _fixtures(sub: str) -> dict:
    d = os.path.join(FIXTURES, sub)
    return {f[:-5]: _read(os.path.join(d, f))
            for f in sorted(os.listdir(d)) if f.endswith(".json")}


CONFIGS = _fixtures("configs")
TRAFFIC = _fixtures("traffic")
CELLS = _fixtures("cells")


def make_root(dst: str) -> str:
    """A checkout-like directory holding the repository's BENCHMARK.json
    with the tiny configurations and cells added, and ``bench/`` with every
    file of the repository's ``bench/`` but its tests (so a kind's
    reference or generator added beside it is there too), the tiny files
    and ``root_only/``."""
    spec = _read(os.path.join(REPO, "BENCHMARK.json"))
    b = os.path.join(dst, "bench")
    shutil.copytree(BENCH, b,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copytree(ROOT_ONLY, b, dirs_exist_ok=True)
    for name, cfg in CONFIGS.items():
        _dump(os.path.join(b, "configs", name + ".json"), cfg)
        spec["configs"].append({"name": name, "source": "test fixture",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "CPU test"})
    for name, tr in TRAFFIC.items():
        _dump(os.path.join(b, "traffic", name + ".json"), tr)
    e2e = {m["name"] for m in spec["end_to_end"]}
    for name, c in CELLS.items():
        if c["reports"] not in e2e:
            raise ValueError(f"fixture cell {name!r} reports "
                             f"{c['reports']!r}, not one of {sorted(e2e)}")
        limits = c["limits"] if "limits" in c else _read(
            os.path.join(BENCH, "limits", c["limits_of"] + ".json"))
        _dump(os.path.join(b, "limits", name + ".json"), limits)
        spec["workloads"].append({"name": name, "config": c["config"],
                                  "traffic": c["traffic"], "chips": 1,
                                  "why": "CPU test"})
        for m in spec["end_to_end"] + spec["per_layer"]:
            moved = m.get("moves", m["name"])
            if "workloads" in m and moved == c["reports"]:
                m["workloads"].append(name)
    _dump(os.path.join(dst, "BENCHMARK.json"), spec)
    return dst
