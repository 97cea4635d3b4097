"""Find everything a cell needs by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Its files, all under the benchmark's directory (the first of ``paths``):

- ``configs``' ``file``: the deployment (its sizes and what its kind reads);
- ``traffic/<traffic>.json``: the traffic mix, with its ``kind``;
- ``kinds/<kind>.py``: that kind of traffic, with the surface it drives
  and the reference it is checked against (contract in ``bench.kinds``);
- ``limits/<workload>.json``: the limit of each number ``correct`` compares;
- ``metrics/<metric>.py``: one reader per per-layer metric;
- ``peaks.json``: chip peaks by ``device_kind``.

Adding a configuration, a traffic mix, a kind of traffic or a metric is
adding files and entries; nothing here names one.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys


class SpecError(ValueError):
    """A cell, or one of its files, is missing or malformed."""


@dataclasses.dataclass
class Cell:
    root: str
    bench_dir: str
    workload: dict
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def name(self) -> str:
        return self.workload["name"]

    @property
    def chips(self) -> int:
        return int(self.workload["chips"])


def _read_json(path: str, what: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError) as e:
        raise SpecError(f"{what}: cannot read {path}: {e}") from e


def _applies(metric: dict, workload: str, reported: set) -> bool:
    """A per-layer metric with ``workloads`` is read in those cells; one
    without is read wherever the metric it moves is reported."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric["moves"] in reported


def load_cell(root: str, workload: str) -> Cell:
    spec = _read_json(os.path.join(root, "BENCHMARK.json"), "benchmark")
    bench_dir = os.path.join(root, spec["paths"][0])
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SpecError(f"no workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    if w["config"] not in configs:
        raise SpecError(f"workload {workload!r} names unknown config "
                        f"{w['config']!r}")
    config = _read_json(os.path.join(root, configs[w["config"]]["file"]),
                        "config")
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      w["traffic"] + ".json"), "traffic")
    kind = traffic.get("kind")
    if not (isinstance(kind, str) and kind.isidentifier()
            and os.path.isfile(kind_path(bench_dir, kind))):
        raise SpecError(f"traffic {w['traffic']!r} names kind {kind!r}, "
                        f"which has no file {kind_path(bench_dir, str(kind))}"
                        f"; kinds found: {kinds(bench_dir)}")
    limits = _read_json(os.path.join(bench_dir, "limits", workload + ".json"),
                        "limits")
    e2e = [m for m in spec["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if _applies(m, workload, reported)]
    for m in per_layer:
        if not os.path.isfile(metric_path(bench_dir, m["name"])):
            raise SpecError(f"metric {m['name']!r} has no reader "
                            f"{metric_path(bench_dir, m['name'])}")
    return Cell(root, bench_dir, w, config, traffic, limits, e2e, per_layer)


def metric_path(bench_dir: str, name: str) -> str:
    return os.path.join(bench_dir, "metrics", name + ".py")


def kind_path(bench_dir: str, kind: str) -> str:
    return os.path.join(bench_dir, "kinds", kind + ".py")


def kinds(bench_dir: str) -> list:
    """The kinds of traffic that have a file under ``bench_dir``."""
    try:
        names = os.listdir(os.path.join(bench_dir, "kinds"))
    except OSError:
        return []
    return sorted(f[:-3] for f in names
                  if f.endswith(".py") and f != "__init__.py")


def load_file(bench_dir: str, rel: str):
    """The module in ``<bench_dir>/<rel>``, loaded from that file, so that
    a checkout or a test's root can hold one the package does not."""
    mod_name = "bench_file_" + "".join(
        c if c.isalnum() else "_" for c in rel[:-3])
    sp = importlib.util.spec_from_file_location(
        mod_name, os.path.join(bench_dir, rel))
    mod = importlib.util.module_from_spec(sp)
    sys.modules[mod_name] = mod
    sp.loader.exec_module(mod)
    return mod


def load_reader(bench_dir: str, name: str):
    """The ``read(records)`` function of a per-layer metric's reader."""
    return load_file(bench_dir, f"metrics/{name}.py").read


def peaks(bench_dir: str, device_kind: str) -> dict:
    """Chip peaks for ``device_kind``; an unknown kind is an error."""
    table = _read_json(os.path.join(bench_dir, "peaks.json"), "peaks")
    kinds = table["kinds"]
    if device_kind not in kinds:
        raise SpecError(f"no peaks for device kind {device_kind!r}; "
                        f"have {sorted(kinds)}")
    return kinds[device_kind]
