"""One run of one cell: set-up, the measured window, the comparison with
the reference, and the result line.

``run_cell`` does everything but look for the chip, so that tests can
drive a whole run on the CPU at a small size.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import os
import shutil
import sys
import tempfile

from bench import drive, spec, trace as tr


def process_age() -> float:
    """Seconds since this process started (from /proc; set-up starts when
    the process does, imports included)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        uptime = float(f.read().split()[0])
    return uptime - start_ticks / os.sysconf("SC_CLK_TCK")


@dataclasses.dataclass
class Records:
    """What a per-layer metric's reader reads: the traced window (``trace``,
    None in an untraced run), the harness's per-request records
    (``requests``: due/start/end seconds on the harness clock), sizes
    (``facts``), the configuration and traffic, and the chip's ``peaks``."""

    trace: object
    requests: list
    facts: dict
    config: dict
    traffic: dict
    peaks: dict


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             device_kind: str | None = None, log=sys.stderr) -> dict:
    """Run ``cell`` once and return its result line (a dict)."""
    import jax
    kind = drive.traffic_kind(cell)(cell, seed)
    kind.setup(seconds)
    gc.collect()
    gc.freeze()
    setup_s = process_age()
    tdir = None
    ann = lambda name: contextlib.nullcontext()
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
        ann = jax.profiler.TraceAnnotation
    try:
        win = kind.window(seconds, ann)
    finally:
        if trace:
            jax.profiler.stop_trace()
    devices = jax.devices()
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    gc.unfreeze()
    numbers = kind.check()
    checks = {}
    for name, value in numbers.items():
        if name not in cell.limits:
            raise spec.SpecError(f"no limit for {name!r} in {cell.name}")
        checks[name] = {"value": value, "limit": cell.limits[name]}
    correct = win.failed == 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    result = {"correct": correct, "attempted": win.attempted,
              "failed": win.failed}
    if not trace:
        metrics = {m["name"]: {"value": win.e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in win.e2e}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
        result["metrics"] = metrics
    else:
        try:
            t = tr.load(tr.find_xplane(tdir))
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        rec = Records(t, win.requests, win.facts, cell.config, cell.traffic,
                      spec.peaks(cell.bench_dir,
                                 device_kind or devices[0].device_kind))
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(cell.bench_dir, m["name"])(rec)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = tr.busy_s(t)
        device["window_s"] = t.window_s
        result["breakdown"] = tr.breakdown(t)
    result["device"] = device
    result["window"] = {"seconds": win.seconds,
                        **{k: v for k, v in win.facts.items()
                           if k in ("late_start_p99_ms", "errors")}}
    result["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=log)
    return result
