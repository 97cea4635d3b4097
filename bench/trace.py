"""Reduction of a profiler trace to device busy time, idle gaps and kernel
time.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into plain
``Ev`` records: the device's op events (the ``XLA Ops`` line of each
``/device:`` plane, so modules and steps are not counted twice) and the
host's events on the thread that ran the harness.  Everything after that
works on ``Ev`` lists, so the arithmetic is tested on synthetic traces.
Times are nanoseconds on the profiler's clock, which puts host and device
events on one axis.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

WINDOW = "bench.window"      # harness annotation around the measured window


@dataclasses.dataclass(frozen=True)
class Ev:
    name: str
    start: float   # ns
    dur: float     # ns
    text: str = ""   # name plus the event's string stats, for matching

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    window: tuple             # (start, end) ns of the measured window
    devices: dict             # device plane name -> [Ev] op events
    host: list                # [Ev] events of the harness's thread

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def ops(self) -> list:
        """Op events of every device, clipped to the window."""
        return [e for evs in self.devices.values() for e in clip(evs,
                                                                 self.window)]


def clip(evs, window) -> list:
    t0, t1 = window
    out = []
    for e in evs:
        s, f = max(e.start, t0), min(e.end, t1)
        if f > s:
            out.append(Ev(e.name, s, f - s, e.text))
    return out


def union_ns(evs) -> float:
    """Length of the union of the events' intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for e in sorted(evs, key=lambda e: e.start):
        if cur_e is None or e.start > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = e.start, e.end
        else:
            cur_e = max(cur_e, e.end)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_s(trace: Trace) -> float:
    """Seconds in which an op ran, averaged over the devices that ran one."""
    busy = [union_ns(clip(evs, trace.window))
            for evs in trace.devices.values()]
    busy = [b for b in busy if b > 0]
    return sum(busy) / len(busy) * 1e-9 if busy else 0.0


def idle_gaps(evs, window) -> list:
    """(start, end) of every stretch of the window with no op running."""
    gaps, t = [], window[0]
    for e in sorted(clip(evs, window), key=lambda e: e.start):
        if e.start > t:
            gaps.append((t, e.start))
        t = max(t, e.end)
    if window[1] > t:
        gaps.append((t, window[1]))
    return gaps


def _label(open_evs) -> str:
    bench = [e for e in open_evs if e.name.startswith("bench.")]
    other = [e for e in open_evs if not e.name.startswith("bench.")]
    inner = lambda evs: min(evs, key=lambda e: e.dur).name if evs else ""
    parts = [p for p in (inner(bench), inner(other)) if p]
    return " > ".join(parts) if parts else "(no host event)"


def labels_at(times, host) -> list:
    """What the harness's thread was doing at each of ``times``: its
    innermost ``bench.`` annotation and, below it, its innermost other
    event.  One sweep over the host events."""
    evs = sorted((e for e in host if e.name != WINDOW),
                 key=lambda e: e.start)
    out = [None] * len(times)
    live, i = [], 0
    for k in sorted(range(len(times)), key=lambda k: times[k]):
        t = times[k]
        while i < len(evs) and evs[i].start <= t:
            live.append(evs[i])
            i += 1
        live = [e for e in live if e.end > t]
        out[k] = _label(live)
    return out


def top(pairs, n: int = 10) -> list:
    """The ``n`` largest (name, seconds) totals of (name, seconds) pairs."""
    acc = collections.Counter()
    for name, s in pairs:
        acc[name] += s
    return [[k, v] for k, v in acc.most_common(n)]


def breakdown(trace: Trace) -> dict:
    """Device ops that took most time, and idle time by what the host was
    doing, each as at most 10 [name, seconds]."""
    dev = next(iter(trace.devices.values()), [])
    ops = clip(dev, trace.window)
    gaps = idle_gaps(dev, trace.window)
    names = labels_at([(s + f) / 2 for s, f in gaps], trace.host)
    return {
        "device_ops": top((e.name[:100], e.dur * 1e-9) for e in ops),
        "idle_gaps": top((n, (f - s) * 1e-9)
                         for n, (s, f) in zip(names, gaps)),
    }


def matching(evs, pattern: str) -> list:
    """Events whose name or string stats match the regular expression."""
    rx = re.compile(pattern)
    return [e for e in evs if rx.search(e.text or e.name)]


def spans(host, name: str) -> list:
    return [e for e in host if e.name == name]


# ---------------------------------------------------------------------------
# .xplane.pb -> Ev lists
# ---------------------------------------------------------------------------


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, main = {}, None
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            evs = [Ev(e.name, float(e.start_ns), float(e.duration_ns), e.name)
                   for ln in plane.lines if ln.name == "XLA Ops"
                   for e in ln.events]
            if evs:
                devices[plane.name] = evs
        elif plane.name.startswith("/host:") and main is None:
            for ln in plane.lines:
                evs = list(ln.events)
                if any(e.name == WINDOW for e in evs):
                    main = [Ev(e.name, float(e.start_ns),
                               float(e.duration_ns)) for e in evs]
                    break
    win = spans(main or [], WINDOW)
    if not win:
        raise ValueError(f"no {WINDOW!r} annotation in {path}")
    return Trace((win[0].start, win[0].end), devices, main)
