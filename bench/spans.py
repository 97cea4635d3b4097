"""Time per call of one step of the program, from the program's own spans.

The program opens a span for each step of ``SketchIndex.query`` and
``SketchIndex.add_many`` (``repro.obs``); under the profiler each span is a
host event of the same name, on the harness's thread and the profiler's
clock.  A step's time per call is the summed duration of the step's spans
that lie inside a call of the parent span in the measured window, divided
by the number of those calls.
"""
from __future__ import annotations

import bisect

from bench import trace as T


def ms_per_call(rec, parent: str, step: str):
    """Milliseconds of ``step`` per ``parent`` call in the traced window;
    None when the run is untraced or no ``parent`` call lies in the
    window (a program without these spans), 0 when calls have no
    ``step``."""
    if rec.trace is None:
        return None
    t0, t1 = rec.trace.window
    calls = sorted((e for e in T.spans(rec.trace.host, parent)
                    if e.start >= t0 and e.end <= t1), key=lambda e: e.start)
    if not calls:
        return None
    starts = [c.start for c in calls]
    total = 0.0
    for e in T.spans(rec.trace.host, step):
        i = bisect.bisect_right(starts, e.start) - 1
        if i >= 0 and e.end <= calls[i].end:
            total += e.dur
    return total / len(calls) * 1e-6
