"""Host time per ``add_many`` call (ms): the call's span on the profiler
clock minus the time in it during which an op ran on the device.  Layer:
service."""
from bench import trace as T


def read(rec):
    if rec.trace is None or not rec.trace.devices:
        return None
    calls = T.spans(rec.trace.host, "bench.add_many")
    if not calls:
        return None
    ops = rec.trace.ops()
    host = [c.dur - T.union_ns(T.clip(ops, (c.start, c.end))) for c in calls]
    return sum(host) / len(host) * 1e-6
