"""Share of the traced window in which no op ran on the device (%), in the
ingest cells.  Layer: device."""
from bench import trace as T


def read(rec):
    if rec.trace is None or not rec.trace.devices:
        return None
    return 100.0 * (1.0 - T.busy_s(rec.trace) / rec.trace.window_s)
