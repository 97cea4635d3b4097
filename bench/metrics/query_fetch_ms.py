"""Time of ``serve.index.query.fetch`` per request in the traced window
(ms): the wait for the estimates and their copy to the host.  Layer:
device."""
from bench import spans


def read(rec):
    return spans.ms_per_call(rec, "serve.index.query",
                             "serve.index.query.fetch")
