"""Time of ``serve.index.query.upload`` per request in the traced window
(ms): the query vector's upload to the device.  Layer: service."""
from bench import spans


def read(rec):
    return spans.ms_per_call(rec, "serve.index.query",
                             "serve.index.query.upload")
