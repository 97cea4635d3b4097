"""Time of ``serve.index.query.validate`` per request in the traced window
(ms): the query vector's checks (``check_vector``).  Layer: service."""
from bench import spans


def read(rec):
    return spans.ms_per_call(rec, "serve.index.query",
                             "serve.index.query.validate")
