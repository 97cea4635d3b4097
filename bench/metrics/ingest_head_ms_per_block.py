"""Time of ``serve.index.add_many.head`` per ``add_many`` call in the traced
window (ms): the per-row head tracking, names and row summaries on the host.
Layer: service."""
from bench import spans


def read(rec):
    return spans.ms_per_call(rec, "serve.index.add_many",
                             "serve.index.add_many.head")
