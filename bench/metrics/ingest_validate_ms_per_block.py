"""Time of ``serve.index.add_many.validate`` per ``add_many`` call in the
traced window (ms): the block's checks (shape, names, ``check_finite``).
Layer: service."""
from bench import spans


def read(rec):
    return spans.ms_per_call(rec, "serve.index.add_many",
                             "serve.index.add_many.validate")
