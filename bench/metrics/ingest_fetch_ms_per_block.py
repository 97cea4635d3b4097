"""Time of ``serve.index.add_many.fetch`` per ``add_many`` call in the
traced window (ms): the wait for the bucketized rows and their copy into the
host master copy.  Layer: device."""
from bench import spans


def read(rec):
    return spans.ms_per_call(rec, "serve.index.add_many",
                             "serve.index.add_many.fetch")
