"""Time of ``serve.index.add_many.upload`` per ``add_many`` call in the
traced window (ms): the dense block's upload to the device.  Layer:
service."""
from bench import spans


def read(rec):
    return spans.ms_per_call(rec, "serve.index.add_many",
                             "serve.index.add_many.upload")
