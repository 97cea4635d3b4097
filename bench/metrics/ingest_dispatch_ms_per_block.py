"""Time of ``serve.index.add_many.dispatch`` per ``add_many`` call in the
traced window (ms): the build and bucketize dispatch.  Layer: engine / XLA
glue."""
from bench import spans


def read(rec):
    return spans.ms_per_call(rec, "serve.index.add_many",
                             "serve.index.add_many.dispatch")
