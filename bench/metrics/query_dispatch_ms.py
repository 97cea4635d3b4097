"""Time of ``serve.index.query.dispatch`` per request in the traced window
(ms): the query sketch, its bucketize and the all-pairs launch, dispatched
op by op.  Layer: engine / XLA glue."""
from bench import spans


def read(rec):
    return spans.ms_per_call(rec, "serve.index.query",
                             "serve.index.query.dispatch")
