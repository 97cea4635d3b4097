"""On-chip benchmark of the sketch service (see ``BENCHMARK.json``)."""
