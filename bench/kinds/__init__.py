"""Kinds of traffic, one file each, found by name.

A traffic file's ``kind`` names ``kinds/<kind>.py`` under the cell's
benchmark directory; ``bench.drive.traffic_kind`` loads it from there (not
from this package), so a checkout or a test's root can hold a kind that the
package does not.  Adding a kind is adding its file, and the files it
reads, beside the cell's configuration, traffic mix and limits.

A kind's file defines ``Kind``, a class with this contract:

- ``Kind(cell, seed)``: the cell (``bench.spec.Cell``: its configuration,
  traffic mix and limits) and the run's seed, from which every input is
  made.
- ``setup(seconds, *, program=True)``: make the inputs and the schedule of
  a ``seconds``-long window from the seed, build the surface the kind
  drives, and warm up every shape the window uses.  With
  ``program=False`` only the inputs and the schedule are made: the control
  runs without the program.
- ``window(seconds, ann) -> bench.drive.Window``: drive the measured window
  on the harness's clock (``bench.drive.clock``), each step inside
  ``ann(<span name>)``, and return the cell's end-to-end numbers, its
  per-request records and the facts its metric readers need.
- ``check() -> dict``: after the window, the numbers ``correct`` compares,
  each against the kind's own reference; the cell's limits file holds a
  limit for each.
- ``control_numbers() -> dict``: the same numbers with the control (the
  reference in the precision below the configuration's) put in the
  program's place; each cell's limits have to fail it.

A kind that ``bench/sweep.py`` can sweep also offers ``serve_one(n)``,
which serves the ``n``-th request of its pool closed loop and returns when
it is answered, and ``schedule(rate, seconds)``, which sets the offered
load of the next window; that window's ``requests`` carry each request's
``due`` and ``end``.

A kind brings its reference as a file of its own, written from the
published semantics and importing nothing of the program (``reference.py``
is the inner-product reference of the two dense kinds);
``bench.spec.load_file`` loads such a file from the cell's benchmark
directory.
``bench.drive`` holds what the kinds share: the clock, the ``Window``, the
relative gap, and for the dense kinds the ``SketchIndex`` they build and
the rows it stores.  The data generators' ``corpus_block`` /
``query_pool`` contract (``bench.data``) binds only the kinds that call
it.
"""
