"""The readers of the program's step spans on a synthetic trace."""
import pytest

from bench import spec, trace as T
from bench.harness import Records
from bench.tests.tiny import BENCH

MS = 1e6
QUERY, ADD = "serve.index.query", "serve.index.add_many"
READERS = {
    "query_validate_ms": (QUERY, "validate"),
    "query_upload_ms": (QUERY, "upload"),
    "query_dispatch_ms": (QUERY, "dispatch"),
    "query_fetch_ms": (QUERY, "fetch"),
    "ingest_validate_ms_per_block": (ADD, "validate"),
    "ingest_upload_ms_per_block": (ADD, "upload"),
    "ingest_dispatch_ms_per_block": (ADD, "dispatch"),
    "ingest_fetch_ms_per_block": (ADD, "fetch"),
    "ingest_head_ms_per_block": (ADD, "head"),
}


def _read(name, host):
    rec = Records(T.Trace((0.0, 100 * MS), {}, [T.Ev(T.WINDOW, 0, 100 * MS)]
                          + host), [], {}, {}, {}, {})
    return spec.load_reader(BENCH, name)(rec)


def _calls(parent, step):
    # two calls in the window (0-100 ms); a call in set-up before it and one
    # after it, whose steps must not count; a sibling step in each call
    other = parent + (".rank" if step != "rank" else ".upload")
    return [T.Ev(parent, -40 * MS, 30 * MS), T.Ev(step, -35 * MS, 20 * MS),
            T.Ev(parent, 10 * MS, 30 * MS), T.Ev(step, 12 * MS, 4 * MS),
            T.Ev(other, 20 * MS, 15 * MS),
            T.Ev(parent, 50 * MS, 30 * MS), T.Ev(step, 55 * MS, 8 * MS),
            T.Ev(parent, 95 * MS, 20 * MS), T.Ev(step, 96 * MS, 2 * MS)]


@pytest.mark.parametrize("name", sorted(READERS))
def test_step_time_per_call(name):
    parent, step = READERS[name]
    assert _read(name, _calls(parent, f"{parent}.{step}")) == \
        pytest.approx(6.0)                       # (4 + 8) ms over 2 calls


@pytest.mark.parametrize("name", sorted(READERS))
def test_step_without_span_reads_zero(name):
    parent, _ = READERS[name]
    host = [T.Ev(parent, 10 * MS, 30 * MS), T.Ev(parent, 50 * MS, 30 * MS)]
    assert _read(name, host) == 0.0


@pytest.mark.parametrize("name", sorted(READERS))
def test_no_parent_span_reads_none(name):
    parent, step = READERS[name]
    # a program without the spans: the harness's own events only; a
    # stray step span outside any call does not make a reading
    host = [T.Ev("bench.serve", 10 * MS, 30 * MS),
            T.Ev(f"{parent}.{step}", 12 * MS, 4 * MS)]
    assert _read(name, host) is None
    untraced = Records(None, [], {}, {}, {}, {})
    assert spec.load_reader(BENCH, name)(untraced) is None
