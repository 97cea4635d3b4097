"""The trace reduction and the metric readers on a small synthetic trace."""
import math

import pytest

from bench import costs, spec, trace as T
from bench.harness import Records
from bench.tests.tiny import BENCH

MS = 1e6
KERNEL = ('%_query_corpus_jit.1 = f32[1,8192] custom-call(s32[4,1,512] %a), '
          'custom_call_target="tpu_custom_call"')
BUILD = ('%_build_priority_payload.{} = s32[8,1,256] custom-call(f32[8,8192,'
         '128] %b), custom_call_target="tpu_custom_call"')


def _trace():
    # window 0..100 ms; device ops: glue 10-20, kernel 20-30, overlapping
    # glue 25-35, kernel 60-70; host: two serve spans and a wait
    ops = [T.Ev("fusion.1", 10 * MS, 10 * MS, "fusion.1"),
           T.Ev("custom-call.2", 20 * MS, 10 * MS, KERNEL),
           T.Ev("copy.3", 25 * MS, 10 * MS, "copy.3"),
           T.Ev("custom-call.2", 60 * MS, 10 * MS, KERNEL),
           T.Ev("late", 120 * MS, 5 * MS, "late")]
    host = [T.Ev(T.WINDOW, 0, 100 * MS),
            T.Ev("bench.serve", 5 * MS, 35 * MS),
            T.Ev("PjitFunction(query)", 36 * MS, 3 * MS),
            T.Ev("bench.wait", 40 * MS, 15 * MS),
            T.Ev("bench.serve", 55 * MS, 20 * MS)]
    return T.Trace((0.0, 100 * MS), {"/device:TPU:0": ops}, host)


def test_busy_idle_and_gaps():
    t = _trace()
    assert math.isclose(T.busy_s(t), 0.035)      # 10-35 and 60-70
    gaps = T.idle_gaps(t.devices["/device:TPU:0"], t.window)
    assert gaps == [(0.0, 10 * MS), (35 * MS, 60 * MS), (70 * MS, 100 * MS)]
    assert T.labels_at([90 * MS, 37 * MS, 50 * MS], t.host) == [
        "(no host event)", "bench.serve > PjitFunction(query)", "bench.wait"]


def test_breakdown_and_kernel_time():
    t = _trace()
    b = T.breakdown(t)
    assert b["device_ops"][0] == ["custom-call.2", pytest.approx(0.02)]
    assert len(b["device_ops"]) == 3             # "late" is outside
    labels = dict(b["idle_gaps"])
    assert labels["(no host event)"] == pytest.approx(0.030)
    k = T.matching(t.ops(), costs.KERNELS["allpairs"])
    assert sum(e.dur for e in k) == 20 * MS


def test_readers():
    t = _trace()
    reqs = [{"due": 0.0, "start": 0.005, "end": 0.040},
            {"due": 0.05, "start": 0.055, "end": 0.075}]
    rec = Records(t, reqs, {"corpus_rows": 8192, "m": 256}, {}, {},
                  spec.peaks(BENCH, "TPU v5 lite"))
    read = lambda name: spec.load_reader(BENCH, name)(rec)
    assert read("device_idle_share.query") == pytest.approx(65.0)
    assert read("query_service_ms") == pytest.approx(27.5)
    assert read("glue_device_ms_per_query") == pytest.approx(10.0)
    want = 100 * 2 * costs.allpairs_corpus_bytes(8192, 256) / 819e9 / 0.02
    assert read("allpairs_roofline") == pytest.approx(want)
    assert read("build_roofline") is None        # no build kernel here
    empty = Records(None, [], {}, {}, {}, {})
    assert spec.load_reader(BENCH, "allpairs_roofline")(empty) is None


def test_ingest_readers():
    ops = [T.Ev("k", 10 * MS, 4 * MS, BUILD.format(4)),
           T.Ev("k", 14 * MS, 2 * MS, BUILD.format(5)),
           T.Ev("copy", 30 * MS, 4 * MS, "copy")]
    host = [T.Ev(T.WINDOW, 0, 50 * MS), T.Ev("bench.add_many", 5 * MS,
                                             20 * MS),
            T.Ev("bench.add_many", 25 * MS, 20 * MS)]
    t = T.Trace((0.0, 50 * MS), {"/device:TPU:0": ops}, host)
    rec = Records(t, [], {"block_rows": 8, "universe": 1 << 20}, {}, {},
                  spec.peaks(BENCH, "TPU v5 lite"))
    read = lambda name: spec.load_reader(BENCH, name)(rec)
    want = 100 * 2 * costs.build_block_bytes(8, 1 << 20) / 819e9 / 0.006
    assert read("build_roofline") == pytest.approx(want)
    assert read("ingest_host_ms_per_block") == pytest.approx(15.0)
    assert read("device_idle_share.ingest") == pytest.approx(80.0)
