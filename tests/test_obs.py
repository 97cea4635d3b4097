"""Observability-layer tests (DESIGN.md §19).

Three contracts: the **disabled** path must hand out shared stateless
singletons with zero per-call allocation (the repo's default state costs
nothing); the **enabled** path must record balanced spans and correct
metrics even when instrumented bodies raise (no handle leaks — the chaos
suite runs force-enabled); and the **canary error-budget SLO** must flag
an injected shard-loss accuracy fault — the "silent wrong answers"
failure mode crash-only monitoring never sees.
"""
import json
import tracemalloc

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import (NOOP_COUNTER, NOOP_GAUGE, NOOP_HISTOGRAM,
                               MetricsRegistry, exponential_buckets)
from repro.obs.tracing import NOOP_SPAN, Tracer
from repro.obs.quality import (CanaryMonitor, QualityMonitor,
                               chebyshev_halfwidth, observe_recovery)

ADD_MANY_STEPS = [f"serve.index.add_many.{s}" for s in
                  ("validate", "upload", "dispatch", "fetch", "head")]
QUERY_STEPS = [f"serve.index.query.{s}" for s in
               ("validate", "upload", "dispatch", "fetch", "rank")]


@pytest.fixture(autouse=True)
def _obs_clean():
    """Every test starts and ends disabled with empty state (the suite
    must not leak enablement into unrelated tests)."""
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


# ---------------------------------------------------------------------------
# disabled path: shared singletons, zero allocation
# ---------------------------------------------------------------------------


def test_disabled_accessors_return_shared_singletons():
    assert obs.counter("repro_x_total") is NOOP_COUNTER
    assert obs.gauge("repro_x") is NOOP_GAUGE
    assert obs.histogram("repro_x_seconds") is NOOP_HISTOGRAM
    assert obs.span("anything") is NOOP_SPAN
    assert obs.op("anything") is NOOP_SPAN
    assert obs.engine_op("anything", False) is NOOP_SPAN
    assert obs.engine_op("anything", True) is NOOP_SPAN
    # the no-ops absorb the full recording API, including labels chains
    NOOP_COUNTER.labels("a", "b").inc(3)
    NOOP_GAUGE.labels("x").set(1.0)
    NOOP_HISTOGRAM.observe(0.5)
    with obs.op("noop") as sp:
        sp.set("k", "v")
    assert not obs.enabled()


def test_disabled_records_nothing():
    obs.counter("repro_never_total", "x").inc()
    with obs.span("never.span"):
        pass
    with obs.op("never.op"):
        pass
    with obs.engine_op("never.fn", False):
        pass
    assert obs.snapshot() == {}
    assert obs.registry().value("repro_op_total", "never.op") == 0.0
    assert obs.tracer().events() == []
    assert obs.tracer().spans_started == 0


def test_disabled_hot_loop_allocates_nothing():
    """The uninstrumented-feeling guarantee: a hot loop through every
    accessor while disabled must not allocate per call (shared
    singletons, no closures, no format strings)."""
    def hot():
        for _ in range(1000):
            obs.counter("repro_hot_total").inc()
            with obs.span("hot.span"):
                pass
            with obs.op("hot.op") as sp:
                sp.set("k", 1)
    hot()  # warm up: interned ints, bytecode, method caches
    tracemalloc.start()
    base, _ = tracemalloc.get_traced_memory()
    hot()
    now, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    # tracemalloc's own bookkeeping shows up as a small constant; per-call
    # allocation over 3000 accessor hits would be tens of kilobytes
    assert now - base < 2048, f"disabled path allocated {now - base} bytes"
    assert obs.tracer().spans_started == 0
    assert obs.registry().value("repro_op_total", "hot.op") == 0.0


def test_enable_disable_flip_without_stale_handles():
    """Call sites resolve through the accessor per call, so a flip takes
    effect immediately — no cached no-op keeps swallowing records."""
    obs.counter("repro_flip_total").inc()      # disabled: dropped
    obs.enable()
    obs.counter("repro_flip_total", "flips").inc()
    assert obs.registry().value("repro_flip_total") == 1.0
    obs.disable()
    obs.counter("repro_flip_total").inc()      # disabled again: dropped
    assert obs.registry().value("repro_flip_total") == 1.0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_counter_gauge_histogram_record():
    obs.enable()
    obs.counter("repro_c_total", "a counter").inc()
    obs.counter("repro_c_total").inc(2.5)
    obs.gauge("repro_g", "a gauge").set(7.0)
    h = obs.histogram("repro_h_seconds", "a histogram")
    h.observe(1e-5)
    h.observe(10.0)
    r = obs.registry()
    assert r.value("repro_c_total") == 3.5
    assert r.value("repro_g") == 7.0
    snap = obs.snapshot()
    assert snap["repro_h_seconds"]["series"][0]["count"] == 2


def test_labeled_families_are_independent_series():
    obs.enable()
    fam = obs.counter("repro_l_total", "labeled", ("op",))
    fam.labels("a").inc()
    fam.labels("b").inc(2)
    r = obs.registry()
    assert r.value("repro_l_total", "a") == 1.0
    assert r.value("repro_l_total", "b") == 2.0


def test_kind_and_label_mismatch_raise():
    obs.enable()
    obs.counter("repro_kind_total", "x")
    with pytest.raises(ValueError, match="kind"):
        obs.registry().gauge("repro_kind_total")
    with pytest.raises(ValueError, match="label"):
        obs.registry().counter("repro_kind_total", labelnames=("x",))


def test_prometheus_text_exposition():
    obs.enable()
    obs.counter("repro_p_total", "help text", ("op",)).labels("q\\x").inc()
    obs.gauge("repro_pg", "a gauge").set(1.5)
    obs.histogram("repro_ph", "h", buckets=(1.0, 2.0)).observe(1.5)
    text = obs.prometheus_text()
    assert "# HELP repro_p_total help text" in text
    assert "# TYPE repro_p_total counter" in text
    assert 'repro_p_total{op="q\\\\x"} 1' in text       # escaped backslash
    assert "repro_pg 1.5" in text
    assert 'repro_ph_bucket{le="2.0"} 1' in text        # cumulative buckets
    assert 'repro_ph_bucket{le="+Inf"} 1' in text
    assert "repro_ph_count 1" in text


def test_exponential_buckets():
    b = exponential_buckets(1.0, 2.0, 4)
    assert b == (1.0, 2.0, 4.0, 8.0)
    with pytest.raises(ValueError):
        exponential_buckets(1.0, 1.0, 4)


# ---------------------------------------------------------------------------
# tracing: balance, parenting, export
# ---------------------------------------------------------------------------


def test_span_nesting_records_parents():
    obs.enable()
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    ev = obs.tracer().events()
    inner = next(e for e in ev if e.name == "inner")
    outer = next(e for e in ev if e.name == "outer")
    assert inner.parent_id == outer.span_id
    assert outer.parent_id is None
    assert obs.tracer().active_depth() == 0


def test_spans_balanced_across_exceptions():
    """The chaos contract: a raising instrumented body must still pop its
    span (no depth leak), mark it failed, and bump the error counter."""
    obs.enable()
    with pytest.raises(RuntimeError):
        with obs.op("serve.fail"):
            raise RuntimeError("boom")
    assert obs.tracer().active_depth() == 0
    ev = obs.tracer().events()
    assert len(ev) == 1 and ev[0].ok is False
    assert ev[0].attrs.get("error") == "RuntimeError"
    r = obs.registry()
    assert r.value("repro_op_errors_total", "serve.fail") == 1.0
    assert r.value("repro_op_total", "serve.fail") == 1.0
    # and the tracer still works for the next span
    with obs.op("serve.next"):
        pass
    assert obs.tracer().active_depth() == 0


def test_op_records_count_latency_error_families():
    obs.enable()
    with obs.op("serve.thing") as sp:
        sp.set("rows", 3)
    snap = obs.snapshot()
    assert obs.registry().value("repro_op_total", "serve.thing") == 1.0
    série = snap["repro_op_seconds"]["series"][0]
    assert série["count"] == 1 and série["sum"] >= 0.0
    assert "repro_op_errors_total" not in snap


def test_ring_buffer_bounds_and_counts_drops():
    t = Tracer(capacity=4)
    for i in range(10):
        with t.span(f"s{i}"):
            pass
    assert len(t.events()) == 4
    assert t.spans_started == 10 and t.spans_finished == 10
    assert t.spans_dropped == 6
    assert [e.name for e in t.events()] == ["s6", "s7", "s8", "s9"]


def test_chrome_trace_export(tmp_path):
    obs.enable()
    with obs.span("outer") as sp:
        sp.set("rows", 5)
        with obs.span("inner"):
            pass
    path = tmp_path / "trace.jsonl"
    n = obs.export_chrome(str(path))
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert n == len(lines) == 2
    for ev in lines:
        assert ev["ph"] == "X" and ev["ts"] >= 0 and ev["dur"] >= 0
    outer = next(e for e in lines if e["name"] == "outer")
    assert outer["args"]["rows"] == 5


def test_engine_op_tracing_verdict():
    """jit boundary rule: under tracing the engine entry point only bumps
    the retrace counter and returns the no-op span (nothing is timed
    inside jit); eager calls get a real dispatch span."""
    obs.enable()
    sp = obs.engine_op("estimate_product", True)
    assert sp is NOOP_SPAN
    assert obs.registry().value("repro_engine_traces_total",
                                "estimate_product") == 1.0
    with obs.engine_op("estimate_product", False):
        pass
    assert obs.registry().value("repro_op_total",
                                "engine.estimate_product") == 1.0
    assert obs.tracer().events()[-1].name == "engine.estimate_product"


# ---------------------------------------------------------------------------
# profiler sink: program spans on the JAX profiler's clock
# ---------------------------------------------------------------------------


def test_profiler_off_keeps_singletons_and_allocates_nothing():
    """With jax loaded but no profiler session, the accessors still hand
    out the shared no-op span and the hot loop (which now asks the
    profiler on every call) still allocates nothing."""
    import jax.profiler
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert obs.span("anything") is NOOP_SPAN
    assert obs.op("anything") is NOOP_SPAN
    assert obs.engine_op("anything", False) is NOOP_SPAN

    def hot():
        for _ in range(1000):
            with obs.span("hot.span"):
                pass
            with obs.op("hot.op") as sp:
                sp.set("k", 1)
            with obs.engine_op("hot.fn", False):
                pass
    hot()
    tracemalloc.start()
    base, _ = tracemalloc.get_traced_memory()
    hot()
    now, _ = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert now - base < 2048, f"profiler-off path allocated {now - base}"


def _profiled(tmp_path, body):
    """Run ``body`` inside a JAX profiler session and an annotation of the
    caller's own; return the host events of the caller's thread as
    (name, start_ns, end_ns)."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("test.caller"):
            body()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for e in line.events]
            if any(n == "test.caller" for n, _, _ in evs):
                return evs
    raise AssertionError("no host line holds the caller's annotation")


def _serve_once():
    from repro.serve import SketchIndex
    rng = np.random.default_rng(12)
    idx = SketchIndex(m=32, n_buckets=64, seed=11)
    idx.add_many(["w0", "w1"], rng.normal(size=(2, 128)).astype(np.float32))
    idx.query(rng.normal(size=128).astype(np.float32))    # compile first
    V = rng.normal(size=(3, 128)).astype(np.float32)
    return lambda: (idx.add_many([f"v{i}" for i in range(3)], V),
                    idx.query(V[0], top_k=2))


def _within(evs, name, parent):
    (s, e), = [(s, e) for n, s, e in evs if n == name]
    (ps, pe), = [(s, e) for n, s, e in evs if n == parent]
    return ps <= s and e <= pe


@pytest.mark.parametrize("enabled", [False, True], ids=["disabled",
                                                        "enabled"])
def test_profiler_session_records_service_steps(tmp_path, enabled):
    """Inside a profiler session ``add_many`` and ``query`` leave one host
    event per step, each inside its call, on the caller's thread, whether
    or not obs is enabled; disabled, nothing reaches the ring or the
    registry."""
    serve = _serve_once()
    if enabled:
        obs.enable()
    evs = _profiled(tmp_path, serve)
    for parent, steps in (("serve.index.add_many", ADD_MANY_STEPS),
                          ("serve.index.query", QUERY_STEPS)):
        assert _within(evs, parent, "test.caller")
        for step in steps:
            assert _within(evs, step, parent), step
    if not enabled:
        assert obs.tracer().events() == [] and obs.snapshot() == {}
    else:
        r = obs.registry()
        assert r.value("repro_op_total", "serve.index.add_many") == 1.0
        assert r.value("repro_op_total", "serve.index.query") == 1.0


def test_profiler_annotation_names_equal_ring_span_names(tmp_path):
    serve = _serve_once()
    obs.enable()
    evs = _profiled(tmp_path, serve)
    ring = sorted(s.name for s in obs.tracer().events())
    ours = sorted(n for n, _, _ in evs if n.startswith(("serve.", "engine.")))
    assert ring == ours
    assert set(ADD_MANY_STEPS + QUERY_STEPS) <= set(ring)


def test_profiler_annotation_keeps_span_api_and_balance(tmp_path):
    """Under a session the accessors return annotated spans whose ``with``
    target still takes attributes; a raising body closes the annotation
    and, enabled, marks the ring span failed; eager engine ops are
    annotated as ``engine.<fn>``, traced ones are not."""
    obs.enable()

    def body():
        with obs.op("serve.outer") as sp:
            sp.set("rows", 2)
            with pytest.raises(RuntimeError):
                with obs.span("serve.outer.step"):
                    raise RuntimeError("boom")
            with obs.engine_op("estimate_product", False):
                pass
            assert obs.engine_op("estimate_product", True) is NOOP_SPAN
    evs = _profiled(tmp_path, body)
    assert _within(evs, "serve.outer.step", "serve.outer")
    assert _within(evs, "engine.estimate_product", "serve.outer")
    assert obs.tracer().active_depth() == 0
    step = next(s for s in obs.tracer().events()
                if s.name == "serve.outer.step")
    assert step.ok is False
    outer = next(s for s in obs.tracer().events() if s.name == "serve.outer")
    assert outer.attrs == {"rows": 2}
    assert obs.registry().value("repro_engine_traces_total",
                                "estimate_product") == 1.0


# ---------------------------------------------------------------------------
# quality: ingest, recovery, canary SLO
# ---------------------------------------------------------------------------


def test_quality_ingest_tau_and_overflow():
    r = MetricsRegistry()
    q = QualityMonitor(r)
    q.observe_ingest([0.5, 0.3], [0, 2])
    q.observe_ingest(0.1, 0)
    assert r.value("repro_quality_tau_last") == pytest.approx(0.1)
    assert r.value("repro_quality_ingest_rows_total") == 3
    assert r.value("repro_quality_overflow_entries_total") == 2
    assert r.value("repro_quality_overflow_rows_total") == 1
    # infinite tau (keep-everything rows) must not poison the EWMA
    q.observe_ingest(np.inf)
    assert np.isfinite(r.value("repro_quality_tau_ewma"))


def test_observe_recovery_gauges():
    r = MetricsRegistry()
    observe_recovery(r, replayed_ops=7, dropped_tail=1,
                     snapshot_mtime=90.0, now=100.0)
    assert r.value("repro_recovery_total") == 1
    assert r.value("repro_recovery_replayed_ops") == 7
    assert r.value("repro_recovery_dropped_tail") == 1
    assert r.value("repro_recovery_snapshot_age_seconds") == 10.0
    observe_recovery(r, replayed_ops=0, dropped_tail=0, snapshot_mtime=None)
    assert r.value("repro_recovery_snapshot_age_seconds") == -1.0


def test_chebyshev_halfwidth_formula():
    # Var <= 2/(m-1) ||a||^2 ||b||^2; halfwidth = sqrt(Var / delta)
    assert chebyshev_halfwidth(4.0, 9.0, 101, 0.05) == pytest.approx(
        np.sqrt(2.0 / 100 * 36.0 / 0.05))


def test_canary_healthy_index_within_budget():
    from repro.serve import SketchIndex
    rng = np.random.default_rng(5)
    n, m = 512, 256
    idx = SketchIndex(m=m, n_buckets=1024, seed=11)
    V = rng.normal(size=(4, n)).astype(np.float32)
    idx.add_many([f"v{i}" for i in range(4)], V)
    qv = rng.normal(size=n).astype(np.float32)
    r = MetricsRegistry()
    mon = CanaryMonitor.from_vectors(
        idx, [("c0", qv, "v0", V[0])], registry=r, m=m)
    readings = mon.check()
    assert len(readings) == 1 and not readings[0].violated
    assert r.value("repro_canary_slo_ok") == 1.0
    assert r.value("repro_canary_checks_total") == 1


def test_canary_maybe_check_rate_limits():
    from repro.serve import SketchIndex
    rng = np.random.default_rng(6)
    idx = SketchIndex(m=32, n_buckets=64, seed=11)
    v = rng.normal(size=128).astype(np.float32)
    idx.add("v0", v)
    r = MetricsRegistry()
    mon = CanaryMonitor.from_vectors(idx, [("c", v, "v0", v)],
                                     registry=r, every=3)
    assert mon.maybe_check() is None
    assert mon.maybe_check() is None
    assert mon.maybe_check() is not None
    assert r.value("repro_canary_checks_total") == 1


def test_canary_flags_injected_shard_loss():
    """The acceptance chaos scenario: kill half the shards of a resilient
    index and the canary error-budget gauge must flip to violation —
    degraded reads cover only surviving coordinate mass, so the realized
    error blows through the Theorem-1/3 half-width that certified the
    healthy estimator."""
    from repro.serve.resilience import ResilientSketchIndex, RetryPolicy
    n, shards, m = 1024, 4, 256
    idx = ResilientSketchIndex(n, num_shards=shards, m=m, n_buckets=512,
                               seed=11,
                               retry=RetryPolicy(attempts=1, deadline=None),
                               sleep=lambda s: None)
    # all-ones target: every shard slice holds n/shards units of mass, and
    # per-shard nnz (256) <= m so healthy estimates are exact
    ones = np.ones(n, np.float32)
    idx.add("target", ones)
    r = MetricsRegistry()
    mon = CanaryMonitor.from_vectors(
        idx, [("ones", ones, "target", ones)], registry=r, m=m)
    healthy = mon.check()[0]
    assert not healthy.violated and healthy.error < 1e-3
    assert r.value("repro_canary_slo_ok") == 1.0

    idx.kill_shard(1)
    idx.kill_shard(3)
    degraded = mon.check()[0]
    # exactly half the mass vanished: error = n/2 = 512, halfwidth ~ 406
    assert degraded.error == pytest.approx(n / 2, rel=1e-3)
    assert degraded.violated
    assert r.value("repro_canary_slo_ok") == 0.0
    assert r.value("repro_canary_error_budget_ratio") > 1.0
    assert r.value("repro_canary_violations_total") == 1
    assert r.value("repro_canary_budget_ratio", "ones") > 1.0


# ---------------------------------------------------------------------------
# force-enabled integration: serve hooks feed the registry
# ---------------------------------------------------------------------------


def test_sketch_index_hooks_record(tmp_path):
    from repro.serve import SketchIndex
    obs.enable()
    rng = np.random.default_rng(7)
    idx = SketchIndex(m=32, n_buckets=64, seed=11)
    V = rng.normal(size=(3, 128)).astype(np.float32)
    idx.add_many([f"v{i}" for i in range(3)], V)
    idx.query(rng.normal(size=128).astype(np.float32))
    idx.all_pairs()
    r = obs.registry()
    assert r.value("repro_op_total", "serve.index.add_many") == 1.0
    assert r.value("repro_op_total", "serve.index.query") == 1.0
    assert r.value("repro_op_total", "serve.index.all_pairs") == 1.0
    assert r.value("repro_quality_ingest_rows_total") == 3
    names = [s.name for s in obs.tracer().events()]
    for step in ADD_MANY_STEPS + QUERY_STEPS:
        assert names.count(step) == 1, step
    assert names.count("serve.index.all_pairs") == 1
    assert obs.tracer().active_depth() == 0


def test_query_program_compiles_once_per_corpus_shape():
    """The served query compiles once per padded corpus shape: repeated
    queries add nothing, an ``add_many`` that doubles the padded rows adds
    one; each query's step spans stay its children, in order."""
    from repro.kernels import sketch_and_query
    from repro.serve import SketchIndex
    sketch_and_query.clear_cache()
    obs.enable()
    rng = np.random.default_rng(16)
    idx = SketchIndex(m=32, n_buckets=64, seed=11)
    V = rng.normal(size=(16, 128)).astype(np.float32)
    idx.add_many([f"v{i}" for i in range(8)], V[:8])
    compiles = lambda: obs.registry().value(
        "repro_query_program_compiles_total")
    for v in V[:3]:
        idx.query(v, top_k=2)
    assert compiles() == 1
    idx.add_many([f"v{i}" for i in range(8, 16)], V[8:])
    assert idx._corpus().idx.shape[0] == 16
    for v in V[:2]:
        idx.query(v, top_k=2)
    assert compiles() == 2
    spans = obs.tracer().events()
    queries = [s for s in spans if s.name == "serve.index.query"]
    assert len(queries) == 5
    for parent in queries:
        steps = sorted((s for s in spans if s.parent_id == parent.span_id),
                       key=lambda s: s.t0)
        assert [s.name for s in steps] == QUERY_STEPS
        assert parent.t0 <= steps[0].t0
        assert steps[-1].t0 + steps[-1].dur <= parent.t0 + parent.dur


def test_discovery_scanstats_fold_into_registry():
    from repro.serve import DiscoveryEngine, SketchIndex
    obs.enable()
    rng = np.random.default_rng(8)
    idx = SketchIndex(m=32, n_buckets=64, seed=11)
    D = 24
    V = rng.normal(size=(D, 128)).astype(np.float32)
    idx.add_many([f"v{i}" for i in range(D)], V)
    eng = DiscoveryEngine(idx, tile=8)
    res = eng.top_pairs(k=5)
    r = obs.registry()
    # the ScanStats dataclass stays the per-call view; the registry holds
    # the same numbers as monitorable series, no extra plumbing
    assert r.value("repro_discovery_scans_total", "pairs") == 1.0
    assert r.value("repro_discovery_tiles_total", "pairs") == \
        res.stats.tiles_total
    assert r.value("repro_discovery_tiles_pruned_total", "pairs") == \
        res.stats.tiles_pruned
    assert r.value("repro_discovery_kernel_launches_total", "pairs") == \
        res.stats.kernel_launches
    assert r.value("repro_op_total", "serve.discovery.top_pairs") == 1.0


def test_validation_rejects_counted():
    from repro.serve import SketchIndex
    obs.enable()
    idx = SketchIndex(m=16, n_buckets=32, seed=1)
    idx.add("a", np.ones(32, np.float32))
    with pytest.raises(ValueError):
        idx.add("a", np.ones(32, np.float32))
    with pytest.raises(ValueError):
        idx.add("b", np.full(32, np.nan, np.float32))
    r = obs.registry()
    assert r.value("repro_validation_rejects_total", "duplicate_name") == 1.0
    assert r.value("repro_validation_rejects_total", "nonfinite") == 1.0
    assert obs.tracer().active_depth() == 0   # failed adds popped cleanly


def test_durable_snapshot_recover_health(tmp_path):
    from repro.serve.resilience import DurableSketchIndex
    obs.enable()
    rng = np.random.default_rng(9)
    dur = DurableSketchIndex(str(tmp_path), m=32, n_buckets=64, seed=3)
    dur.add("a", rng.normal(size=128).astype(np.float32))
    dur.snapshot()
    dur.add("b", rng.normal(size=128).astype(np.float32))
    dur.journal.close()
    DurableSketchIndex.recover(str(tmp_path), m=32, n_buckets=64, seed=3)
    r = obs.registry()
    assert r.value("repro_snapshots_total") == 1.0
    assert r.value("repro_wal_appends_total", "add") >= 2.0
    assert r.value("repro_recovery_total") == 1.0
    assert r.value("repro_recovery_replayed_ops") == 1.0   # "b" replayed
    assert r.value("repro_recovery_snapshot_age_seconds") >= 0.0
    assert r.value("repro_op_total", "serve.durable.snapshot") == 1.0
    assert r.value("repro_op_total", "serve.durable.recover") == 1.0


def test_gradient_noise_scale_symmetry_and_gauges():
    """The i<j symmetry fix must agree with the full O(W^2) double loop
    (the estimator is symmetric in its arguments) and publish the GNS
    quality gauges when enabled."""
    import jax.numpy as jnp
    from repro.core.estimator import estimate_inner_product
    from repro.train.telemetry import gradient_noise_scale, sketch_grads
    rng = np.random.default_rng(10)
    shards = [sketch_grads([jnp.asarray(rng.normal(size=256), jnp.float32)],
                           64, 7) for _ in range(3)]
    # symmetry of the estimator itself (shared-seed joint inclusion)
    e_ij = float(estimate_inner_product(shards[0].sketch, shards[1].sketch))
    e_ji = float(estimate_inner_product(shards[1].sketch, shards[0].sketch))
    assert e_ij == pytest.approx(e_ji, rel=1e-6)
    obs.enable()
    gns = float(gradient_noise_scale(shards, 32))
    assert gns >= 0.0
    r = obs.registry()
    assert r.value("repro_train_gns") == pytest.approx(gns, rel=1e-6)
    assert r.value("repro_train_gns_ci_halfwidth") > 0.0
    assert r.value("repro_train_gns_big_norm2") > 0.0
