"""repro.obs — unified observability facade (DESIGN.md §19).

One process-local switch, three pillars:

- :mod:`repro.obs.metrics` — thread-safe registry of labeled counter /
  gauge / histogram families with Prometheus-text and JSON-snapshot
  exporters.
- :mod:`repro.obs.tracing` — context-manager spans in a bounded ring
  buffer with a Chrome ``trace_event`` JSONL exporter.
- :mod:`repro.obs.quality` — estimator-health self-monitoring: tau /
  overflow / coverage gauges, canary-pair error-budget SLO, WAL and
  recovery health.

**The disabled path is the default and it is free.**  Every call site in
the repo goes through the module accessors below (``obs.counter(...)``,
``obs.span(...)``, ``obs.op(...)``); while disabled they return shared
stateless no-op singletons, so an uninstrumented-feeling hot path costs
one module-attribute read and a bool test — zero per-call allocation
(asserted by ``tests/test_obs.py`` under ``tracemalloc`` and by the
``benchmarks/obs_overhead.py`` gate).

Enable with :func:`enable` or by exporting ``REPRO_OBS=1`` before
import.  Call sites never branch themselves and never hold stale
handles across an enable/disable flip, because resolution happens per
call inside the accessor.

**jit boundary rule** (DESIGN.md §19): never open a span inside a
jitted body — Python there runs only at trace time, so a span would
time tracing once and then vanish from every cached execution while its
metrics silently stop moving.  Engine entry points instead call
:func:`engine_op` with an ``is_tracing`` flag probed from their inputs:
under a ``jax.core.Tracer`` the call increments
``repro_engine_traces_total{fn=...}`` (retrace/recompile visibility)
and returns the no-op span; concrete inputs get a real dispatch span.
jax itself is never imported here — call sites pass the verdict in.

**Profiler sink** (DESIGN.md §19): while a JAX profiler session is
active, :func:`span`, :func:`op` and eager :func:`engine_op` also open a
``jax.profiler.TraceAnnotation`` of the same name, whether or not
``obs`` is enabled, so the program's steps land on the profiler's clock
beside the device's ops.  Annotations carry the name only (attributes
stay on the ring-buffer span).  The profiler is looked up in
``sys.modules`` on each call: before ``jax`` is imported it counts as
off, and with no session the only cost is that lookup and one
``TraceAnnotation.is_enabled()`` call, neither of which allocates.
"""
from __future__ import annotations

import os
import sys
import threading

from .metrics import (  # noqa: F401  (re-exported)
    DEFAULT_BUCKETS,
    NOOP_COUNTER,
    NOOP_GAUGE,
    NOOP_HISTOGRAM,
    NOOP_METRIC,
    MetricsRegistry,
    exponential_buckets,
)
from .tracing import NOOP_SPAN, Span, Tracer  # noqa: F401

_ENABLED = False
_REGISTRY = MetricsRegistry()
_TRACER = Tracer()
_QUALITY = None            # lazy: quality pulls in numpy
_QUALITY_LOCK = threading.Lock()


# ---------------------------------------------------------------------------
# Switch
# ---------------------------------------------------------------------------


def enable() -> None:
    """Turn observability on process-wide (idempotent)."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    """Turn observability off; accumulated metrics/spans are retained
    until :func:`reset`."""
    global _ENABLED
    _ENABLED = False


def enabled() -> bool:
    return _ENABLED


def reset() -> None:
    """Drop all recorded state (families, spans, quality monitors) —
    test isolation and fresh measurement windows."""
    global _QUALITY
    _REGISTRY.reset()
    _TRACER.clear()
    with _QUALITY_LOCK:
        _QUALITY = None


# ---------------------------------------------------------------------------
# Accessors — the only API instrumented call sites use
# ---------------------------------------------------------------------------


def registry() -> MetricsRegistry:
    """The live registry (always real, even while disabled — exporters
    and tests may inspect it; *recording* goes through the accessors
    below, which are what the switch gates)."""
    return _REGISTRY


def tracer() -> Tracer:
    return _TRACER


def quality_monitor():
    """The process :class:`~repro.obs.quality.QualityMonitor`
    (created on first use; always bound to :func:`registry`).

    Named ``quality_monitor`` (not ``quality``) on purpose: importing the
    :mod:`repro.obs.quality` submodule binds ``repro.obs.quality`` to the
    *module* object, which would silently shadow a function of the same
    name."""
    global _QUALITY
    q = _QUALITY
    if q is None:
        with _QUALITY_LOCK:
            if _QUALITY is None:
                from .quality import QualityMonitor
                _QUALITY = QualityMonitor(_REGISTRY)
            q = _QUALITY
    return q


def counter(name: str, help: str = "", labelnames=()):
    """Counter family, or the shared no-op when disabled."""
    if not _ENABLED:
        return NOOP_COUNTER
    return _REGISTRY.counter(name, help, labelnames)


def gauge(name: str, help: str = "", labelnames=()):
    if not _ENABLED:
        return NOOP_GAUGE
    return _REGISTRY.gauge(name, help, labelnames)


def histogram(name: str, help: str = "", labelnames=(), buckets=None):
    if not _ENABLED:
        return NOOP_HISTOGRAM
    return _REGISTRY.histogram(name, help, labelnames, buckets)


def _profiler_annotation():
    """``jax.profiler.TraceAnnotation`` while a profiler session is
    active, else None (also before ``jax`` has been imported)."""
    ann = getattr(sys.modules.get("jax.profiler"), "TraceAnnotation", None)
    return ann if ann is not None and ann.is_enabled() else None


class _Annotated:
    """A profiler annotation around a span (a ring-buffer :class:`Span`,
    an :class:`_Op` or :data:`NOOP_SPAN`); ``with`` binds the inner
    span, so ``set`` keeps working.  Only constructed while a profiler
    session is active."""

    __slots__ = ("_ann", "_inner")

    def __init__(self, ann, inner):
        self._ann = ann
        self._inner = inner

    def __enter__(self):
        self._ann.__enter__()
        return self._inner.__enter__()

    def __exit__(self, exc_type, exc, tb) -> bool:
        try:
            return self._inner.__exit__(exc_type, exc, tb)
        finally:
            self._ann.__exit__(exc_type, exc, tb)


def _sink(name: str, inner):
    """``inner``, wrapped in a profiler annotation named ``name`` while a
    profiler session is active."""
    ann = _profiler_annotation()
    return inner if ann is None else _Annotated(ann(name), inner)


def span(name: str):
    """Plain tracing span (no metrics), or the shared no-op span; under
    an active profiler session also a profiler annotation."""
    return _sink(name, _TRACER.span(name) if _ENABLED else NOOP_SPAN)


class _Op:
    """Timed operation: one span plus the shared labeled op families
    ``repro_op_total/seconds/errors_total{op=...}`` (DESIGN.md §19).
    Only ever constructed while enabled — the disabled path returns
    :data:`NOOP_SPAN` from :func:`op` before reaching here."""

    __slots__ = ("name", "_span")

    def __init__(self, name: str):
        self.name = name
        self._span = _TRACER.span(name)

    def __enter__(self) -> Span:
        return self._span.__enter__()

    def __exit__(self, exc_type, exc, tb) -> bool:
        self._span.__exit__(exc_type, exc, tb)
        r = _REGISTRY
        r.counter("repro_op_total", "operations by dotted span name",
                  ("op",)).labels(self.name).inc()
        r.histogram("repro_op_seconds", "operation latency",
                    ("op",)).labels(self.name).observe(self._span.dur)
        if exc_type is not None:
            r.counter("repro_op_errors_total", "operations that raised",
                      ("op",)).labels(self.name).inc()
        return False


def op(name: str):
    """Timed span: records the span *and* count/latency/error metrics
    under the shared ``repro_op_*{op=name}`` families.  This is the
    default instrumentation primitive for serve/engine entry points."""
    return _sink(name, _Op(name) if _ENABLED else NOOP_SPAN)


def engine_op(name: str, is_tracing: bool):
    """jit-aware :func:`op` for engine entry points.  The caller probes
    its inputs for ``jax.core.Tracer`` leaves and passes the verdict —
    jax never crosses into ``repro.obs``.  Under tracing: bump
    ``repro_engine_traces_total{fn=name}`` (each bump is one retrace /
    compile of that entry point) and return the no-op span, so nothing
    is timed inside ``jax.jit``.  Eager: a real ``engine.<name>``
    dispatch span."""
    if is_tracing:
        if _ENABLED:
            _REGISTRY.counter(
                "repro_engine_traces_total",
                "jax trace/compile passes through engine entry points "
                "(steady state: constant; growth = retrace churn)",
                ("fn",)).labels(name).inc()
        return NOOP_SPAN
    if not _ENABLED and _profiler_annotation() is None:
        return NOOP_SPAN
    full = "engine." + name
    return _sink(full, _Op(full) if _ENABLED else NOOP_SPAN)


# ---------------------------------------------------------------------------
# Exposition conveniences
# ---------------------------------------------------------------------------


def snapshot() -> dict:
    return _REGISTRY.snapshot()


def prometheus_text() -> str:
    return _REGISTRY.prometheus_text()


def export_chrome(path: str) -> int:
    return _TRACER.export_chrome(path)


def __getattr__(name: str):
    # heavy (numpy-touching) quality symbols resolve lazily so that
    # `import repro.obs` stays stdlib-only for the kernels wrappers
    if name in ("QualityMonitor", "CanaryMonitor", "CanaryPair",
                "CanaryReading", "chebyshev_halfwidth", "observe_recovery"):
        from . import quality as _q
        return getattr(_q, name)
    raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")


if os.environ.get("REPRO_OBS", "").strip().lower() in ("1", "true", "on"):
    enable()
