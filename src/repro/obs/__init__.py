"""Observability: metrics registry, lifecycle tracing, profiler hooks.

One :class:`Observability` bundle per engine threads three host-side,
hot-path-cheap surfaces through the serve stack (docs/observability.md):

- :mod:`repro.obs.metrics` — the typed registry that owns every serve
  counter/gauge/histogram (Prometheus text + JSON snapshot; exact
  p50/p95/p99).
- :mod:`repro.obs.trace` — the bounded request-lifecycle event ring,
  exported as Chrome/Perfetto trace-event JSON.
- :mod:`repro.obs.profile` — compile-event counters around every jitted
  serve callable + opt-in ``jax.profiler`` span annotations.

The trainer uses :mod:`repro.obs.profile` alone: its step spans are
always-on ``jax.profiler`` annotations and its jitted steps count their
traces into ``Trainer.compile_counts`` (docs/observability.md,
"Training").

Everything is append-only host work — no device sync is ever introduced
on the jitted path — and ``Observability(enabled=False)`` collapses the
whole stack to no-ops (the ``serve/obs_overhead`` bench row holds the
enabled/disabled throughput delta to ≤3%). The package is stdlib-only
at import time so the dependency-free lint CI job can load the metric
catalog.
"""
from __future__ import annotations

from repro.obs import profile
from repro.obs.metrics import METRIC_CATALOG, Histogram, MetricsRegistry
from repro.obs.trace import (RequestOutcome, TraceBuffer,
                             lifecycle_violations, request_outcomes)

__all__ = ["METRIC_CATALOG", "Histogram", "MetricsRegistry",
           "Observability", "RequestOutcome", "TraceBuffer",
           "lifecycle_violations", "request_outcomes", "profile"]


class Observability:
    """Per-engine observability bundle.

    Attributes:
        metrics: the :class:`~repro.obs.metrics.MetricsRegistry` (a
            disabled shell when ``enabled=False``).
        trace: the :class:`~repro.obs.trace.TraceBuffer`, or None when
            disabled or ``trace_capacity=0`` (emission sites guard on
            ``trace is not None``).
        compile_counts: ``{callable name: XLA traces}`` — every jitted
            serve callable registers itself here via
            :func:`repro.obs.profile.count_traces`.
        span: ``name -> context manager`` for profiler annotations
            (no-op unless profiling is opted in, see
            :func:`repro.obs.profile.spans_enabled`).
    """

    def __init__(self, enabled: bool = True, trace_capacity: int = 65536,
                 profile_spans=None):
        self.enabled = enabled
        self.metrics = MetricsRegistry(enabled=enabled)
        self.trace = TraceBuffer(trace_capacity) \
            if enabled and trace_capacity > 0 else None
        self.compile_counts: dict = {}
        self.span = profile.span_factory(
            enabled and profile.spans_enabled(profile_spans))
