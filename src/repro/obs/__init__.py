"""`repro.obs` — dependency-free tracing + metrics for the serving stack.

One subsystem answers "why was this response slow?" end to end:

- :mod:`repro.obs.tracing` — request-scoped spans (trace_id/span_id via
  contextvars, thread-safe across the frontend's scheduler thread) opened
  at frontend admission, per cache tier probed, around the fused engine
  pass and each of its phases, and at kernel dispatch; mirrored into the
  JAX profiler's trace while tracing is on.
- :mod:`repro.obs.host` — after-the-fact spans for what no layer calls:
  garbage collections (``host.gc``) and JAX compilations
  (``jax.compile``).
- :mod:`repro.obs.metrics` — typed counters/gauges/histograms with
  fixed-bucket p50/p99; the components' ``telemetry()`` dicts are thin
  views over it (:class:`StatsView`), byte-identical key sets.
- :mod:`repro.obs.export` — JSON-lines span log + Chrome ``trace_event``
  timelines (``launch.serve --dcim-trace PATH``, Perfetto-loadable) and
  :func:`metrics_snapshot` text exposition.

Tracing is off by default; :func:`configure` turns it on (optionally with
a sampling rate) and the disabled path is a single contextvar read —
≤1% overhead on ``service/p50_latency_ms``, asserted in CI.
"""

from __future__ import annotations

from . import host
from .export import (chrome_trace_events, span_dicts, write_chrome_trace,
                     write_spans_jsonl)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry, StatsView,
                      get_registry, metrics_snapshot)
from .tracing import NOOP_SPAN, Span, SpanContext, SpanHandle, Tracer, tracer

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "StatsView",
    "get_registry", "metrics_snapshot",
    "NOOP_SPAN", "Span", "SpanContext", "SpanHandle", "Tracer", "tracer",
    "span_dicts", "write_spans_jsonl", "chrome_trace_events",
    "write_chrome_trace",
    "configure", "install_engine_hooks", "uninstall_engine_hooks",
]


def configure(enabled: bool | None = None, sample: float | None = None,
              clock=None) -> Tracer:
    """Configure the global tracer.  Enabling also installs the engine
    execute/latency metric hooks and the host GC and compile observers
    (:mod:`repro.obs.host`); disabling removes the host observers."""
    tracer.configure(enabled=enabled, sample=sample, clock=clock)
    if tracer.enabled:
        install_engine_hooks()
        host.install()
    else:
        host.uninstall()
    return tracer


# -- engine metrics via the engine's own observation hooks -------------------
#
# The engine opens its own spans (core/engine.py); these hooks only feed the
# operator-facing ``engine/executions`` counter and ``engine/pass_latency_s``
# histogram.

_installed = False


def _on_execute(plan) -> None:
    get_registry().counter("engine/executions").inc()


def _on_latency(plan, elapsed_s: float) -> None:
    get_registry().histogram("engine/pass_latency_s").observe(elapsed_s)


def install_engine_hooks() -> None:
    """Register the engine execute/latency metric hooks (idempotent).
    Imports the engine lazily so ``repro.obs`` itself stays importable
    without jax."""
    global _installed
    if _installed:
        return
    from ..core import engine
    engine.add_execute_hook(_on_execute)
    engine.add_latency_hook(_on_latency)
    _installed = True


def uninstall_engine_hooks() -> None:
    global _installed
    if not _installed:
        return
    from ..core import engine
    try:
        engine.remove_execute_hook(_on_execute)
    except ValueError:
        pass
    try:
        engine.remove_latency_hook(_on_latency)
    except ValueError:
        pass
    _installed = False
