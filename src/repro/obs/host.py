"""Host-runtime spans: garbage collections and JAX compilations.

Neither is work the serving stack calls, so neither can open a span around
itself.  Both are observed through the runtime's own hooks and recorded
after the fact on the global tracer:

  ``host.gc``      each garbage collection of :data:`GC_MIN_S` or more,
                   tagged with its ``generation``, from ``gc.callbacks``.
                   The profiler annotation opens at the collection's start
                   callback and closes at its stop callback; the span is
                   queued without a lock (:meth:`Tracer.defer`), because a
                   collection may start while any lock is held;
  ``jax.compile``  each backend compilation, from JAX's
                   ``/jax/core/compile/backend_compile_duration``
                   monitoring event, placed to end when the event arrives,
                   under the current span if one is open, else as a root.

:func:`install` runs when ``repro.obs.configure(enabled=True)`` turns
tracing on and :func:`uninstall` when it turns it off; both callbacks also
return at once whenever the tracer is off.
"""

from __future__ import annotations

import gc

from .tracing import tracer

#: Collections shorter than this are not recorded.
GC_MIN_S = 1e-3

#: JAX's monitoring event for one backend compilation.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# (start_s, parent context, profiler annotation) of the collection running
_gc_open: tuple | None = None
_compile_listener_on = False


def _on_gc(phase: str, info: dict) -> None:
    global _gc_open
    if not tracer.enabled:
        return
    if phase == "start":
        _gc_open = (tracer.clock(), tracer.current(), tracer.mirror("host.gc"))
        return
    if _gc_open is None:
        return
    start_s, parent, annotation = _gc_open
    _gc_open = None
    if annotation is not None:
        annotation.__exit__(None, None, None)
    end_s = tracer.clock()
    if end_s - start_s >= GC_MIN_S:
        tracer.defer("host.gc", start_s, end_s,
                     tags={"generation": info.get("generation")},
                     parent=parent)


def _on_duration(event: str, duration_s: float, **kwargs) -> None:
    if event != COMPILE_EVENT or not tracer.enabled:
        return
    end_s = tracer.clock()
    tracer.record("jax.compile", end_s - duration_s, end_s,
                  tags={"fun_name": kwargs.get("fun_name")})


def install() -> None:
    """Add the GC callback and the compile listener (idempotent)."""
    global _compile_listener_on
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
    if not _compile_listener_on:
        try:
            from jax import monitoring
        except ImportError:
            return
        monitoring.register_event_duration_secs_listener(_on_duration)
        _compile_listener_on = True


def uninstall() -> None:
    """Remove both again (idempotent)."""
    global _compile_listener_on, _gc_open
    while _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)
    _gc_open = None
    if _compile_listener_on:
        from jax import monitoring
        monitoring.unregister_event_duration_listener(_on_duration)
        _compile_listener_on = False
