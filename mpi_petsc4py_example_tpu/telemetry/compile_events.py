"""Compile spans: what JAX traces, lowers and compiles while telemetry is
enabled.

JAX reports each phase of building a program through ``jax.monitoring``
as a duration event, when the phase ends, on the thread that builds it.
The listener turns each event into a completed span on the
``perf_counter`` clock every span uses (``t1`` is now, ``t0`` is ``t1``
less the duration): a child of the span open on that thread (the
``ksp.dispatch`` of a solve's first call, ``ksp.setup``, ...), or,
when none is open (a program built outside any solve), a child of a
``compile.group`` root. The compile spans recorded one after another
with no span open share one group, and so one slot of the flight ring:
the hundreds of one-op programs JAX compiles for eager calls would
otherwise push solve trees and fault events out of it::

    compile.trace    /jax/core/compile/jaxpr_trace_duration
    compile.lower    /jax/core/compile/jaxpr_to_mlir_module_duration
    compile.backend  /jax/core/compile/backend_compile_duration: the XLA
                     compile or the persistent-cache load; ``cache_hit``
                     says which (a /jax/compilation_cache/cache_hits
                     event came during it)

Each span carries the ``fun_name`` JAX passes. JAX nests its events: a
jit traced inside another's trace reports an event of its own, inside
the outer one's interval, so the spans are siblings that overlap. Take
the union of their intervals, not the sum, to count seconds.

:func:`listen` registers once, from ``telemetry.enable()``; after
``telemetry.disable()`` the listeners return at their first line.
"""

from __future__ import annotations

import threading
import time

from . import spans as _spans

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
BACKEND_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"

_lock = threading.Lock()
_registered = False
_hit = threading.local()      # a cache hit seen on this thread's compile


def listen():
    """Register the listeners with ``jax.monitoring`` (once)."""
    global _registered
    with _lock:
        if _registered:
            return
        import jax.monitoring
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _registered = True


def _on_event(event, **kw):
    if not _spans.enabled():
        return
    if event == CACHE_HIT_EVENT:
        _hit.seen = True


def _on_duration(event, duration, **kw):
    if not _spans.enabled() or event not in (TRACE_EVENT, LOWER_EVENT,
                                             BACKEND_EVENT):
        return
    t1 = time.perf_counter()
    t0 = t1 - float(duration)
    attrs = {"fun_name": kw["fun_name"]} if "fun_name" in kw else {}
    group = _spans.start_span("compile.group")    # held with no span open
    if event == TRACE_EVENT:
        _spans.completed_span("compile.trace", t0, t1, group=group,
                              **attrs)
    elif event == LOWER_EVENT:
        _hit.seen = False
        _spans.completed_span("compile.lower", t0, t1, group=group,
                              **attrs)
    elif event == BACKEND_EVENT:
        hit = getattr(_hit, "seen", False)
        _hit.seen = False
        _spans.completed_span("compile.backend", t0, t1, group=group,
                              cache_hit=hit, **attrs)
