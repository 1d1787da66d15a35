"""Structured solve telemetry: spans, metrics registry, flight recorder,
trace export.

The observability layer PETSc deployments get from ``-log_view`` /
``PetscLogStage``, made machine-readable and per-request:

* **spans** (:mod:`.spans`) — a context-propagated hierarchical span API
  with wall/monotonic timestamps and structured attributes, emitted from
  ``KSP.solve/solve_many``, ``RefinedKSP``, ``resilient_solve`` (the
  recovery-ladder stages become child spans carrying the RecoveryEvent
  data), the ``SolveServer`` dispatcher, and the EPS/PC (MG) entries;
* **metrics registry** (:mod:`.metrics`) — typed counters/gauges/
  histograms replacing the ad-hoc ``record_*`` globals (which remain as
  thin shims in ``utils/profiling.py``), with :func:`snapshot` JSON and
  a Prometheus text exporter (``SolveServer.metrics_endpoint()``);
* **flight recorder** (:mod:`.flight`) — a bounded ring of recent span
  trees + fault/recovery events, dumped automatically on unrecovered
  errors and on demand;
* **trace export** (:mod:`.export`) — Chrome/Perfetto trace-event JSON;
* **compile spans** (:mod:`.compile_events`) — every trace, lower and
  compile-or-cache-load JAX does while spans are armed, as ``compile.*``
  spans under the span that caused it.

Every name is registered in :mod:`.names` (``NAMES``) — validated at
runtime and by tpslint TPS014.

Gating: the METRICS registry is always on (host dict updates, the same
cost class as the globals it replaced). SPANS + flight ring + trace are
armed by :func:`enable` / the ``-telemetry`` flag; disabled they are a
shared no-op context manager — no allocation, no clock read, no device
work, zero extra XLA programs (the cfg12 bench gates the armed overhead
at <2% wall). :func:`enable` is the one call that touches jax: it
registers the compile listener with ``jax.monitoring``, once per
process; disabled, the listener returns at once.

Runtime flags (utils/options): ``-telemetry`` (arm spans+flight),
``-telemetry_flight_len N`` (ring length), ``-telemetry_dump <path>``
(at-exit JSON dump of the metrics snapshot + flight ring).
"""

from __future__ import annotations

import atexit
import json

from .export import export_trace, trace_events
from .flight import auto_dump, recorder as flight_recorder
from .metrics import Histogram, percentile, registry
from .names import FLIGHT_FAULT_POINTS, NAMES
from .spans import (NOOP, Span, current_span, disable, enable, enabled,
                    span, start_span)

__all__ = [
    "NAMES", "FLIGHT_FAULT_POINTS", "NOOP", "Span", "Histogram",
    "auto_dump", "configure_from_options", "current_span", "disable",
    "enable", "enabled", "export_trace", "flight_recorder", "percentile",
    "prometheus_text", "registry", "reset", "snapshot", "span",
    "start_span", "trace_events",
]


def snapshot() -> dict:
    """JSON-able snapshot of every registry metric."""
    return registry.snapshot()


def prometheus_text() -> str:
    """The registry in Prometheus text exposition format."""
    return registry.prometheus_text()


def reset():
    """Clear metrics + flight ring (test isolation; spans' enabled flag
    is left as-is — use :func:`disable`)."""
    registry.reset()
    flight_recorder.clear()


_dump_armed = False


def _atexit_dump(path: str):
    payload = {"metrics": snapshot(),
               "flight": flight_recorder.entries()}
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)


def configure_from_options():
    """Apply the ``-telemetry*`` runtime flags (called from
    ``utils.options.init`` after argv parsing, and safe to call again —
    the PETSc setFromOptions idiom)."""
    global _dump_armed
    from ..utils.options import global_options
    opt = global_options()
    if opt.get_bool("telemetry", False):
        enable()
    flen = opt.get_int("telemetry_flight_len", 0)
    if flen > 0:
        flight_recorder.set_maxlen(flen)
    dump = opt.get_string("telemetry_dump")
    if dump and not _dump_armed:
        _dump_armed = True
        atexit.register(_atexit_dump, dump)
