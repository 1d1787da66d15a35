"""Profiling and observability (SURVEY.md §5.1/§5.5).

The reference reaches PETSc's ``-log_view`` / ``-ksp_monitor`` machinery
through the options DB [external]; equivalents here:

* per-iteration residual monitors — ``KSP.set_monitor`` / ``-ksp_monitor``
  (solvers/ksp.py), recorded into an in-program history buffer threaded
  through the compiled loop (no host callbacks — works on every runtime)
  and replayed to the user callbacks after the solve;
* a solve-event log — every KSP/EPS solve records (solver, n, iterations,
  wall, reason), the newest ``metrics.RESERVOIR_LEN`` kept; ``log_view()``
  prints the PETSc-``-log_view``-style summary, automatically at exit when
  ``-log_view`` is set.

Since the telemetry layer landed, this module is a COMPATIBILITY VIEW:
every ``record_*`` function is a thin shim writing into the typed
metrics registry (:mod:`..telemetry.metrics` — counters, gauges,
fixed-bucket histograms), and ``log_view`` renders FROM that registry —
one source of truth, so ``registry.snapshot()`` / the Prometheus
exporter / ``log_view`` can never disagree. The only state kept here is
the two event LOGS whose per-entry rows ``log_view`` prints (the solve
event table and the mesh-shrink detail list); everything countable
lives in the registry.
"""

from __future__ import annotations

import atexit
import collections
import sys
from dataclasses import dataclass

from .options import global_options
from ..telemetry import metrics as _metrics
from ..telemetry import flight as _flight
from ..telemetry import spans as _spans

_REG = _metrics.registry


@dataclass
class SolveEvent:
    what: str          # e.g. "KSPSolve(cg+jacobi)"
    n: int
    iterations: int
    wall: float
    reason: int


# bounded like the histogram reservoirs: a serving process solves forever
_EVENTS: collections.deque[SolveEvent] = collections.deque(
    maxlen=_metrics.RESERVOIR_LEN)
_atexit_armed = False


def record_event(what: str, n: int, iterations: int, wall: float,
                 reason: int):
    global _atexit_armed
    _EVENTS.append(SolveEvent(what, n, iterations, wall, reason))
    _REG.counter("solve.count").inc(label=what)
    _REG.counter("solve.iterations").inc(int(iterations))
    _REG.histogram("solve.latency_seconds").observe(float(wall))
    if iterations > 0 and wall > 0:
        _REG.histogram("solve.per_iter_seconds").observe(
            float(wall) / int(iterations))
    _REG.gauge("solve.programs").set(program_count())
    if not _atexit_armed and global_options().get_bool("log_view", False):
        _atexit_armed = True
        atexit.register(log_view)


def record_sdc(checks: int = 0, detections: int = 0, replacements: int = 0):
    """Accumulate silent-error-detection activity for the -log_view row:
    ABFT checksum checks performed, detectors fired, and true-residual
    replacements executed (solvers/ksp.py guarded solves)."""
    if checks:
        _REG.counter("abft.checks").inc(int(checks))
    if detections:
        _REG.counter("abft.detections").inc(int(detections))
    if replacements:
        _REG.counter("abft.replacements").inc(int(replacements))


def sdc_counts() -> dict:
    return {"abft_checks": int(_REG.counter("abft.checks").total()),
            "detections": int(_REG.counter("abft.detections").total()),
            "replacements": int(
                _REG.counter("abft.replacements").total())}


# solve-server coalescing totals (serving/server.py): dispatched batch
# widths (histogram), per-request queue waits, zero-padding columns —
# printed as a -log_view row. Process-wide twin of SolveServer.stats();
# BOTH views compute their wait statistics through the registry
# Histogram.summary helper, so they cannot drift.
def record_serving(width: int, waits=(), padded: int = 0):
    """Accumulate one dispatched coalesced batch: ``width`` REAL
    requests (padding excluded), their queue waits in seconds, and the
    zero columns added by the pow2 padding policy."""
    _REG.counter("serving.requests").inc(int(width))
    _REG.counter("serving.batches").inc()
    if padded:
        _REG.counter("serving.padded_cols").inc(int(padded))
    _REG.counter("serving.width").inc(label=int(width))
    h = _REG.histogram("serving.queue_wait_seconds")
    for w in waits:
        h.observe(float(w))


def record_requests_per_launch(width: int):
    """Accumulate one persistent_serve launch: ``width`` REAL request
    slots riding it (pow2 slot padding excluded) — the -log_view
    requests-per-launch row (serving/persistent.py)."""
    _REG.histogram("dispatch.requests_per_launch").observe(float(width))


def serving_stats() -> dict:
    """Process-wide coalescing stats: batch-width histogram + queue-wait
    aggregates (per-server percentiles live on SolveServer.stats() —
    same Histogram.summary code path)."""
    h = _REG.histogram("serving.queue_wait_seconds")
    s = h.summary((50, 99))
    requests = int(_REG.counter("serving.requests").total())
    batches = int(_REG.counter("serving.batches").total())
    return {"requests": requests, "batches": batches,
            "padded_cols": int(_REG.counter("serving.padded_cols").total()),
            "width_hist": {int(k): int(v) for k, v in
                           _REG.counter("serving.width").items().items()},
            "wait_sum_s": float(h.sum),
            "wait_max_s": s["max"],
            "mean_width": (requests / batches) if batches else 0.0,
            "wait_mean_s": s["mean"],
            "wait_p50_s": s["p50"],
            "wait_p99_s": s["p99"]}


# elastic degraded-mesh recoveries (resilience/elastic.py + retry.py
# mesh_shrink stage): one entry per executed shrink, printed as a
# -log_view row — losing hardware mid-run is exactly the event an
# operator reading the log needs to see
_MESH_SHRINKS: list[dict] = []


def record_mesh_shrink(old_devices: int, new_devices: int,
                       rebuild_seconds: float):
    """Record one executed degraded-mesh rebuild: the mesh went from
    ``old_devices`` to ``new_devices`` and re-placing operands / PC
    factors / programs took ``rebuild_seconds``."""
    entry = {"old_devices": int(old_devices),
             "new_devices": int(new_devices),
             "rebuild_s": float(rebuild_seconds)}
    _MESH_SHRINKS.append(entry)
    _REG.counter("elastic.mesh_shrinks").inc()
    if _spans.enabled():
        _flight.recorder.record_event("mesh_shrink", **entry)


def mesh_shrinks() -> list[dict]:
    return [dict(e) for e in _MESH_SHRINKS]


# the ladder's upward twin (resilience/elastic.py grown_comm + the
# serving re-grow adoption): one entry per executed re-grow — recovered
# capacity is as operator-relevant as lost capacity
_MESH_REGROWS: list[dict] = []


def record_mesh_regrow(old_devices: int, new_devices: int,
                       rebuild_seconds: float):
    """Record one executed mesh RE-GROW: healed hardware brought the
    mesh from ``old_devices`` back up to ``new_devices``; re-placing
    operands / PC factors / programs took ``rebuild_seconds``."""
    entry = {"old_devices": int(old_devices),
             "new_devices": int(new_devices),
             "rebuild_s": float(rebuild_seconds)}
    _MESH_REGROWS.append(entry)
    _REG.counter("elastic.mesh_regrows").inc()
    if _spans.enabled():
        _flight.recorder.record_event("mesh_regrow", **entry)


def mesh_regrows() -> list[dict]:
    return [dict(e) for e in _MESH_REGROWS]


def record_admission(rejected: int = 0, expired: int = 0, shed: int = 0):
    """Accumulate serving admission-control outcomes: submissions
    rejected by the queue bound, requests expired by their deadline,
    and bulk requests SHED (resolved with the typed overload error) to
    admit more urgent traffic (serving/qos.py)."""
    if rejected:
        _REG.counter("serving.rejected").inc(int(rejected))
    if expired:
        _REG.counter("serving.expired").inc(int(expired))
    if shed:
        _REG.counter("serving.shed").inc(int(shed))


def admission_counts() -> dict:
    return {"rejected": int(_REG.counter("serving.rejected").total()),
            "expired": int(_REG.counter("serving.expired").total()),
            "shed": int(_REG.counter("serving.shed").total())}


def record_qos(qos_class: str):
    """Count one admitted request by its QoS class (serving/qos.py —
    'default' for unlabeled submissions)."""
    _REG.counter("qos.requests").inc(label=str(qos_class or "default"))


def qos_counts() -> dict[str, int]:
    return {str(k): int(v) for k, v in
            _REG.counter("qos.requests").items().items()}


def record_migration(op: str, src: str, dst: str, seconds: float):
    """Record one fleet session migration (serving/fleet.py): operator
    ``op`` moved from replica ``src`` to ``dst`` in ``seconds``."""
    _REG.counter("fleet.migrations").inc()
    if _spans.enabled():
        _flight.recorder.record_event("fleet_migration", op=str(op),
                                      src=str(src), dst=str(dst),
                                      seconds=float(seconds))


def migration_count() -> int:
    return int(_REG.counter("fleet.migrations").total())


def record_sync(kind: str, count: int = 1):
    """Count a host<->device synchronization point (a blocking D2H fetch).

    Each such point costs a host round trip — often more than the device
    work between them — so the *count* is the latency-critical metric (SURVEY.md §3.5 applied to restarts):
    EPS restarts fetch the projected matrix once per cycle, KSP solves
    fetch the (iters, rnorm, reason) triple once per solve.
    """
    _REG.counter("sync.count").inc(int(count), label=str(kind))


def sync_counts() -> dict[str, int]:
    return {k: int(v) for k, v in
            _REG.counter("sync.count").items().items()}


def events() -> list[SolveEvent]:
    return list(_EVENTS)


def clear_events():
    """Reset the process-wide observability state (event logs AND the
    telemetry metrics registry — the single source of truth)."""
    _EVENTS.clear()
    _MESH_SHRINKS.clear()
    _MESH_REGROWS.clear()
    _REG.reset()


def log_view(file=None):
    """Print the accumulated solve log, -log_view style — rendered FROM
    the telemetry metrics registry (plus the two per-entry event logs),
    the same data ``telemetry.snapshot()`` and the Prometheus exporter
    serve."""
    file = file or sys.stderr
    syncs = sync_counts()
    sdc = sdc_counts()
    serving = serving_stats()
    admission = admission_counts()
    per_iter = _REG.histogram("solve.per_iter_seconds")
    if (not _EVENTS and not syncs
            and not any(sdc.values()) and not serving["batches"]
            and not _MESH_SHRINKS
            and not _MESH_REGROWS and not migration_count()
            and not any(admission.values())):
        print("log_view: no solve events recorded", file=file)
        return
    if _EVENTS:
        total = sum(e.wall for e in _EVENTS)
        print("-" * 72, file=file)
        print(f"{'event':32s} {'n':>10s} {'iters':>6s} {'wall (s)':>10s} "
              f"{'it/s':>8s}", file=file)
        print("-" * 72, file=file)
        for e in _EVENTS:
            its = e.iterations / e.wall if e.wall > 0 else 0.0
            print(f"{e.what:32s} {e.n:10d} {e.iterations:6d} "
                  f"{e.wall:10.4f} {its:8.1f}", file=file)
        print("-" * 72, file=file)
        print(f"{len(_EVENTS)} solve(s), total wall {total:.4f} s",
              file=file)
    if syncs:
        parts = ", ".join(f"{k}: {v}" for k, v in sorted(syncs.items()))
        print(f"host-device sync points: {parts}", file=file)
    if any(sdc.values()):
        print(f"silent-error detection: {sdc['abft_checks']} ABFT "
              f"check(s), {sdc['detections']} detection(s), "
              f"{sdc['replacements']} residual replacement(s)", file=file)
    if serving["batches"]:
        hist = ", ".join(f"k={k}: {v}"
                         for k, v in sorted(serving["width_hist"].items()))
        print(f"solve server: {serving['batches']} coalesced "
              f"dispatch(es), {serving['requests']} request(s), mean "
              f"width {serving['mean_width']:.1f} [{hist}], queue wait "
              f"mean {serving['wait_mean_s'] * 1e3:.1f} ms / max "
              f"{serving['wait_max_s'] * 1e3:.1f} ms, "
              f"{serving['padded_cols']} padded column(s)", file=file)
    if any(admission.values()):
        print(f"serving admission control: {admission['rejected']} "
              f"rejected (queue bound), {admission['expired']} "
              f"deadline-expired, {admission['shed']} shed (QoS)",
              file=file)
    qos = qos_counts()
    if qos:
        parts = ", ".join(f"{k}: {v}" for k, v in sorted(qos.items()))
        print(f"QoS classes served: {parts}", file=file)
    if _MESH_SHRINKS:
        shr = ", ".join(f"{e['old_devices']}->{e['new_devices']} "
                        f"({e['rebuild_s'] * 1e3:.0f} ms)"
                        for e in _MESH_SHRINKS)
        print(f"elastic recovery: {len(_MESH_SHRINKS)} mesh shrink(s) "
              f"[{shr}]", file=file)
    if _MESH_REGROWS:
        gr = ", ".join(f"{e['old_devices']}->{e['new_devices']} "
                       f"({e['rebuild_s'] * 1e3:.0f} ms)"
                       for e in _MESH_REGROWS)
        print(f"elastic recovery: {len(_MESH_REGROWS)} mesh re-grow(s) "
              f"[{gr}]", file=file)
    if migration_count():
        print(f"fleet: {migration_count()} session migration(s)",
              file=file)
    dispatches = dispatch_counts()
    if dispatches:
        # the megasolve measurement row: launches by program kind — a
        # fused solve contributes exactly one 'megasolve' launch where
        # the unfused refinement path pays one 'ksp' per outer step
        parts = ", ".join(f"{k}: {int(v)}"
                          for k, v in sorted(dispatches.items()))
        total_d = int(sum(dispatches.values()))
        print(f"compiled-program dispatches: {total_d} [{parts}]",
              file=file)
    rpl = _REG.histogram("dispatch.requests_per_launch")
    if rpl.count:
        # the persistent-serving amortization row: requests riding each
        # persistent_serve launch — mean > 1 is the measured
        # ≪1-dispatch-per-request claim (serving/persistent.py)
        s = rpl.summary((50, 99))
        occupied = [(b, c) for b, c in
                    zip(list(rpl.buckets) + [float("inf")],
                        rpl.bucket_counts()) if c]
        cells = "  ".join(
            (f">{rpl.buckets[-1]:g}: {c}" if b == float("inf")
             else f"<={b:g}: {c}") for b, c in occupied)
        print(f"persistent requests-per-launch histogram ({rpl.count} "
              f"launch(es), mean {s['mean']:.2f}, p50 {s['p50']:.1f}, "
              f"p99 {s['p99']:.1f}): {cells}", file=file)
    if per_iter.count:
        # the fixed-bucket per-iteration latency histogram: only
        # occupied buckets, cumulative-free
        s = per_iter.summary((50, 99))
        occupied = [(b, c) for b, c in
                    zip(list(per_iter.buckets) + [float("inf")],
                        per_iter.bucket_counts()) if c]
        cells = "  ".join(
            (f">{per_iter.buckets[-1]:g}s: {c}" if b == float("inf")
             else f"<={b:g}s: {c}") for b, c in occupied)
        print(f"per-iteration latency histogram ({per_iter.count} "
              f"solve(s), p50 {s['p50'] * 1e6:.1f} us, p99 "
              f"{s['p99'] * 1e6:.1f} us): {cells}", file=file)
    stale = _REG.histogram("multisplit.stale_age")
    if stale.count:
        # the async-tier staleness row: the age (versions behind the
        # reader) of every exchange read the multisplit block workers
        # consumed, plus the bound enforcement counters — the tier's
        # degradation budget made visible
        s = stale.summary((50, 99))
        occupied = [(b, c) for b, c in
                    zip(list(stale.buckets) + [float("inf")],
                        stale.bucket_counts()) if c]
        cells = "  ".join(
            (f">{stale.buckets[-1]:g}: {c}" if b == float("inf")
             else f"<={b:g}: {c}") for b, c in occupied)
        resyncs = int(_REG.counter("multisplit.resyncs").total())
        lost = int(_REG.counter("multisplit.block_lost").total())
        steps = int(_REG.counter("multisplit.step").total())
        print(f"multisplit staleness histogram ({stale.count} read(s), "
              f"{steps} step(s), p50 age {s['p50']:.1f}, p99 "
              f"{s['p99']:.1f}, {resyncs} resync(s), {lost} block(s) "
              f"lost): {cells}", file=file)
    print(f"compiled programs held: {program_count()}", file=file)


def dispatch_counts() -> dict[str, float]:
    """Compiled-program launches by program kind (ksp / ksp_many /
    megasolve / megasolve_many) — the ``dispatch.programs`` registry
    counter the per-root-span ``dispatches`` attribute mirrors."""
    return {str(k): v for k, v in
            _REG.counter("dispatch.programs").items().items()}


def program_count() -> int:
    """Total jit-compiled solver programs cached this process (KSP + EPS
    + fused megasolve) — each costs one trace + compile-cache load per
    fresh process, the dominant fixed cost of short driver runs."""
    n = 0
    try:
        from ..solvers.krylov import _PROGRAM_CACHE as kc
        n += len(kc)
    except (ImportError, AttributeError):   # introspection only
        pass
    try:
        from ..solvers.eps import _PROGRAM_CACHE as ec
        n += len(ec)
    except (ImportError, AttributeError):
        pass
    try:
        from ..solvers.megasolve import (_MEGASOLVE_CACHE as mc,
                                         _MEGASOLVE_CACHE_MANY as mcm,
                                         _PERSISTENT_CACHE as mcp)
        n += len(mc) + len(mcm) + len(mcp)
    except (ImportError, AttributeError):
        pass
    return n

