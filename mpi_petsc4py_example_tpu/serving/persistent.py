"""Persistent serving programs: the device-resident request queue.

Megasolve (PR 12) made a served block cost exactly ONE dispatch; this
module kills the remaining *per-batch* launch cost. A persistent
session owns a long-lived multi-request device program — the
``persistent_serve`` AOT kind (solvers/megasolve.py,
``build_megasolve_program_many(..., persistent=True)``): one dispatched
``lax.while_loop`` draining up to Q request SLOTS per launch, each slot
a full megasolve (fp64 refinement outer + nested CG-family inner plan,
verified-residual exit gate) with PER-SLOT masked independence — a
hard request refining in slot 3 never stalls the easy request that
froze in slot 0 at its own verified tolerance. Slots are independent
enough to carry *heterogeneous tolerances*: the program takes
``(Q,)``-shaped per-slot rtol/atol operands, so requests from
DIFFERENT coalescer compatibility groups ride one launch — the thing a
per-batch dispatch structurally cannot do.

The host side is a double buffer. While launch N executes on device,
the dispatcher keeps coalescing: every batch it routes here is STAGED
into launch N+1's operand slots (host-side; zero device traffic) and
the dispatcher returns to its queue immediately. Launch N+1 is
enqueued on the device stream *before* the host blocks fetching launch
N's results (JAX async dispatch), so the device never idles between
launches, and a burst of B batches costs ``ceil(B_requests / Q)``
launches — the amortized ≪ 1 dispatch/request the
``dispatch.programs`` counter shows.
Slot-count padding reuses the coalescer's pow2 discipline (a zero slot
carries zero tolerances: its residual norm is 0, its target 0, it
freezes at outer step 0). QoS ordering is preserved: slots fill in the
dispatcher's deadline-weighted batch order, FIFO.

Resolution points — every staged future resolves, never hangs:

- a staged backlog reaching Q slots turns the buffer over inline
  (resolve launch N, open launch N+1) — bounded memory AND latency
  under sustained load;
- the dispatcher's idle pass flushes every outstanding launch the
  moment the queue goes quiet (server._loop);
- ``drain``/``shutdown`` count staged + in-flight slots via
  ``SolveServer._persistent_unresolved`` and the dispatcher flushes
  before stopping.

Resilience: a fault inside the persistent loop must resolve EVERY
slot's future. When a fault plan is armed (or the mesh registry holds
a lost device), staging routes the whole launch through the per-batch
resilient path (``resilient_solve_many`` + the session's fused
megasolve) instead of the direct program call: the ``ksp.program``
boundary fires the fault, the retry tier rolls back to the per-slot
verified carries and re-enters past iteration 0, and an elastic shrink
is adopted server-wide — after which the next launch simply rebuilds
the persistent program on the surviving mesh (the program cache is
keyed on ``comm.mesh``; ``stats["rebuilds"]`` counts the reloads). A
launch that fails at resolve time takes the same fallback; a fallback
that itself fails resolves every slot future with the typed error —
exactly the dispatcher's never-hang contract.

PETSc has no analog: one ``KSPSolve`` per call is its serving model.
A resident multi-request program is a deliberate TPU-native divergence
(PARITY.md, round 18).
"""

from __future__ import annotations

import time

import numpy as np

from ..resilience import faults as _faults
from ..resilience.retry import resilient_solve_many
from ..telemetry import spans as _telemetry
from ..utils.convergence import ConvergedReason
from ..utils.profiling import record_requests_per_launch, record_sync
from .coalescer import padded_width

__all__ = ["PersistentRunner"]


class _Launch:
    """One in-flight persistent launch: the staged slot metadata plus
    the device output handles (or the fallback marker)."""

    __slots__ = ("reqs", "waits", "k", "kpad", "t0", "out", "fallback",
                 "span", "n")

    def __init__(self, reqs, waits, k, kpad, n):
        self.reqs = reqs
        self.waits = waits
        self.k = k
        self.kpad = kpad
        self.n = n
        self.t0 = time.monotonic()
        self.out = None          # device output tuple (direct path)
        self.fallback = False    # route through resilient_solve_many
        self.span = None


class PersistentRunner:
    """The per-session host half of persistent serving (module doc).

    All mutating entry points (``enqueue``/``flush``/``quiesce``) run
    under the server's ``_session_lock`` — the dispatcher thread for
    enqueue and the idle flush, any thread for the rebuild paths —
    so the staged list and the in-flight record need no lock of their
    own. The established lock order (``_session_lock`` before ``_cv``)
    is preserved: resolution notifies the server condvar LAST.
    """

    def __init__(self, server, sess, capacity: int | None = None):
        self._server = server
        self._sess = sess
        self.capacity = int(capacity or server.max_k)
        self._staged: list = []        # [(SolveRequest, wait_s), ...]
        self._rec: _Launch | None = None
        # live-request counter for drain/idle accounting: incremented
        # on enqueue, decremented only AFTER a slot's futures resolve —
        # deriving the count from _staged/_rec instead would read a
        # transient 0 while _launch holds slots in neither (program
        # build/compile), letting a concurrent drain exit early
        self._live = 0
        self._mesh = server.comm.mesh  # last launch's mesh (rebuild det.)
        # the ABFT-guard demotion warns ONCE per session registration —
        # under sustained traffic a per-dispatch warning is pure noise
        # (stats["fallbacks"] counts every demoted launch regardless)
        self._guard_warned = False
        self.stats = {"launches": 0, "requests": 0, "padded_slots": 0,
                      "fallbacks": 0, "rebuilds": 0, "turnovers": 0}

    # ---- dispatcher entry points -------------------------------------------
    def enqueue(self, reqs, waits):
        """Stage one coalesced batch's slots into the next launch.

        Returns immediately in the steady state — the launch is opened
        asynchronously when the buffer is free, and only a backlog at
        slot capacity forces an inline turnover (resolve the previous
        launch, open the next)."""
        self._live += len(reqs)
        self._staged.extend(zip(reqs, waits))
        if self._rec is None:
            self._launch()
            return
        # tpslint: disable=TPS015 — backlog turnover: each trip drains
        # a FULL launch (Q slots) and runs only while staged >= Q, so
        # dispatches stay at ceil(backlog/Q); the amortization this
        # rule asks for is what the loop body already does
        while self._rec is not None and len(self._staged) >= self.capacity:
            self.stats["turnovers"] += 1
            self._turn()

    def flush(self):
        """Resolve every outstanding launch and drain the staged
        backlog — the dispatcher's idle pass and the drain/shutdown
        path. Each turn opens the NEXT launch before blocking on the
        previous one (double buffer), so a deep backlog still overlaps
        host demux with device execution."""
        # tpslint: disable=TPS015 — this loop IS the amortizer: each
        # _turn dispatches one persistent_serve program that drains up
        # to Q staged requests, so trips scale with backlog/Q, not
        # with requests; there is no fused form above it to reach for
        while self._rec is not None or self._staged:
            self._turn()

    def quiesce(self):
        """Resolve the in-flight launch WITHOUT opening the next one —
        the mesh-rebuild hook (shrink adoption / re-grow): outstanding
        device buffers on the old mesh are consumed, while staged
        host-side slots stay staged and simply launch on the rebuilt
        mesh later. Reentrancy-safe: inside our own fallback's shrink
        adoption the in-flight record is already detached, so this is
        a no-op."""
        rec, self._rec = self._rec, None
        if rec is not None:
            self._resolve(rec)

    @property
    def unresolved(self) -> int:
        """Requests whose futures this runner still owes — staged,
        mid-launch, or riding the in-flight program. Read without the
        session lock: the counter only drops AFTER futures resolve, so
        a stale read errs on the side of one extra condvar lap, never
        an early drain exit."""
        return self._live

    # ---- launch / resolve ---------------------------------------------------
    def _turn(self):
        rec, self._rec = self._rec, None
        if self._staged:
            self._launch()           # enqueue N+1 before blocking on N
        if rec is not None:
            self._resolve(rec)

    def _launch(self):
        """Open a launch over the first ≤ capacity staged slots."""
        take = self._staged[: self.capacity]
        del self._staged[: len(take)]
        reqs = [r for r, _w in take]
        waits = [w for _r, w in take]
        k = len(reqs)
        kpad = padded_width(k, self.capacity, self._server.pad_pow2)
        sess = self._sess
        rec = _Launch(reqs, waits, k, kpad, sess.n)
        rec.span = _telemetry.start_span(
            "serving.persistent_launch", op=sess.name, width=k,
            padded=kpad - k)
        record_requests_per_launch(k)
        self.stats["launches"] += 1
        self.stats["requests"] += k
        self.stats["padded_slots"] += kpad - k
        # a fault plan armed (or a lost device still inside THIS
        # session's mesh) routes the launch through the resilient
        # per-batch path at resolve time: the ksp.program boundary must
        # FIRE the fault so the retry tier can roll back and re-enter —
        # the direct program call below would sail past host-level
        # fault points. A lost device the mesh already shrank around
        # does not force the fallback: the registry stays populated
        # until heal, but the surviving mesh is healthy.
        mesh_devs = set(sess.ksp.get_operators()[0].comm.device_ids)
        # a silent-corruption guard acquired AFTER registration
        # (ksp.abft / residual replacement toggled on the live session,
        # e.g. by a runtime -ksp_* flag) disqualifies the persistent
        # program — it carries no in-program detectors. Demote to the
        # resilient per-batch path, warning once per registration
        guard = (bool(sess.ksp.abft)
                 or int(sess.ksp.residual_replacement) > 0)
        if guard and not self._guard_warned:
            self._guard_warned = True
            import warnings
            warnings.warn(
                f"persistent session {sess.name!r}: the ABFT/"
                "residual-replacement guard was enabled after "
                "registration — launches fall back to per-batch "
                "dispatch (counted in stats['fallbacks']; this warns "
                "once per registration)", stacklevel=2)
        if (guard or _faults.active()
                or (set(_faults.lost_devices()) & mesh_devs)):
            rec.fallback = True
            self._rec = rec
            return
        try:
            rec.out = self._launch_device(rec)
        # tpslint: disable=TPS005 — a failed launch becomes the
        # fallback's problem (and ultimately the slot futures'), never
        # the dispatcher thread's
        except Exception:  # noqa: BLE001
            rec.fallback = True
        self._rec = rec

    def _launch_device(self, rec):
        """Stage operands and dispatch the persistent program — the
        per-slot-tolerance twin of KSP._solve_many_megasolve. Returns
        the device output handles WITHOUT blocking (JAX async
        dispatch): the host only blocks in _resolve."""
        import jax
        import jax.numpy as jnp

        from ..solvers.krylov import donation_supported
        from ..solvers.megasolve import (GATE_REFINE_MAX,
                                         build_megasolve_program_many,
                                         megasolve_stencil_supported)
        from ..utils.dtypes import tolerance_dtype
        sess = self._sess
        ksp = sess.ksp
        mat = ksp.get_operators()[0]
        pc = ksp.get_pc()
        comm = mat.comm
        if self._mesh is not None and self._mesh is not comm.mesh:
            # the session was rebuilt (shrink adoption / re-grow) since
            # the last launch: the program cache key carries comm.mesh,
            # so this launch transparently compiles/loads the
            # persistent program for the new geometry
            self.stats["rebuilds"] += 1
        self._mesh = comm.mesh
        op_dt = np.dtype(mat.dtype)
        sf = (ksp.megasolve_stencil_fastpath
              and megasolve_stencil_supported(ksp.get_type(), pc, mat,
                                              nrhs=rec.kpad))
        prog = build_megasolve_program_many(
            comm, ksp.get_type(), pc, mat, None, nrhs=rec.kpad,
            zero_guess=True, donate=True, sstep_s=ksp.sstep_s,
            stencil_fastpath=sf, persistent=True)
        B = np.zeros((sess.n, rec.kpad), dtype=op_dt)
        dt = tolerance_dtype(op_dt)
        rt = np.zeros(rec.kpad, dt)
        at = np.zeros(rec.kpad, dt)
        for j, r in enumerate(rec.reqs):
            B[:, j] = r.b
            rt[j] = r.rtol
            at[j] = r.atol
        # padding slots keep rtol = atol = 0 with a zero RHS: residual
        # norm 0, target 0 — frozen at outer step 0 by the mask
        maxit = max((r.max_it for r in rec.reqs), default=1)
        Bd, Xd0 = comm.put_rows_many([B, np.zeros_like(B)])
        if donation_supported():
            Xd0 = jnp.array(Xd0)      # op output, donation-safe
        _telemetry.record_program_dispatch("persistent_serve")
        return prog(mat.device_arrays(), pc.device_arrays(), Bd, Xd0,
                    rt, at, rt.copy(), dt.type(ksp.divtol),
                    np.int32(maxit), np.int32(GATE_REFINE_MAX),
                    np.int32(ConvergedReason.DIVERGED_MAX_IT))

    def _resolve(self, rec):
        """Block on a launch's device results and resolve every slot
        future; any failure demotes to the resilient fallback. Never
        raises — the dispatcher (and drain) depend on it."""
        try:
            if not rec.fallback:
                try:
                    self._resolve_device(rec)
                    return
                # tpslint: disable=TPS005 — a resolve-time failure
                # (device loss surfacing at fetch, donation misuse,
                # anything) must reach the slot futures through the
                # recovery path below, not kill the dispatcher
                except Exception:  # noqa: BLE001
                    rec.fallback = True
            self._resolve_fallback(rec)
        finally:
            # every slot future is resolved by now (result, recovered
            # result, or typed exception): release the drain count,
            # THEN wake the waiters
            self._live -= rec.k
            self._notify()

    def _resolve_device(self, rec):
        import jax

        from .server import ServedSolveResult, SolveServer
        Xd, steps, ii, rn, rs = rec.out[:5]
        fetch = jax.device_get((Xd, ii, rn, rs))
        record_sync("persistent launch resolve")
        wall = time.monotonic() - rec.t0
        X = np.asarray(fetch[0])[: rec.n]
        iters = np.asarray(fetch[1])
        rnorms = np.asarray(fetch[2])
        reasons = np.asarray(fetch[3]).astype(np.int64).copy()
        bad = ~np.isfinite(rnorms)
        reasons[bad] = ConvergedReason.DIVERGED_NANORINF
        for j, r in enumerate(rec.reqs):
            out = ServedSolveResult(
                iterations=int(iters[j]),
                residual_norm=float(rnorms[j]),
                reason=int(reasons[j]), wall_time=wall, history=[],
                x=np.array(X[:, j]), op=r.op, batch_width=rec.k,
                queue_wait=rec.waits[j])
            r.future.set_result(out)
            SolveServer._end_request_span(
                r, "ok", batch=rec.span, iterations=int(iters[j]),
                queue_wait=rec.waits[j])
        rec.span.set_attrs(outcome="ok", width=rec.k).end()

    def _resolve_fallback(self, rec):
        """The recovery path: one resilient per-batch megasolve over
        the launch's slots. Heterogeneous slot tolerances collapse to
        the strictest (min rtol/atol, max max_it) — every slot is
        solved at least as accurately as it asked. A persistent device
        loss shrinks the mesh through the elastic tier and the server
        adopts it; the NEXT launch rebuilds the persistent program on
        the surviving geometry."""
        from .server import ServedSolveResult, SolveServer
        self.stats["fallbacks"] += 1
        sess = self._sess
        ksp = sess.ksp
        reqs = rec.reqs
        t0 = time.monotonic()
        try:
            ksp.set_tolerances(
                rtol=min(r.rtol for r in reqs),
                atol=min(r.atol for r in reqs),
                max_it=max(r.max_it for r in reqs))
            B = np.zeros((sess.n, rec.kpad), dtype=sess.dtype)
            for j, r in enumerate(reqs):
                B[:, j] = r.b
            res = resilient_solve_many(
                ksp, B, policy=self._server.retry_policy)
        # tpslint: disable=TPS005 — exhausted retries / non-retriable
        # errors resolve every slot future typed; the dispatcher must
        # survive
        except Exception as exc:  # noqa: BLE001
            rec.span.set_attr("error", type(exc).__name__)
            rec.span.set_attrs(outcome="error").end()
            for r in reqs:
                r.future.set_exception(exc)
                SolveServer._end_request_span(r, "error", batch=rec.span)
            return
        shrinks = [e for e in res.recovery_events
                   if e.kind == "mesh_shrink"]
        if shrinks:
            self._server._adopt_shrunk_mesh(sess, shrinks,
                                            time.monotonic() - t0)
        per = res.per_rhs()
        for j, r in enumerate(reqs):
            col = per[j]
            out = ServedSolveResult(
                iterations=col.iterations,
                residual_norm=col.residual_norm,
                reason=col.reason, wall_time=res.wall_time,
                history=col.history, attempts=res.attempts,
                recovery_events=list(res.recovery_events),
                abft_checks=res.abft_checks,
                sdc_detections=res.sdc_detections,
                residual_replacements=res.residual_replacements,
                x=np.array(res.X[:, j]), op=r.op, batch_width=rec.k,
                queue_wait=rec.waits[j])
            r.future.set_result(out)
            SolveServer._end_request_span(
                r, "ok", batch=rec.span, iterations=col.iterations,
                queue_wait=rec.waits[j])
        rec.span.set_attrs(outcome="recovered",
                           attempts=res.attempts).end()

    def _notify(self):
        # lock order: we already hold _session_lock (all entry points
        # do); _cv nests inside it
        with self._server._cv:
            self._server._cv.notify_all()
