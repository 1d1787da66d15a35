"""SolveServer — a persistent, device-resident solve session.

Where an end-to-end solve spends most of its wall in per-request
dispatch/launch latency, the serving answer is to stop paying that
latency per request: a long-lived :class:`SolveServer`
session registers each operator ONCE — CSR/ELL/DIA operands, PC
factors, and the AOT-cached compiled programs stay resident in device
HBM — and a concurrent stream of solve requests is COALESCED into
``(n, k)`` blocks dispatched through the PR-4 block-CG kernels
(``KSP.solve_many``: collective count per iteration independent of k),
with donated iterate buffers on the hot path (krylov ``donate=True``:
zero extra device allocations per launch). This is the PETSc
reuse-the-KSP-object idiom (PARITY.md "Serving sessions") made
concurrent: JAXMg and JAX-AMG (PAPERS.md) both keep solver state
device-resident between solves for exactly this reason.

Client APIs:

* :meth:`SolveServer.submit` — async: returns a
  ``concurrent.futures.Future`` resolving to a
  :class:`ServedSolveResult` (per-request iterations/residual/reason +
  the solution vector).
* :meth:`SolveServer.solve` — sync: submit + wait.

Requests are grouped by the coalescer (serving/coalescer.py): same
operator + same tolerances may share a block; a batching window
(``-solve_server_window``) holds the first request briefly so
concurrent arrivals ride the same launch; ``-solve_server_max_k`` caps
the block width and ``-solve_server_pad_pow2`` rounds widths up to
powers of two so a server compiles at most log2(max_k)+1 block
programs per operator configuration.

Resilience rides along PER REQUEST: with ``-solve_server_resilient``
(default on) every dispatched block runs under
:func:`resilience.retry.resilient_solve_many` — a worker crash
checkpoints the partial iterate block, backs off
(:meth:`RetryPolicy.serving`'s short deterministic delays), rebuilds,
and resumes; a detected silent corruption rolls the block back to the
verified iterates and re-enters immediately, and the PR-5 per-column
detection means one poisoned request cannot contaminate its
batch-mates' verified answers (the independent final re-verification
covers every column).

PERSISTENT device loss rides the elastic escalation
(resilience/elastic.py): when a dispatch's recovery trail reports a
``mesh_shrink`` — the resilient wrapper already resharded the failing
session and replayed its in-flight batch-mates from the checkpointed
iterate block — the server ADOPTS the degraded mesh: every other
resident operator is rebuilt on it and re-warmed at the block widths
traffic has used, so the session survives losing hardware instead of
dying with it. Degraded capacity also demands admission control, so
the server carries two hardening knobs: ``-solve_server_max_queue``
bounds the pending queue (excess submissions are REJECTED with a typed
:class:`~..utils.errors.ServerOverloadedError` instead of queueing
unboundedly) and ``-solve_server_deadline`` gives each request a
server-side dispatch deadline (expired requests resolve with
:class:`~..utils.errors.DeadlineExceededError` rather than occupying a
batch column). Every pending future always resolves — a result, a
typed rejection, or the dispatch error — never a hang.

The fleet round adds the QoS tier (serving/qos.py) on top: requests
carry priority + deadline CLASSES (``submit(qos="interactive")``), the
dispatcher runs a deadline-weighted scheduling pass per window and
dispatches ONE batch at a time — a p99-sensitive arrival preempts
queued bulk batches into the next pass, never an in-flight block — and
under overload the admission tier sheds the least-urgent pending bulk
request (typed resolution) before rejecting interactive arrivals. The
PR-8 shrink adoption also gained its inverse: when
:func:`resilience.faults.heal` restores devices, the dispatcher adopts
the largest viable LARGER mesh (``-elastic_regrow``), rebuilding every
resident session on it — lost capacity comes back without restarting
the server. Multi-replica deployments front N of these servers with
:class:`~.fleet.SolveRouter`.
"""

from __future__ import annotations

import threading
import time
from concurrent.futures import Future
from dataclasses import dataclass, field

import numpy as np

from ..core.mat import Mat
from ..parallel.mesh import as_comm
from ..resilience import faults as _faults
from ..resilience.retry import RetryPolicy, resilient_solve_many
from ..solvers.ksp import KSP
from ..telemetry import flight as _flight
from ..telemetry import metrics as _metrics
from ..telemetry import spans as _telemetry
from ..utils.convergence import SolveResult
from ..utils.errors import DeadlineExceededError, ServerOverloadedError
from ..utils.options import global_options
from ..utils.profiling import (record_admission, record_qos,
                               record_serving)
from . import qos as _qos
from .coalescer import SolveRequest, padded_width


class ServerClosedError(RuntimeError):
    """Submission to a server that has been shut down."""


@dataclass
class ServedSolveResult(SolveResult):
    """A per-request :class:`SolveResult` as demultiplexed from the
    coalesced block it rode in.

    ``x`` is the request's solution vector (host copy of its block
    column); ``batch_width`` the number of REAL requests coalesced into
    the dispatched block (padding columns excluded); ``queue_wait`` the
    seconds the request waited between submission and dispatch (the
    batching-window + backlog cost the latency percentiles in
    benchmarks/run_all.py cfg9 report). ``wall_time`` is the whole
    block's wall — launches are shared, so per-request wall is not a
    meaningful quantity. The resilience trail (``attempts`` /
    ``recovery_events`` / SDC counters) is the BLOCK's: recovery acts on
    the dispatched block as a unit.
    """
    x: object = None
    op: str = ""
    batch_width: int = 1
    queue_wait: float = 0.0


class _OperatorSession:
    """One registered operator: device-resident operands + a dedicated
    KSP whose PC factors and compiled programs persist across requests.

    The registered tolerance DEFAULTS are stored here, not read back
    from the KSP: dispatches set the session KSP's tolerances to each
    batch's (possibly overridden) values, so the KSP object's own
    rtol/atol/max_it drift with traffic while these stay the contract
    ``register_operator`` documented."""

    __slots__ = ("name", "operator", "ksp", "dtype", "n",
                 "rtol", "atol", "max_it", "multisplit", "persistent")

    def __init__(self, name, operator, ksp, multisplit=None,
                 persistent=None):
        self.name = name
        self.operator = operator
        self.ksp = ksp
        self.dtype = np.dtype(operator.dtype)
        self.n = int(operator.shape[0])
        self.rtol = float(ksp.rtol)
        self.atol = float(ksp.atol)
        self.max_it = int(ksp.max_it)
        self.multisplit = multisplit   # async-tier solver, or None
        self.persistent = persistent   # PersistentRunner, or None

    @property
    def schedule(self) -> str:
        """The session's reduction-plan schedule ("cg" / "pipecg" /
        "sstep:<s>" / "multisplit") — part of every request's
        compatibility key (serving/coalescer.py): the schedule is
        compiled into the block program, so blocks never mix schedules.
        "multisplit" is the ASYNC schedule class: jittery-mesh sessions
        route to the stale-tolerant tier (solvers/multisplit.py) and
        never coalesce with synchronous-plan sessions."""
        if self.multisplit is not None:
            return "multisplit"
        tp = self.ksp.get_type()
        return f"{tp}:{int(self.ksp.sstep_s)}" if tp == "sstep" else tp


class SolveServer:
    """Long-lived solve session with request coalescing (module doc).

    Parameters (each overridable at construction time by the options DB
    — PETSc precedence: runtime flags beat programmatic defaults):

    window
        Batching window in seconds (``-solve_server_window``): the
        dispatcher holds the OLDEST pending request this long so
        concurrent arrivals coalesce into its block. 0 dispatches
        every snapshot of the queue immediately.
    max_k
        Maximum coalesced block width (``-solve_server_max_k``).
    pad_pow2
        Round block widths up to powers of two with zero columns
        (``-solve_server_pad_pow2``) — bounds the compiled-program
        population; a zero column freezes at iteration 0 under the
        masked block-CG kernel.
    resilient
        Dispatch through ``resilient_solve_many``
        (``-solve_server_resilient``).
    retry_policy
        The :class:`RetryPolicy` for resilient dispatches; default
        :meth:`RetryPolicy.serving` (short deterministic backoff —
        clients are waiting). ``-solve_server_retry_delay`` overrides
        its base delay.
    max_queue
        Admission control (``-solve_server_max_queue``): pending-queue
        bound above which :meth:`submit` raises
        :class:`ServerOverloadedError` instead of enqueueing. 0 (the
        default) queues unboundedly.
    deadline
        Default server-side dispatch deadline in seconds per request
        (``-solve_server_deadline``); a request still queued past it
        resolves with :class:`DeadlineExceededError`. 0 disables;
        :meth:`submit` takes a per-request override.
    autostart
        Start the dispatcher thread immediately. ``False`` lets tests
        (and batch drivers) enqueue a known request population and then
        :meth:`start` — every pending request is then coalesced in one
        deterministic window.
    """

    def __init__(self, comm=None, *, window: float = 0.002,
                 max_k: int = 32, pad_pow2: bool = True,
                 resilient: bool = True,
                 retry_policy: RetryPolicy | None = None,
                 max_queue: int = 0, deadline: float = 0.0,
                 autostart: bool = True):
        self.comm = as_comm(comm)
        # the mesh this server was PROVISIONED on: the re-grow ceiling
        # (shrink adoption moves self.comm down the ladder; a heal may
        # move it back up, never past this)
        self._full_comm = self.comm
        self._heal_epoch_seen = _faults.heal_epoch()
        self.window = float(window)
        self.max_k = int(max_k)
        self.pad_pow2 = bool(pad_pow2)
        self.resilient = bool(resilient)
        self.retry_policy = retry_policy or RetryPolicy.serving()
        self.max_queue = int(max_queue)
        self.deadline = float(deadline)
        self.qos_classes = _qos.builtin_classes()
        self._sessions: dict[str, _OperatorSession] = {}
        self._pending: list[SolveRequest] = []
        # batches left over from the last scheduling pass, valid while
        # _pending is untouched by submit/shed: draining an N-request
        # backlog then costs ONE schedule, not one per dispatched batch
        self._sched_cache: list | None = None
        self._inflight = 0
        self._stop = False
        self._closed = False
        self._cv = threading.Condition()
        # serializes SESSION MUTATION (regrow/adopt rebuilds, operator
        # un/registration) against in-flight dispatches: the dispatcher
        # holds it across _dispatch, so a public regrow()/unregister
        # from another thread waits for the current block instead of
        # swapping operators under it (RLock: the dispatcher's own
        # shrink-adoption path re-enters)
        self._session_lock = threading.RLock()
        self._thread: threading.Thread | None = None
        self._dispatch_hook = None       # test seam: called per batch
        self._stats = {"requests": 0, "batches": 0, "padded_cols": 0,
                       "width_hist": {}, "qos_hist": {},
                       "rejected": 0, "expired": 0, "shed": 0,
                       "mesh_shrinks": [], "mesh_regrows": []}
        # per-server queue-wait histogram: the SAME Histogram type (and
        # .summary percentile code path) the process-wide registry twin
        # uses — SolveServer.stats() and profiling.serving_stats() can
        # no longer drift in how they compute p50/p99
        self._wait_hist = _metrics.Histogram(
            "serving.queue_wait_seconds",
            _metrics.QUEUE_WAIT_BUCKETS_S)
        self.set_from_options()
        if autostart:
            self.start()

    # ---- configuration ------------------------------------------------------
    def set_from_options(self):
        """Apply ``-solve_server_*`` runtime flags (utils/options)."""
        opt = global_options()
        self.window = opt.get_real("solve_server_window", self.window)
        self.max_k = opt.get_int("solve_server_max_k", self.max_k)
        self.pad_pow2 = opt.get_bool("solve_server_pad_pow2",
                                     self.pad_pow2)
        self.resilient = opt.get_bool("solve_server_resilient",
                                      self.resilient)
        self.max_queue = opt.get_int("solve_server_max_queue",
                                     self.max_queue)
        self.deadline = opt.get_real("solve_server_deadline",
                                     self.deadline)
        delay = opt.get_real("solve_server_retry_delay", None)
        if delay is not None:
            # REPLACE, never mutate: the caller may share one
            # RetryPolicy object with non-serving resilient solves
            import dataclasses
            self.retry_policy = dataclasses.replace(
                self.retry_policy, base_delay=float(delay))
        return self

    setFromOptions = set_from_options

    # ---- operator registry --------------------------------------------------
    def register_operator(self, name: str, A, *, ksp_type: str = "cg",
                          pc_type: str = "jacobi", dtype=None,
                          rtol: float = 1e-5, atol: float = 0.0,
                          max_it: int = 10000, abft: bool = False,
                          residual_replacement: int = 0,
                          megasolve: bool = False,
                          multisplit: bool = False,
                          persistent: bool = False,
                          warm_widths=()):
        """Register operator ``name`` and make its solve state resident.

        ``A`` is a framework operator (Mat / matrix-free stencil) or
        anything ``Mat.from_scipy`` accepts (scipy sparse, dense
        ndarray). Registration builds the session KSP, places the
        operands, and sets up the PC ONCE — every later request reuses
        the resident factors and cached programs. ``rtol/atol/max_it``
        are the session DEFAULTS a request may override per submit
        (different tolerances then coalesce separately).

        ``warm_widths`` pre-compiles (and AOT-caches) the block
        programs for the given widths by dispatching zero-RHS blocks —
        they converge at iteration 0 — so the first real request at
        that width pays no compile.

        ``abft`` / ``residual_replacement`` arm the PR-5
        silent-corruption guard on the session: an in-program detection
        rolls the whole block back to the verified iterates and the
        resilient dispatch re-enters immediately — one poisoned request
        cannot contaminate its batch-mates (per-column detection +
        independent final re-verification). ``megasolve`` routes the
        session's coalesced dispatches through the FUSED whole-solve
        program (solvers/megasolve.py): a served block — refinement
        recurrence, true-residual verification and all — costs exactly
        ONE compiled-program launch, the measurement the
        ``serving.dispatch`` span's ``dispatches`` attribute reports.
        The session KSP also applies the options DB (``-ksp_*`` flags —
        abft, residual replacement, true-residual gating, megasolve —
        override these defaults at runtime, the PETSc precedence).

        ``persistent`` (or ``-solve_server_persistent``) registers the
        session in PERSISTENT serving mode (serving/persistent.py):
        dispatched batches stage into a double-buffered device-resident
        multi-request program — one ``persistent_serve`` launch drains
        up to ``max_k`` request slots, each a full megasolve with
        per-slot masked independence and per-slot tolerances — so
        sustained traffic pays amortized ≪ 1 program dispatch per
        request. Requires a megasolve-eligible configuration without
        the ABFT guard (ineligible sessions warn and fall back to
        per-batch dispatch); implies ``megasolve`` for the resilient
        fallback path.

        ``multisplit`` routes the session to the ASYNCHRONOUS tier
        (solvers/multisplit.py): requests dispatch per-column through
        the stale-tolerant outer iteration instead of a coalesced
        synchronous block — the schedule class for jittery or degrading
        meshes, where any synchronous plan pays max-of-device latency
        per reduction. QoS-``interactive`` batches ride FRESHER
        exchanges: their staleness bound tightens to
        ``-multisplit_urgent_stale`` (default: half the session bound).
        ``ksp_type``/``pc_type`` then configure the per-block INNER
        solves (with ``-multisplit_inner_*`` flags taking precedence).
        """
        if name in self._sessions:
            raise ValueError(f"operator {name!r} already registered")
        op = A
        if not hasattr(op, "device_arrays"):
            import scipy.sparse as sp
            op = Mat.from_scipy(self.comm, sp.csr_matrix(A), dtype=dtype)
        ksp = KSP().create(self.comm)
        ksp.set_operators(op)
        ksp.set_type(ksp_type)
        ksp.get_pc().set_type(pc_type)
        ksp.set_tolerances(rtol=rtol, atol=atol, max_it=max_it)
        ksp.abft = bool(abft)
        ksp.residual_replacement = int(residual_replacement)
        ksp.megasolve = bool(megasolve)
        ksp.set_from_options()
        # the options DB keeps PETSc precedence, but a global -ksp_type/
        # -pc_type aimed at some OTHER solver in the process can silently
        # turn this session's coalesced block dispatch into per-column
        # sequential solves (KSP.solve_many's fallback routing) — results
        # stay correct, the serving throughput win evaporates. Say so.
        from ..solvers.krylov import batched_pc_supported
        if (not multisplit
                and (ksp.get_type() not in ("cg", "pipecg", "sstep")
                     or not batched_pc_supported(ksp.get_pc()))):
            import warnings
            warnings.warn(
                f"SolveServer operator {name!r}: configuration "
                f"{ksp.get_type()}+{ksp.get_pc().get_type()} has no "
                "batched kernel — coalesced blocks will dispatch as "
                "per-column sequential solves (check for stray global "
                "-ksp_type/-pc_type options)", stacklevel=2)
        ksp.set_up()                  # PC factors placed NOW, once
        ms = None
        if multisplit:
            from ..solvers.multisplit import MultisplitSolver
            if not hasattr(op, "to_scipy"):
                raise ValueError(
                    f"operator {name!r}: the multisplit schedule class "
                    "needs a host-reconstructible operator (Mat) — "
                    "matrix-free stencils have no row splitting")
            # the session's ksp_type/pc_type seed the per-block inner
            # solves — unless -multisplit_inner_type is set (PETSc
            # precedence: runtime flags beat programmatic defaults)
            inner = (None if global_options().has("multisplit_inner_type")
                     else ksp.get_type())
            ms = MultisplitSolver(self.comm, inner_type=inner,
                                  pc_type=ksp.get_pc().get_type(),
                                  rtol=rtol, atol=atol, dtype=dtype)
            ms.set_operator(op)
        persistent = global_options().get_bool("solve_server_persistent",
                                               persistent)
        pr_wanted = bool(persistent) and ms is None
        if persistent and ms is not None:
            raise ValueError(
                f"operator {name!r}: persistent and multisplit are "
                "mutually exclusive schedule classes — the async tier "
                "has no coalesced block program to keep resident")
        if pr_wanted:
            from ..solvers.megasolve import megasolve_supported
            guard = bool(ksp.abft) or int(ksp.residual_replacement) > 0
            if guard or not megasolve_supported(ksp.get_type(),
                                                ksp.get_pc(), op, nrhs=2):
                import warnings
                warnings.warn(
                    f"SolveServer operator {name!r}: persistent serving "
                    "needs a megasolve-eligible configuration without "
                    "the ABFT guard — falling back to per-batch "
                    "dispatch", stacklevel=2)
                pr_wanted = False
            else:
                # the recovery path (serving/persistent.py fallback)
                # dispatches through the session KSP: keep it on the
                # fused per-batch program
                ksp.megasolve = True
        sess = _OperatorSession(name, op, ksp, multisplit=ms)
        if pr_wanted:
            from .persistent import PersistentRunner
            sess.persistent = PersistentRunner(self, sess)
        with self._session_lock:
            # under the session lock: a concurrent regrow/adoption must
            # not iterate the registry while it grows
            self._sessions[name] = sess
            for w in warm_widths:
                w = padded_width(int(w), self.max_k, self.pad_pow2)
                ksp.solve_many(np.zeros((sess.n, w), sess.dtype))
        return sess

    registerOperator = register_operator

    def register_session(self, name: str, operator, *,
                         ksp_type: str = "cg", pc_type: str = "jacobi",
                         **kw):
        """Register an operator that is ALREADY a framework Mat/stencil
        resident on (or rebuildable for) this server's mesh — the
        migration landing pad (serving/fleet.py): the router reloads the
        elastic checkpoint onto the destination comm and hands the
        re-placed operator here, so a migrated session never round-trips
        through scipy again. Same contract as
        :meth:`register_operator`."""
        return self.register_operator(name, operator, ksp_type=ksp_type,
                                      pc_type=pc_type, **kw)

    def unregister_operator(self, name: str):
        """Remove a resident session (the migration departure hook —
        serving/fleet.py). Refuses while requests for it are queued:
        callers drain first so no future can be orphaned; its device
        buffers are released with the session object."""
        with self._session_lock, self._cv:
            if any(r.op == name for r in self._pending):
                raise RuntimeError(
                    f"unregister_operator({name!r}): requests still "
                    "pending — drain() first")
            sess = self._sessions.pop(name, None)
        if sess is None:
            raise ValueError(f"unknown operator {name!r}; registered: "
                             f"{self.operators()}")
        return sess

    def operators(self):
        return sorted(self._sessions)

    # ---- client APIs --------------------------------------------------------
    def submit(self, op: str, b, *, rtol: float | None = None,
               atol: float | None = None, max_it: int | None = None,
               deadline: float | None = None, qos: str | None = None,
               priority: int | None = None) -> Future:
        """Enqueue one solve; returns a Future of ServedSolveResult.

        Tolerance overrides narrow the request's compatibility group —
        requests with different tolerances never share a block.
        ``deadline`` overrides the per-request dispatch deadline in
        seconds (0 = none; default: the named QoS class's deadline, else
        the server's). ``qos`` names a service class
        (``interactive``/``bulk`` — serving/qos.py): it sets the
        request's priority tier and default deadline; ``priority``
        overrides the tier directly (LOWER is more urgent). With the
        queue at ``max_queue``, an arrival first tries to SHED the
        least-urgent strictly-lower-priority pending request (its future
        resolves with the typed overload error — bulk sheds before
        interactive, nothing hangs); when nothing pending is less
        urgent, the arrival itself is rejected with
        :class:`ServerOverloadedError` (admission control — the caller
        sheds load).
        """
        sess = self._sessions.get(op)
        if sess is None:
            raise ValueError(f"unknown operator {op!r}; registered: "
                             f"{self.operators()}")
        b = np.asarray(b)
        if b.shape != (sess.n,):
            raise ValueError(f"submit({op!r}): b must be ({sess.n},), "
                             f"got {b.shape}")
        cls = _qos.resolve(qos, self.qos_classes)
        prio = (int(priority) if priority is not None
                else cls.priority if cls is not None
                else _qos.DEFAULT_PRIORITY)
        if deadline is not None:
            budget = float(deadline)
        elif cls is not None and cls.deadline > 0:
            budget = cls.deadline
        else:
            budget = self.deadline
        fut: Future = Future()
        req = SolveRequest(
            # a COPY of the caller's RHS: the request sits in the
            # batching window while the caller may reuse its buffer for
            # the next submission — a zero-copy view would silently
            # rewrite this request's RHS
            op=op, b=np.array(b, dtype=sess.dtype, copy=True),
            rtol=sess.rtol if rtol is None else float(rtol),
            atol=sess.atol if atol is None else float(atol),
            max_it=sess.max_it if max_it is None else int(max_it),
            # the session's storage dtype IS its precision plan — part
            # of the compatibility key (serving/coalescer.py), as is
            # the reduction-plan schedule (cg/pipecg/sstep:<s>)
            precision=str(sess.dtype),
            schedule=sess.schedule,
            qos=cls.name if cls is not None else "",
            priority=prio,
            future=fut)
        if budget > 0:
            req.t_deadline = req.t_submit + budget
        with self._cv:
            if self._closed:
                raise ServerClosedError("SolveServer is shut down")
            if self._sessions.get(op) is not sess:
                # the session was unregistered (a fleet migration's
                # departure) between validation above and this enqueue:
                # reject now rather than queue a request no dispatch
                # can serve
                raise ValueError(f"operator {op!r} was unregistered "
                                 "while submitting")
            if self.max_queue > 0 and len(self._pending) >= self.max_queue:
                victim = _qos.shed_victim(self._pending, prio)
                if victim is None:
                    self._stats["rejected"] += 1
                    record_admission(rejected=1)
                    raise ServerOverloadedError(len(self._pending),
                                                self.max_queue)
                # QoS shedding: the less-urgent victim gives its queue
                # slot to this arrival; its future RESOLVES with the
                # typed error (shed=True) — resolved, never dropped.
                # Removal by IDENTITY: dataclass equality would compare
                # the ndarray RHS payloads
                self._pending = [r for r in self._pending
                                 if r is not victim]
                self._stats["shed"] += 1
                record_admission(shed=1)
                if victim.future.set_running_or_notify_cancel():
                    victim.future.set_exception(ServerOverloadedError(
                        len(self._pending) + 1, self.max_queue,
                        shed=True))
                self._end_request_span(victim, "shed")
            record_qos(req.qos)
            # the request's span is opened only for ADMITTED requests
            # (rejections are counted by serving.rejected — a burst of
            # ~flight_len rejected submissions must not flush the
            # dispatch history out of the post-mortem ring), on the
            # client thread; it is finished at resolution on the
            # dispatcher thread and linked to the dispatch span it rode
            # in (no-op singleton when disabled)
            req.span = _telemetry.start_span("serving.request", op=op)
            self._pending.append(req)
            # the queue changed (appended here, possibly shed above):
            # the dispatcher must re-schedule — a new arrival may
            # preempt the cached batch order
            self._sched_cache = None
            _metrics.registry.gauge("serving.queue_depth").set(
                len(self._pending))
            self._cv.notify_all()
        return fut

    def solve(self, op: str, b, *, timeout: float | None = None,
              **tol_overrides) -> ServedSolveResult:
        """Synchronous client API: submit + wait."""
        return self.submit(op, b, **tol_overrides).result(timeout)

    # ---- lifecycle ----------------------------------------------------------
    def start(self):
        """Start the dispatcher thread (idempotent)."""
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._loop, name="SolveServer-dispatch",
                daemon=True)
            self._thread.start()
        return self

    def drain(self, timeout: float | None = None) -> bool:
        """Block until every submitted request has resolved; False on
        timeout. The server stays open for new submissions."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while (self._pending or self._inflight
                   or self._persistent_unresolved()):
                rem = (None if deadline is None
                       else deadline - time.monotonic())
                if rem is not None and rem <= 0:
                    return False
                self._cv.wait(rem if rem is not None else 0.5)
        return True

    def drain_operator(self, name: str,
                       timeout: float | None = None) -> bool:
        """Block until no request for ``name`` sits in the pending
        queue; False on timeout. Unlike :meth:`drain` this does NOT
        wait out traffic to co-resident sessions — the migration path
        (serving/fleet.py) uses it so moving one session off a busy
        replica cannot livelock behind the others' sustained load.
        An in-flight block for the session may still be executing;
        session swaps serialize on the session lock, which waits it
        out."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            while any(r.op == name for r in self._pending):
                rem = (None if deadline is None
                       else deadline - time.monotonic())
                if rem is not None and rem <= 0:
                    return False
                self._cv.wait(rem if rem is not None else 0.5)
        return True

    def shutdown(self, wait: bool = True):
        """Stop the server. ``wait=True`` (default) FLUSHES the queue —
        every pending future resolves (the drain-on-shutdown contract) —
        then joins the dispatcher. ``wait=False`` fails pending futures
        with :class:`ServerClosedError` and returns promptly."""
        with self._cv:
            if self._closed and self._thread is None:
                return
            self._closed = True
            if not wait:
                for r in self._pending:
                    if r.future.set_running_or_notify_cancel():
                        r.future.set_exception(
                            ServerClosedError("server shut down before "
                                              "dispatch"))
                    if r.span is not None:
                        r.span.set_attr("outcome", "closed").end()
                self._pending.clear()
                self._sched_cache = None
            pending = bool(self._pending)
        if self._thread is None and pending:
            # never-started server (autostart=False): flush inline so
            # shutdown keeps the every-future-resolves contract
            self.start()
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown(wait=exc == (None, None, None))
        return False

    # ---- dispatcher ---------------------------------------------------------
    def _loop(self):
        while True:
            with self._cv:
                while (not self._pending and not self._stop
                       and not self._persistent_unresolved()):
                    self._cv.wait()
                stopping = not self._pending and self._stop
                idle = not self._pending
                t_open = (self._pending[0].t_submit if self._pending
                          else 0.0)
            if idle:
                # the queue went quiet (or we are stopping) with
                # persistent launches outstanding: resolve them NOW —
                # staged futures must never wait on the next arrival.
                # Outside _cv (resolution blocks on device results and
                # notifies the condvar) under the session lock, the
                # established lock order.
                with self._session_lock:
                    self._flush_persistent()
                if stopping:
                    return
                continue
            # a heal may have restored capacity while the server sat
            # degraded — adopt the larger mesh BEFORE dispatching this
            # window's traffic (cheap epoch check when nothing healed)
            self._maybe_regrow()
            # batching window: hold the oldest pending request at most
            # `window` seconds so concurrent arrivals ride its block;
            # shutdown flushes immediately. Requests arriving after the
            # scheduling pass below land in a LATER pass by construction
            # — and the window is only charged once per backlog: a
            # request requeued by the one-batch-per-pass discipline is
            # older than the window, so the next pass dispatches it
            # immediately.
            while True:
                with self._cv:
                    if self._stop:
                        break
                    rem = self.window - (time.monotonic() - t_open)
                    if rem <= 0:
                        break
                    self._cv.wait(timeout=rem)
            # QoS scheduling pass (serving/qos.py): group the snapshot
            # into compatible batches ordered by deadline-weighted
            # priority and dispatch ONE — the rest stay pending, so a
            # high-priority arrival during this batch's launch preempts
            # the remaining bulk batches into the next pass (never the
            # in-flight block: preemption is scheduling, not
            # cancellation). The remaining batch order is CACHED and
            # reused while nothing touches the queue (submit/shed
            # invalidate), so draining a quiet backlog schedules once,
            # not once per batch.
            with self._cv:
                if self._sched_cache:
                    batch = self._sched_cache.pop(0)
                else:
                    with _telemetry.span(
                            "serving.coalesce",
                            taken=len(self._pending)) as csp:
                        batches = _qos.schedule(self._pending,
                                                self.max_k)
                        csp.set_attrs(batches=len(batches))
                    if not batches:
                        continue
                    batch = batches[0]
                    self._sched_cache = batches[1:]
                chosen = {id(r) for r in batch}
                self._pending = [r for r in self._pending
                                 if id(r) not in chosen]
                self._inflight += len(batch)
                _metrics.registry.gauge("serving.queue_depth").set(
                    len(self._pending))
            try:
                with self._session_lock:
                    self._dispatch(batch)
            finally:
                with self._cv:
                    self._inflight -= len(batch)
                    self._cv.notify_all()

    def _dispatch(self, reqs):
        """Solve one coalesced batch and demux per-request results."""
        if self._dispatch_hook is not None:
            self._dispatch_hook(reqs)
        # server-side deadlines: a request whose dispatch deadline has
        # passed resolves with DEADLINE_EXCEEDED instead of occupying a
        # batch column — on a degraded (shrunk) mesh the capacity goes
        # to requests whose clients are still waiting
        now = time.monotonic()
        expired = [r for r in reqs if r.expired(now)]
        if expired:
            with self._cv:
                self._stats["expired"] += len(expired)
            record_admission(expired=len(expired))
            for r in expired:
                if r.future.set_running_or_notify_cancel():
                    r.future.set_exception(DeadlineExceededError(
                        now - r.t_submit, r.t_deadline - r.t_submit))
                self._end_request_span(r, "deadline_exceeded")
            reqs = [r for r in reqs if not r.expired(now)]
        # honor client-side cancellation (Future protocol): a request
        # cancelled before dispatch never reaches the device
        live = []
        for r in reqs:
            if r.future.set_running_or_notify_cancel():
                live.append(r)
            else:
                self._end_request_span(r, "cancelled")
        reqs = live
        if not reqs:
            return
        sess = self._sessions.get(reqs[0].op)
        if sess is None:
            # the session vanished after these requests were queued (an
            # out-of-contract unregister without a drain): resolve the
            # futures with the typed error — the dispatcher must NEVER
            # die on a bad batch, every later request depends on it
            exc = ValueError(f"operator {reqs[0].op!r} is no longer "
                             "registered")
            for r in reqs:
                r.future.set_exception(exc)
                self._end_request_span(r, "error")
            return
        k = len(reqs)
        t0 = time.monotonic()
        waits = [t0 - r.t_submit for r in reqs]
        with self._cv:
            qh = self._stats["qos_hist"]
            for r in reqs:
                key = r.qos or "default"
                qh[key] = qh.get(key, 0) + 1
        if sess.persistent is not None:
            # persistent serving: stage this batch's slots into the
            # resident program's NEXT launch (double-buffered;
            # serving/persistent.py) and return to coalescing
            # immediately — resolution happens at buffer turnover or
            # the idle flush, never here
            sess.persistent.enqueue(reqs, waits)
            self._record(k, waits, 0)
            return
        kpad = padded_width(k, self.max_k, self.pad_pow2)
        # the batch span: a ROOT span on the dispatcher thread; every
        # request resolved out of this block links back to it
        bsp = _telemetry.span("serving.dispatch", op=reqs[0].op,
                              width=k, padded=kpad - k,
                              precision=reqs[0].precision)
        with bsp:
            B = np.zeros((sess.n, kpad), dtype=sess.dtype)
            for j, r in enumerate(reqs):
                B[:, j] = r.b
            ksp = sess.ksp
            ksp.set_tolerances(rtol=reqs[0].rtol, atol=reqs[0].atol,
                               max_it=reqs[0].max_it)
            try:
                if sess.multisplit is not None:
                    res = self._multisplit_solve_many(sess, reqs, B, k)
                elif self.resilient:
                    res = resilient_solve_many(ksp, B,
                                               policy=self.retry_policy)
                else:
                    res = ksp.solve_many(B)
            # tpslint: disable=TPS005 — whatever the dispatch raised
            # (exhausted retries, validation, a non-retriable device
            # failure) must reach the WAITING CLIENT FUTURES, not kill
            # the dispatcher thread; re-raising here would hang every
            # later request
            except Exception as exc:  # noqa: BLE001
                bsp.set_attr("error", type(exc).__name__)
                # close the batch span FIRST (end() is idempotent; the
                # with-exit becomes a no-op) so the dump below includes
                # this failed dispatch's own span tree, then dump — the
                # failure just became the clients' problem (no-op
                # disarmed)
                bsp.end()
                _flight.auto_dump("serving dispatch failed: "
                                  f"{type(exc).__name__}")
                for r in reqs:
                    r.future.set_exception(exc)
                    self._end_request_span(r, "error", batch=bsp)
                self._record(k, waits, kpad - k)
                return
            shrinks = [e for e in res.recovery_events
                       if e.kind == "mesh_shrink"]
            if shrinks:
                # the resilient dispatch survived a persistent device
                # loss by resharding THIS session onto a degraded mesh
                # (its batch-mates replayed from the checkpointed block
                # inside the retry loop) — adopt the new mesh
                # server-wide
                self._adopt_shrunk_mesh(sess, shrinks,
                                        time.monotonic() - t0)
            per = res.per_rhs()
            for j, r in enumerate(reqs):
                col = per[j]
                out = ServedSolveResult(
                    iterations=col.iterations,
                    residual_norm=col.residual_norm,
                    reason=col.reason, wall_time=res.wall_time,
                    history=col.history,
                    attempts=res.attempts,
                    recovery_events=list(res.recovery_events),
                    abft_checks=res.abft_checks,
                    sdc_detections=res.sdc_detections,
                    residual_replacements=res.residual_replacements,
                    x=np.array(res.X[:, j]), op=r.op, batch_width=k,
                    queue_wait=waits[j])
                r.future.set_result(out)
                self._end_request_span(r, "ok", batch=bsp,
                                       iterations=col.iterations,
                                       queue_wait=waits[j])
            bsp.set_attrs(attempts=res.attempts,
                          iterations=max(res.iterations, default=0))
        self._record(k, waits, kpad - k)

    def _persistent_unresolved(self) -> int:
        """Requests staged into (or riding) persistent launches — the
        drain/shutdown and idle-flush accounting. Lock-free snapshot:
        a stale count only costs one extra condvar lap."""
        n = 0
        for s in list(self._sessions.values()):
            if s.persistent is not None:
                n += s.persistent.unresolved
        return n

    def _flush_persistent(self):
        """Resolve every outstanding persistent launch and drain the
        staged backlogs (serving/persistent.py). Caller holds the
        session lock (the runners' concurrency contract)."""
        for s in list(self._sessions.values()):
            if s.persistent is not None:
                s.persistent.flush()

    def _multisplit_solve_many(self, sess, reqs, B, k):
        """Dispatch one batch through the ASYNCHRONOUS tier: per-column
        stale-tolerant outer solves (solvers/multisplit.py) instead of a
        coalesced synchronous block program — the "multisplit" schedule
        class. QoS-URGENT batches ride fresher exchanges: when any
        member is ``interactive``, the staleness bound tightens to
        ``-multisplit_urgent_stale`` (default: half the session's
        bound), trading straggler tolerance for iterate freshness on
        the traffic that is actually waiting."""
        from ..utils.convergence import BatchedSolveResult
        ms = sess.multisplit
        bound = None
        if any(r.qos == "interactive" for r in reqs):
            bound = global_options().get_int(
                "multisplit_urgent_stale", max(1, ms.max_stale // 2))
        t0 = time.monotonic()
        X = np.zeros((sess.n, k), dtype=sess.dtype)
        iters, rnorms, reasons, hists = [], [], [], []
        for j, r in enumerate(reqs):
            res = ms.solve(B[:, j], rtol=r.rtol, atol=r.atol,
                           max_stale=bound)
            X[:, j] = res.x
            iters.append(int(res.iterations))
            rnorms.append(float(res.residual_norm))
            reasons.append(int(res.reason))
            hists.append([rn for _v, rn in res.history])
        return BatchedSolveResult(iterations=iters, residual_norms=rnorms,
                                  reasons=reasons,
                                  wall_time=time.monotonic() - t0, X=X,
                                  histories=hists)

    @staticmethod
    def _end_request_span(req, outcome: str, batch=None, **attrs):
        """Finish a request's detached serving.request span, linking it
        to the batch span it was resolved out of."""
        sp = req.span
        if sp is None:
            return
        if batch is not None and batch.span_id:
            sp.set_attr("batch_span", batch.span_id)
        sp.set_attrs(outcome=outcome, **attrs)
        sp.end()

    def _rebuild_sessions_on(self, comm_new, skip=None) -> dict:
        """Re-place every resident session on ``comm_new`` (operands,
        PC factors, ABFT checksums; base + previously seen block-width
        programs re-warmed/AOT-loaded) — the shared rebuild step of the
        shrink adoption AND the re-grow. ``skip`` excludes a session the
        elastic retry stage already rebuilt. Per-session failures are
        recorded, never raised: a session that cannot live on the new
        geometry must not abort adoption for the sessions that can —
        its next dispatch surfaces the recorded error on client
        futures. Runs on the dispatcher thread (the only place sessions
        are mutated mid-flight)."""
        from ..resilience import elastic as _elastic
        # persistent launches hold device buffers on the OLD mesh:
        # consume them first (quiesce resolves the in-flight launch,
        # leaving host-side staged slots to launch on the new geometry;
        # inside our own fallback's shrink adoption the record is
        # already detached — a no-op)
        for s in list(self._sessions.values()):
            if s.persistent is not None:
                s.persistent.quiesce()
        with self._cv:
            widths = sorted(padded_width(w, self.max_k, self.pad_pow2)
                            for w in self._stats["width_hist"])
        failures = {}
        for s in self._sessions.values():
            if s is skip:
                continue
            try:
                mat2 = _elastic.rebuild_operator(s.operator, comm_new)
                _elastic.rebuild_ksp(s.ksp, mat2)
                s.operator = mat2
                _elastic.warm(s.ksp, widths)
            # tpslint: disable=TPS005 — a session whose operator cannot
            # be rebuilt on the new mesh must not abort adoption for
            # the sessions that CAN: record it, keep going; its next
            # dispatch surfaces the recorded error on client futures
            except Exception as exc:  # noqa: BLE001
                failures[s.name] = repr(exc)
        return failures

    def _adopt_shrunk_mesh(self, shrunk_sess, shrink_events, dispatch_wall):
        """Adopt the degraded mesh a resilient dispatch landed on.

        ``shrunk_sess``'s KSP was already rebuilt by the elastic retry
        stage; every OTHER resident operator is re-registered via
        :meth:`_rebuild_sessions_on` so the next dispatch of any session
        runs on surviving hardware instead of failing on the lost
        device."""
        comm_new = shrunk_sess.ksp.comm
        if comm_new is self.comm or comm_new.size >= self.comm.size:
            return
        old_n = self.comm.size
        t0 = time.monotonic()
        shrunk_sess.operator = shrunk_sess.ksp.get_operators()[0]
        failures = self._rebuild_sessions_on(comm_new, skip=shrunk_sess)
        self.comm = comm_new
        # deliberately do NOT touch _heal_epoch_seen here: a heal that
        # landed WHILE this degraded dispatch was running must still
        # trigger _maybe_regrow on the next pass (resetting to the
        # current epoch would swallow it); a stale pre-degradation heal
        # costs one harmless grown_comm plan that the still-lost
        # registry rejects
        entry = {"old_devices": old_n, "new_devices": comm_new.size,
                 "dispatch_wall_s": float(dispatch_wall),
                 "adopt_wall_s": time.monotonic() - t0,
                 "resumed_iteration": max(
                     (e.iterations for e in shrink_events), default=0),
                 "rebuild_failures": failures}
        with self._cv:
            self._stats["mesh_shrinks"].append(entry)

    def _maybe_regrow(self) -> bool:
        """Cheap hot-loop check: when the server sits DEGRADED and
        :func:`resilience.faults.heal` ran since, plan and adopt the
        largest viable larger mesh (never past the provisioned one).
        Returns True when a re-grow was executed."""
        if self.comm.size >= self._full_comm.size:
            return False
        ep = _faults.heal_epoch()
        if ep == self._heal_epoch_seen:
            return False
        self._heal_epoch_seen = ep
        return self.regrow()

    def regrow(self) -> bool:
        """Rebuild every resident session onto the largest viable
        larger mesh over healed devices (the elastic ladder's upward
        direction — ``-elastic_regrow``); no-op (False) when the server
        is not degraded, the policy disarms re-growing, or the healed
        hardware does not support a strictly larger rung. The public
        twin of the dispatcher's heal-epoch check, for drivers that
        know a repair happened (a fleet router, an operator console) —
        safe from any thread: the session lock makes the rebuild wait
        out an in-flight dispatch instead of swapping operators under
        it."""
        from ..resilience import elastic as _elastic
        from ..utils.profiling import record_mesh_regrow
        policy = _elastic.ElasticPolicy.from_options()
        if not (policy.enabled and policy.regrow):
            return False
        with self._session_lock:
            grown = _elastic.MeshRebuilder(policy).grown_comm(
                self.comm, self._full_comm)
            if grown is None:
                return False
            old_n = self.comm.size
            t0 = time.monotonic()
            with _telemetry.span("serving.regrow", old_devices=old_n,
                                 new_devices=int(grown.size)) as gsp:
                failures = self._rebuild_sessions_on(grown)
                self.comm = grown
                wall = time.monotonic() - t0
                record_mesh_regrow(old_n, grown.size, wall)
                gsp.set_attrs(
                    rebuilt=len(self._sessions) - len(failures),
                    failures=len(failures))
        entry = {"old_devices": old_n, "new_devices": grown.size,
                 "adopt_wall_s": wall, "rebuild_failures": failures}
        with self._cv:
            self._stats["mesh_regrows"].append(entry)
        return True

    def _record(self, width, waits, padded):
        record_serving(width, waits, padded)   # the process-wide twin
        for w in waits:
            self._wait_hist.observe(float(w))
        with self._cv:
            st = self._stats
            st["requests"] += width
            st["batches"] += 1
            st["padded_cols"] += padded
            st["width_hist"][width] = st["width_hist"].get(width, 0) + 1

    # ---- observability ------------------------------------------------------
    def stats(self) -> dict:
        """Per-server coalescing statistics (profiling.serving_stats()
        keeps the process-wide twin printed by ``log_view``; both views
        compute their wait percentiles through the SAME registry
        ``Histogram.summary`` helper)."""
        with self._cv:
            st = self._stats
            out = {"requests": st["requests"], "batches": st["batches"],
                   "padded_cols": st["padded_cols"],
                   "width_hist": dict(st["width_hist"]),
                   "qos_hist": dict(st["qos_hist"]),
                   "rejected": st["rejected"], "expired": st["expired"],
                   "shed": st["shed"],
                   "pending": len(self._pending),
                   "devices": int(self.comm.size),
                   "mesh_shrinks": [dict(e)
                                    for e in st["mesh_shrinks"]],
                   "mesh_regrows": [dict(e)
                                    for e in st["mesh_regrows"]]}
            per = {s.name: dict(s.persistent.stats)
                   for s in self._sessions.values()
                   if s.persistent is not None}
            if per:
                out["persistent"] = per
        out["mean_width"] = (out["requests"] / out["batches"]
                             if out["batches"] else 0.0)
        s = self._wait_hist.summary((50, 99))
        if s["count"]:
            out["queue_wait_mean_s"] = s["mean"]
            out["queue_wait_p50_s"] = s["p50"]
            out["queue_wait_p99_s"] = s["p99"]
            out["queue_wait_max_s"] = s["max"]
        return out

    def metrics_endpoint(self) -> str:
        """The process-wide telemetry registry in Prometheus text
        exposition format (content type ``text/plain; version=0.0.4``)
        — mount it behind ``GET /metrics`` on whatever HTTP front-end
        fronts this server (the framework deliberately ships the
        PAYLOAD, not a web server)."""
        return _metrics.registry.prometheus_text()

    metricsEndpoint = metrics_endpoint

    def __repr__(self):
        return (f"SolveServer(ops={self.operators()}, "
                f"window={self.window:g}s, max_k={self.max_k}, "
                f"resilient={self.resilient})")
