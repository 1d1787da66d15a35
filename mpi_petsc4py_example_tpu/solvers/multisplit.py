"""Asynchronous two-stage multisplitting — the stale-tolerant solver tier.

Every synchronous plan in the zoo (classic/pipecg/s-step CG,
solvers/krylov.py) stalls the WHOLE mesh on its slowest device at every
reduction: one sticky straggler taxes every iteration, and a lost device
stalls the solve until the elastic ladder rebuilds it. "A highly
scalable approach to solving linear systems using two-stage
multisplitting" (PAPERS.md) removes that failure mode by changing the
contract from synchrony to bounded staleness:

* the operator is row-partitioned into ``-multisplit_blocks`` blocks
  (parallel/partition.py — the same contiguous PETSc-style split);
* each block runs an INNER solve on its diagonal block ``A_ii`` with its
  own :class:`..solvers.ksp.KSP` on a 1-device sub-communicator — any
  registered plan (``-multisplit_inner_type``: cg/pipecg/sstep/...), so
  the whole PC / precision / ABFT zoo is inherited unchanged;
* the OUTER iteration is asynchronous block relaxation: block ``i``
  repeatedly solves ``A_ii x_i = b_i - sum_{j!=i} A_ij x_j`` against
  whatever neighbor iterates the stale-tolerant exchange
  (parallel/exchange.StaleExchange) currently holds. Reads never block;
  every read carries a staleness age; a partner over the
  ``-multisplit_max_stale`` bound triggers a RESYNC (the one deliberate
  wait), counted in ``multisplit.resyncs``;
* convergence is declared ONLY at a globally **consistent version cut**
  (``StaleExchange.consistent_cut``): the supervisor assembles the full
  iterate with every live block at one matching version and measures the
  true residual with ONE compiled program holding exactly ONE ``psum``
  (``multisplit_residual`` — contracts.py pins it). Stale local norms
  are never a convergence basis — tpslint TPS018 enforces the call-site
  half of that contract.

Robustness is the headline. A per-device ``comm.delay`` timing fault
(resilience/faults.py) simulates jittery or sticky-slow devices — the
async tier absorbs them as staleness where every synchronous plan pays
max-of-device latency per reduction. A mid-solve ``device.lost`` degrades to ONE stale block:
the survivors keep iterating against the block's last exchanged version
(frozen by ``StaleExchange.mark_lost``), and the failed block re-homes
onto a survivor device FROM that version — per-block version counters
are monotonic across the loss, so the solve provably never revisits
iteration 0 (the chaos drill's assertion, tools/chaos_smoke.py
``--multisplit``).

Convergence of the outer iteration requires the usual multisplitting
hypotheses (block-diagonally-dominant / M-matrix style splittings —
Frommer & Szyld's classical conditions); for general SPD systems the
synchronous tier remains the default and this tier is the
latency-insensitive scale-out option (README "Asynchronous
multisplitting" discusses when async wins).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..core.mat import Mat
from ..core.vec import Vec
from ..parallel.exchange import StaleExchange, check_staleness_bound
from ..parallel.mesh import DeviceComm, as_comm, faulted_psum
from ..parallel.partition import row_partition
from ..resilience import faults as _faults
from ..telemetry import metrics as _metrics
from ..telemetry import spans as _telemetry
from ..utils.convergence import ConvergedReason
from ..utils.errors import DeviceExecutionError
from ..utils.options import global_options

#: program-kind names (contracts.py PROGRAM_KINDS): the inner-block
#: solve program a block's KSP dispatches per async step, and the
#: consistent-cut residual program (one psum, full mesh).
BLOCK_PROGRAM_KIND = "multisplit_block"
RESIDUAL_PROGRAM_KIND = "multisplit_residual"

DEFAULT_MAX_STALE = 4
DEFAULT_MAX_OUTER = 500
DEFAULT_INNER_RTOL = 1e-2
DEFAULT_INNER_MAX_IT = 50
DEFAULT_RESYNC_TIMEOUT = 30.0


def build_multisplit_residual_program(comm: DeviceComm, A: Mat):
    """The consistent-cut residual program: ``||b - A x||^2`` over the
    FULL mesh with exactly ONE ``psum`` (contracts.py pins the count —
    the async tier's only global collective, paid per convergence CHECK,
    never per iteration; the zero-outer-collectives-per-step contract is
    the whole point of the tier)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    axis = comm.axis
    spmv = A.local_spmv(comm)
    nops = len(A.device_arrays())

    def local(*args):
        op_local = args[:nops]
        b_local, x_local = args[nops], args[nops + 1]
        r = b_local - spmv(op_local, x_local)
        return faulted_psum(jnp.sum(r * r), axis)

    in_specs = (P(axis),) * (nops + 2)
    return jax.jit(comm.shard_map(local, in_specs, P()))


class MultisplitResult:
    """Outcome of one asynchronous multisplit solve."""

    __slots__ = ("x", "iterations", "residual_norm", "reason", "wall_time",
                 "history", "resyncs", "blocks_lost", "block_steps",
                 "cut_version", "max_stale_seen")

    def __init__(self, x, iterations, residual_norm, reason, wall_time,
                 history, resyncs, blocks_lost, block_steps, cut_version,
                 max_stale_seen):
        self.x = x
        self.iterations = iterations          # consistent-cut version
        self.residual_norm = residual_norm
        self.reason = reason
        self.wall_time = wall_time
        self.history = history                # (cut_version, rnorm) pairs
        self.resyncs = resyncs
        self.blocks_lost = blocks_lost
        self.block_steps = block_steps        # outer steps per block
        self.cut_version = cut_version
        self.max_stale_seen = max_stale_seen

    @property
    def converged(self) -> bool:
        return self.reason > 0

    def __repr__(self):
        return (f"MultisplitResult(reason="
                f"{ConvergedReason.name(self.reason)}, "
                f"cut={self.cut_version}, rnorm={self.residual_norm:.3e}, "
                f"steps={self.block_steps}, resyncs={self.resyncs}, "
                f"lost={self.blocks_lost})")


class _BlockState:
    """Everything one block's solver thread owns: its 1-device subcomm,
    diagonal-block operator + inner KSP, host off-diagonal coupling, and
    the current iterate."""

    __slots__ = ("index", "rstart", "rend", "device_id", "comm", "mat",
                 "ksp", "A_diag", "A_off", "b_local", "x", "version",
                 "steps", "resyncs", "lost_count", "max_age")

    def __init__(self, index, rstart, rend):
        self.index = index
        self.rstart = rstart
        self.rend = rend
        self.device_id = None
        self.comm = None
        self.mat = None
        self.ksp = None
        self.A_diag = None      # scipy CSR of A[rows, rows] (re-home src)
        self.A_off = None       # scipy CSR of A[rows, :] with diag zeroed
        self.b_local = None
        self.x = None
        self.version = 0        # last exchange version this block holds
        self.steps = 0
        self.resyncs = 0
        self.lost_count = 0
        self.max_age = 0        # worst staleness this block read


class MultisplitSolver:
    """Asynchronous two-stage multisplit solver (module doc).

    Flags (``-multisplit_*``, utils/options.py) set the defaults;
    constructor keywords override them programmatically, PETSc
    precedence inverted deliberately — the flags are the operator's
    knobs, the keywords are the embedding layer's (the serving tier
    tightens ``max_stale`` per QoS class this way).
    """

    def __init__(self, comm=None, *, nblocks: int | None = None,
                 max_stale: int | None = None,
                 inner_type: str | None = None,
                 inner_rtol: float | None = None,
                 inner_max_it: int | None = None,
                 max_outer: int | None = None,
                 resync_timeout: float | None = None,
                 pc_type: str = "jacobi",
                 rtol: float = 1e-5, atol: float = 0.0, dtype=None):
        self.comm = as_comm(comm)
        opts = global_options()
        if nblocks is None:
            nblocks = opts.get_int("multisplit_blocks", self.comm.size)
        if max_stale is None:
            max_stale = opts.get_int("multisplit_max_stale",
                                     DEFAULT_MAX_STALE)
        if inner_type is None:
            inner_type = opts.get_string("multisplit_inner_type", "cg")
        if inner_rtol is None:
            inner_rtol = opts.get_real("multisplit_inner_rtol",
                                       DEFAULT_INNER_RTOL)
        if inner_max_it is None:
            inner_max_it = opts.get_int("multisplit_inner_max_it",
                                        DEFAULT_INNER_MAX_IT)
        if max_outer is None:
            max_outer = opts.get_int("multisplit_max_outer",
                                     DEFAULT_MAX_OUTER)
        if resync_timeout is None:
            resync_timeout = opts.get_real("multisplit_resync_timeout",
                                           DEFAULT_RESYNC_TIMEOUT)
        self.nblocks = max(1, int(nblocks))
        self.max_stale = max(0, int(max_stale))
        self.inner_type = str(inner_type)
        self.inner_rtol = float(inner_rtol)
        self.inner_max_it = max(1, int(inner_max_it))
        self.max_outer = max(1, int(max_outer))
        self.resync_timeout = float(resync_timeout)
        self.pc_type = pc_type
        self.rtol = float(rtol)
        self.atol = float(atol)
        self.dtype = dtype
        self.n = 0
        self._A = None                 # host scipy CSR (set_operator)
        self._A_full = None            # residual-mesh Mat (cut checks)
        self._residual_prog = None
        self._residual_comm = None     # full mesh, shrunk on device loss
        self._b_dev = None             # placed rhs of the CURRENT solve
        self._blocks: list[_BlockState] = []
        self._exchange: StaleExchange | None = None
        self._stop = threading.Event()
        self._worker_error = None

    # ----------------------------------------------------------- operator
    def set_operator(self, A):
        """Accepts a scipy sparse matrix / dense array, or a framework
        :class:`Mat` (fetched back to host CSR for the splitting — the
        two-stage decomposition is a HOST restructuring, like PETSc's
        PCASM subdomain extraction)."""
        import scipy.sparse as sp
        if hasattr(A, "to_scipy"):
            A = A.to_scipy()
        A = sp.csr_matrix(A)
        if A.shape[0] != A.shape[1]:
            raise ValueError(f"multisplit needs a square operator, "
                             f"got {A.shape}")
        self.n = int(A.shape[0])
        self._A = A
        self._A_full = None
        self._residual_prog = None
        self._residual_comm = self.comm
        count, displ = row_partition(self.n, self.nblocks)
        self._blocks = []
        devices = list(self.comm.mesh.devices.flat)
        for i in range(self.nblocks):
            st = _BlockState(i, int(displ[i]), int(displ[i] + count[i]))
            rows = slice(st.rstart, st.rend)
            st.A_diag = sp.csr_matrix(A[rows, rows])
            off = sp.lil_matrix(A[rows, :])
            off[:, rows] = 0            # own-block coupling lives in A_ii
            st.A_off = sp.csr_matrix(off)
            self._place_block(st, devices[i % len(devices)])
            self._blocks.append(st)
        return self

    set_operators = set_operator       # KSP-surface spelling

    def _place_block(self, st: _BlockState, device):
        """(Re-)build a block's device residency: 1-device subcomm,
        diagonal-block operator, inner KSP — the same recipe the
        ``device.lost`` re-home replays on a survivor device."""
        st.device_id = int(device.id)
        st.comm = DeviceComm(devices=[device])
        kw = {} if self.dtype is None else {"dtype": self.dtype}
        st.mat = Mat.from_scipy(st.comm, st.A_diag, **kw)
        from .ksp import KSP
        ksp = KSP().create(st.comm)
        ksp.set_operators(st.mat)
        ksp.set_type(self.inner_type)
        ksp.get_pc().set_type(self.pc_type)
        ksp.set_tolerances(rtol=self.inner_rtol,
                           max_it=self.inner_max_it)
        ksp.set_initial_guess_nonzero(True)   # warm-started outer steps
        st.ksp = ksp

    # -------------------------------------------------------------- solve
    def solve(self, b, x0=None, *, rtol=None, atol=None,
              max_stale=None) -> MultisplitResult:
        """Run the asynchronous outer iteration until the consistent-cut
        residual meets ``max(rtol*||b||, atol)`` or every block hits
        ``-multisplit_max_outer``. ``max_stale`` overrides the staleness
        bound for THIS solve (the serving tier's QoS-urgent tightening,
        ``-multisplit_urgent_stale``)."""
        if self._A is None:
            raise RuntimeError("set_operator first")
        rtol = self.rtol if rtol is None else float(rtol)
        atol = self.atol if atol is None else float(atol)
        bound = self.max_stale if max_stale is None else max(0,
                                                             int(max_stale))
        b = np.asarray(b, dtype=self._blocks[0].A_diag.dtype).ravel()
        if b.shape[0] != self.n:
            raise ValueError(f"rhs length {b.shape[0]} != n {self.n}")
        bnorm = float(np.linalg.norm(b))
        target = max(rtol * bnorm, atol)
        x0 = (np.zeros_like(b) if x0 is None
              else np.asarray(x0, dtype=b.dtype).ravel())
        # history ring must cover the staleness the bound tolerates so
        # the consistent cut stays reconstructible (exchange module doc)
        self._exchange = StaleExchange(self.nblocks,
                                       history=bound + 4)
        self._stop.clear()
        self._worker_error = None
        self._b_dev = None
        for st in self._blocks:
            st.b_local = b[st.rstart:st.rend].copy()
            st.x = x0[st.rstart:st.rend].copy()
            st.version = 0
            st.steps = 0
            st.resyncs = 0
            st.lost_count = 0
            st.max_age = 0
        t0 = time.monotonic()
        with _telemetry.span("multisplit.solve", blocks=self.nblocks,
                             n=self.n, max_stale=bound,
                             inner=self.inner_type) as sp:
            threads = [threading.Thread(target=self._block_worker,
                                        args=(st, bound),
                                        name=f"multisplit-b{st.index}",
                                        daemon=True)
                       for st in self._blocks]
            for t in threads:
                t.start()
            try:
                result = self._supervise(b, target, threads, t0, rtol)
            finally:
                # the workers must be parked before this thread can
                # raise: a worker still inside a compiled dispatch at
                # interpreter teardown aborts the process
                self._stop.set()
                for t in threads:
                    t.join()
            if self._worker_error is not None:
                raise self._worker_error
            sp.set_attrs(reason=ConvergedReason.name(result.reason),
                         cut=result.cut_version,
                         resyncs=result.resyncs,
                         blocks_lost=result.blocks_lost)
        return result

    # The supervisor declares convergence ONLY through consistent_cut()
    # (never on stale per-block reads) — the TPS018 sanitizer contract.
    def _supervise(self, b, target, threads, t0, rtol) -> MultisplitResult:
        exch = self._exchange
        history = []
        last_cut = 0
        rnorm = float("inf")
        reason = ConvergedReason.ITERATING
        while True:
            cut = exch.consistent_cut()
            if cut is not None and cut[0] > last_cut:
                last_cut, payloads = cut
                x_full = self._assemble_cut(payloads)
                rnorm = self._residual_norm(b, x_full)
                history.append((last_cut, rnorm))
                if rnorm <= target:
                    reason = (ConvergedReason.CONVERGED_RTOL
                              if rnorm <= rtol * max(
                                  float(np.linalg.norm(b)), 1e-300)
                              else ConvergedReason.CONVERGED_ATOL)
                    break
            if self._worker_error is not None:
                break
            if not any(t.is_alive() for t in threads):
                # every block exhausted its outer budget (or died): one
                # final cut check above already ran — report divergence
                cut = exch.consistent_cut()
                if cut is not None and cut[0] > last_cut:
                    continue
                reason = ConvergedReason.DIVERGED_MAX_IT
                break
            exch.wait_change(timeout=0.01)
        x = self._final_iterate(last_cut)
        return MultisplitResult(
            x=x, iterations=last_cut, residual_norm=rnorm,
            reason=reason, wall_time=time.monotonic() - t0,
            history=history,
            resyncs=sum(st.resyncs for st in self._blocks),
            blocks_lost=sum(st.lost_count for st in self._blocks),
            block_steps=tuple(st.steps for st in self._blocks),
            cut_version=last_cut,
            max_stale_seen=max(st.max_age for st in self._blocks))

    def _final_iterate(self, cut_version):
        """The solution at the LAST verified cut when one exists, else
        the freshest per-block iterates (diverged reporting)."""
        exch = self._exchange
        cut = exch.consistent_cut()
        if cut is not None and cut[0] >= cut_version and cut_version > 0:
            return self._assemble_cut(cut[1])
        x = np.zeros(self.n, dtype=self._blocks[0].b_local.dtype)
        for st in self._blocks:
            r = exch.latest(st.index)
            x[st.rstart:st.rend] = (r.payload if r.payload is not None
                                    else st.x)
        return x

    def _assemble_cut(self, payloads) -> np.ndarray:
        x = np.zeros(self.n, dtype=self._blocks[0].b_local.dtype)
        for st in self._blocks:
            x[st.rstart:st.rend] = payloads[st.index]
        return x

    def _residual_norm(self, b, x_full) -> float:
        """True residual at a consistent cut: one compiled program, one
        psum, fp64 (contracts.py ``multisplit/residual``). Runs on the
        full mesh; when that mesh holds a LOST device the check itself
        re-homes onto the survivor mesh (the same elastic-shrink
        discipline the block workers follow) and retries once."""
        for attempt in (0, 1):
            try:
                if self._A_full is None:
                    kw = {} if self.dtype is None else {"dtype": self.dtype}
                    self._A_full = Mat.from_scipy(self._residual_comm,
                                                  self._A, **kw)
                    self._residual_prog = build_multisplit_residual_program(
                        self._residual_comm, self._A_full)
                    self._b_dev = None
                dt = np.dtype(self._A_full.dtype)
                if self._b_dev is None:
                    self._b_dev = self._residual_comm.put_rows(
                        np.asarray(b, dtype=dt))
                x_dev = self._residual_comm.put_rows(
                    np.asarray(x_full, dtype=dt))
                args = (*self._A_full.device_arrays(), self._b_dev, x_dev)
                out = self._residual_prog(*args)
                _telemetry.record_program_dispatch(RESIDUAL_PROGRAM_KIND)
                return float(np.sqrt(max(0.0, float(out))))
            except (DeviceExecutionError, _faults.XlaRuntimeError):
                lost = _faults.lost_devices()
                if attempt or not lost:
                    raise
                import jax
                survivors = [d for d in jax.devices()
                             if int(d.id) not in lost]
                if not survivors:
                    raise
                with _telemetry.span("resilient.shrink",
                                     what="multisplit_residual",
                                     old_devices=self._residual_comm.size,
                                     new_devices=len(survivors)):
                    self._residual_comm = DeviceComm(devices=survivors)
                    self._A_full = None
                    self._residual_prog = None
                    self._b_dev = None
        raise AssertionError("unreachable")

    # ------------------------------------------------------- block worker
    def _block_worker(self, st: _BlockState, bound: int):
        exch = self._exchange
        registry = _metrics.registry
        try:
            while not self._stop.is_set() and st.steps < self.max_outer:
                # simulated per-device latency (comm.delay timing fault:
                # seeded jitter or a sticky slow device) — the straggler
                # the async tier absorbs as staleness
                d = _faults.delay_seconds("comm.delay",
                                          device=st.device_id)
                if d > 0:
                    time.sleep(d)
                reads = exch.read_all(st.index, st.version)
                for r in reads.values():
                    registry.histogram("multisplit.stale_age").observe(r.age)
                    st.max_age = max(st.max_age, r.age)
                over = check_staleness_bound(reads, bound)
                if over:
                    # bounded-staleness supervisor: partners over the
                    # bound force a resync — wait (bounded) until each
                    # catches up to within the bound or is marked lost
                    st.resyncs += 1
                    registry.counter("multisplit.resyncs").inc()
                    floor = max(1, st.version - bound)
                    for nb in over:
                        exch.wait_for(nb, floor,
                                      timeout=self.resync_timeout)
                    reads = exch.read_all(st.index, st.version)
                try:
                    self._inner_step(st, reads)
                except (DeviceExecutionError,
                        _faults.XlaRuntimeError) as exc:
                    if not self._block_device_lost(st, exc):
                        self._worker_error = exc
                        return
                    self._rehome(st)
                    continue
                v = exch.publish(st.index, st.x.copy())
                if v is not None:
                    st.version = v
                st.steps += 1
                registry.counter("multisplit.step").inc(
                    label=f"block{st.index}")
        finally:
            exch.kick()        # wake the supervisor for a final look

    def _inner_step(self, st: _BlockState, reads):
        """One outer step: stale boundary coupling on the host, inner
        solve of ``A_ii x_i = b_i - A_off x_stale`` on the block's
        device (program kind ``multisplit_block`` — the inner KSP's
        compiled plan, contracts.py pins its reduce-site chain)."""
        x_stale = np.zeros(self.n, dtype=st.b_local.dtype)
        for nb, r in reads.items():
            if r.payload is not None:
                o = self._blocks[nb]
                x_stale[o.rstart:o.rend] = r.payload
        x_stale[st.rstart:st.rend] = st.x
        rhs = st.b_local - st.A_off.dot(x_stale)
        # Two-stage forcing term: the inner target must be relative to
        # the WARM-START residual ``rhs - A_ii x_i``, not to ||rhs||
        # (the KSP default). ||rhs|| converges to a nonzero constant as
        # the outer iteration converges, so an ||rhs||-relative inner
        # tolerance floors the outer error at inner_rtol — the inner
        # solve would accept the warm start unchanged and every block
        # would stall at ~1e-2. Contracting the inner residual by
        # inner_rtol each outer step keeps the two-stage iteration a
        # contraction all the way to the outer tolerance.
        r0 = float(np.linalg.norm(rhs - st.A_diag.dot(st.x)))
        if r0 == 0.0:
            return                     # block already exact for this rhs
        bvec = Vec.from_global(st.comm, rhs)
        xvec = Vec.from_global(st.comm, st.x)
        st.ksp.solve(bvec, xvec, _rtol=0.0, _atol=self.inner_rtol * r0)
        st.x = xvec.to_numpy()[: st.rend - st.rstart]

    @staticmethod
    def _block_device_lost(st: _BlockState, exc) -> bool:
        """Is this failure the persistent-loss signature for the block's
        device (vs a transient/other error the solve must surface)?"""
        lost = _faults.lost_devices()
        if st.device_id in lost:
            return True
        dev = _faults.device_from_error(exc)
        return dev is not None and dev in lost

    def _rehome(self, st: _BlockState):
        """Degrade-then-re-home after ``device.lost``: freeze the block
        at its last exchanged version (survivors keep iterating against
        it — mark_lost), rebuild the block on a survivor device, restore
        the iterate FROM the frozen version, and resume publishing from
        that same version (republish) — the never-iteration-0 contract
        the chaos drill asserts."""
        import jax
        exch = self._exchange
        exch.mark_lost(st.index)
        st.lost_count += 1
        _metrics.registry.counter("multisplit.block_lost").inc()
        last = exch.latest(st.index)
        lost_ids = _faults.lost_devices()
        survivors = [d for d in jax.devices()
                     if int(d.id) not in lost_ids]
        if not survivors:
            raise DeviceExecutionError(
                "multisplit re-home", RuntimeError(
                    "UNAVAILABLE: every device is lost — no survivor "
                    "can adopt the block"))
        with _telemetry.span("resilient.shrink", block=st.index,
                             old_device=st.device_id):
            device = survivors[st.index % len(survivors)]
            self._place_block(st, device)
            if last.payload is not None:
                st.x = np.array(last.payload, dtype=st.x.dtype)
            exch.republish(st.index, st.x.copy())
            st.version = max(st.version, last.version)
