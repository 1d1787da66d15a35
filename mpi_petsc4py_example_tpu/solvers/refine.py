"""Mixed-precision iterative refinement — the fp64 story on TPU.

TPU v5e has no native f64 MXU; f64 arithmetic is emulated and slow
(SURVEY.md §7.3). The TPU-native answer: run the Krylov iteration in a LOW
precision on device (fast path) inside an fp64 outer refinement loop — the
classic Wilkinson scheme. Each outer step computes the true fp64 residual
``r = b - A·x`` (host CSR via the native toolkit, or fp64 device SpMV),
solves the low-precision correction system ``A δ = r`` with any KSP/PC
combination, and accumulates ``x += δ`` in fp64. For well-conditioned
systems a handful of corrections reach full fp64 backward error at
low-precision speed.

PR 10 makes the inner precision a first-class axis
(``-ksp_inner_precision {bf16,f32,f64}``): the inner operator/PC/iterate
channel is stored at the chosen precision — bf16 halves the bytes every
inner iterate moves vs f32, and quarters them vs f64 — while the inner
Krylov's reductions accumulate in f32 (the mixed-precision plans of
solvers/cg_plans) and the OUTER loop stays fp64 end to end, so the final
accuracy contract (``rtol`` against the fp64 residual) is unchanged. bf16
inner solves converge to ~bf16 resolution per correction, so they take
more (cheap) outer steps — the per-step ``inner_rtol`` is floored at a
few storage epsilons to keep a too-tight target from spinning the inner
loop against precision it cannot resolve.
"""

from __future__ import annotations

import time

import numpy as np

from ..core.mat import Mat
from ..core.vec import Vec
from ..parallel.mesh import as_comm
from ..telemetry import spans as _telemetry
from ..utils.convergence import ConvergedReason, SolveResult
from ..utils.dtypes import inner_precision_dtype, real_eps
from ..utils.options import global_options
from .ksp import KSP

#: tightest per-correction inner target the storage precision can
#: resolve: a handful of eps (bf16 ~3e-2, f32 ~5e-7)
_INNER_RTOL_FLOOR_EPS = 4.0


class RefinedKSP:
    """KSP-shaped mixed-precision solver: low-precision inner Krylov
    (``-ksp_inner_precision`` — bf16/f32/f64, default f32), fp64 outer
    refinement.

    Usage matches KSP; ``set_operators`` takes the fp64 CSR (scipy matrix
    or triple) so both precisions of the operator can be built, plus an
    optional pre-built device operator (``inner_op`` — e.g. a
    ``StencilPoisson3D`` constructed at the inner dtype) for matrix-free
    stencils, where the scipy matrix serves only the exact fp64 residual.
    """

    def __init__(self, comm=None):
        self.comm = as_comm(comm) if comm is not None else None
        self.inner = KSP(self.comm)
        self.inner_rtol = 1e-6
        self.rtol = 1e-12
        self.atol = 0.0
        self.max_refine = 20
        self.inner_precision = "f32"
        self.megasolve = False        # -ksp_megasolve: run the whole
                                      # refinement recurrence — inner
                                      # low-precision solve, fp64 true
                                      # residual, correction AXPY, exit
                                      # verification — as ONE fused
                                      # device program
                                      # (solvers/megasolve.py): one
                                      # dispatch per solve instead of
                                      # one launch per outer step (plus
                                      # the per-step host round-trips:
                                      # placements, fetches, the host
                                      # fp64 residual SpMV)
        self._A_host = None
        self._mat_lp: Mat | None = None
        self._inner_op = None
        self._outer_op = None         # explicit fp64 device operator
        self._mat_outer: Mat | None = None   # lazily built from A_host
        self.result = SolveResult()

    def create(self, comm=None):
        self.comm = as_comm(comm)
        self.inner.create(self.comm)
        return self

    # ---- precision axis ----------------------------------------------------
    def set_inner_precision(self, precision: str):
        """Choose the inner storage precision (``bf16``/``f32``/``f64``).
        Must be called before :meth:`set_operators` (the inner operator is
        built at this dtype), or re-call ``set_operators`` after."""
        inner_precision_dtype(precision)     # validate the spelling
        self.inner_precision = str(precision).lower()
        return self

    setInnerPrecision = set_inner_precision

    @property
    def inner_dtype(self) -> np.dtype:
        """The inner storage dtype of the current precision setting."""
        return inner_precision_dtype(self.inner_precision)

    def set_from_options(self):
        """Apply the options DB: ``-ksp_inner_precision``,
        ``-ksp_refine_max`` (outer-step cap) and
        ``-ksp_refine_inner_rtol`` (per-correction inner target), then the
        inner KSP's own flags (``-ksp_type``, ``-pc_type``, ...)."""
        opt = global_options()
        p = self.inner._prefix
        ip = opt.get_string(p + "ksp_inner_precision")
        if ip:
            self.set_inner_precision(ip)
        self.max_refine = opt.get_int(p + "ksp_refine_max", self.max_refine)
        self.inner_rtol = opt.get_real(p + "ksp_refine_inner_rtol",
                                       self.inner_rtol)
        self.megasolve = opt.get_bool(p + "ksp_megasolve", self.megasolve)
        self.inner.set_from_options()
        # the inner KSP must NOT also route through megasolve: the
        # refinement loop is fused HERE (a fused inner would nest two
        # verification loops and double-count the outer recurrence)
        self.inner.megasolve = False
        return self

    setFromOptions = set_from_options

    def set_operators(self, A_scipy, inner_op=None, outer_op=None):
        """``A_scipy``: fp64 scipy sparse matrix (kept for exact
        residuals). ``inner_op``: optional device operator already built
        at the inner precision (matrix-free stencils); defaults to an
        assembled Mat at :attr:`inner_dtype`. ``outer_op``: optional
        fp64 DEVICE operator for the fused megasolve path's in-program
        exact residual (must share ``inner_op``'s layout — e.g. the same
        stencil built at fp64); without one, an fp64 device Mat is
        assembled from ``A_scipy`` lazily when ``-ksp_megasolve`` routes
        a solve through the fused program (custom inner operators with
        no fp64 twin fall back to the unfused host loop)."""
        A = A_scipy.tocsr()
        self._A_host = A
        self._mat_outer = None        # rebuilt lazily for the new A
        self._outer_op = outer_op
        if self.comm is None:
            self.create(None)
        if inner_op is not None:
            self._inner_op = inner_op
            self._mat_lp = None
            self.inner.set_operators(inner_op)
        else:
            self._mat_lp = Mat.from_scipy(self.comm, A,
                                          dtype=self.inner_dtype)
            self._inner_op = self._mat_lp
            self.inner.set_operators(self._mat_lp)
        return self

    def set_type(self, t):
        self.inner.set_type(t)
        return self

    def get_pc(self):
        return self.inner.get_pc()

    def set_tolerances(self, rtol=None, atol=None, max_refine=None,
                       inner_rtol=None):
        if rtol is not None:
            self.rtol = float(rtol)
        if atol is not None:
            self.atol = float(atol)
        if max_refine is not None:
            self.max_refine = int(max_refine)
        if inner_rtol is not None:
            self.inner_rtol = float(inner_rtol)
        return self

    # ---- the Wilkinson loop ------------------------------------------------
    def _arm_inner_guards(self):
        """Pipelined CG's u/w recurrence drift scales with the STORAGE
        epsilon — at bf16 it can overwhelm the per-correction target
        outright (measured: divergence on a 24² Poisson without the
        bound). When the inner type is pipecg on sub-f32 storage and no
        replacement is armed, default the designed drift bound
        (``-ksp_pipeline_auto_replacement``) on the inner KSP."""
        from ..utils.dtypes import is_low_precision
        if (self.inner.get_type() == "pipecg"
                and is_low_precision(self.inner_dtype)
                and self.inner.residual_replacement == 0
                and self.inner.pipeline_auto_replacement == 0):
            self.inner.pipeline_auto_replacement = 25
        if (self.inner.get_type() == "sstep"
                and self.inner.residual_replacement == 0
                and self.inner.sstep_auto_replacement == 0):
            # the CA-CG basis-stall gate, armed at EVERY inner
            # precision: the monomial basis' conditioning (~kappa^(s/2))
            # can exceed the inner storage resolution outright, stalling
            # the correction solves — the gate restarts the basis from
            # the true residual and, past -ksp_sstep_max_replacements,
            # demotes the inner solve to classic CG so refinement always
            # completes (measured: f32 inner sstep on the kappa~n^2
            # tridiagonal family stalls without it)
            self.inner.sstep_auto_replacement = 25

    def _effective_inner_rtol(self) -> float:
        """The per-correction target the inner solve actually runs at:
        ``inner_rtol`` floored at a few STORAGE epsilons — a bf16 inner
        CG asked for 1e-6 would spin max_it against resolution it does
        not have; the outer fp64 loop supplies the remaining digits."""
        floor = _INNER_RTOL_FLOOR_EPS * real_eps(self.inner_dtype)
        return max(self.inner_rtol, floor)

    # ---- megasolve: the fused one-dispatch refinement path -----------------
    #: inner per-correction iteration cap — the same 20000 the unfused
    #: host loop sets on the inner KSP (set_tolerances in _solve_impl)
    _INNER_MAX_IT = 20000

    def _outer_operator(self):
        """The fp64 DEVICE operator the fused program's exact-residual
        channel applies: the explicit ``outer_op``, the inner Mat itself
        when the inner precision is already fp64 (shared-operand
        program), or an fp64 Mat assembled lazily from the host CSR.
        ``None`` for custom inner operators without an fp64 twin — the
        solve then falls back to the unfused host loop."""
        if self._outer_op is not None:
            return self._outer_op
        if self._mat_lp is None:
            return None
        if self.inner_dtype == np.dtype(np.float64):
            return self._mat_lp
        if self._mat_outer is None:
            self._mat_outer = Mat.from_scipy(self.comm, self._A_host,
                                             dtype=np.float64)
        return self._mat_outer

    def _megasolve_available(self, many: bool = False) -> bool:
        """Route through the fused whole-solve program? Mirrors
        KSP._megasolve_eligible: configurations without a fused
        equivalent — including, for the block form, PCs without a
        batched apply — fall back to the unfused host loop silently."""
        if not self.megasolve or self._inner_op is None:
            return False
        nullspace = getattr(self._inner_op, "nullspace", None)
        if nullspace is not None and getattr(nullspace, "dim", 0) > 0:
            return False              # no fused projection exists —
            #                           the unfused inner solves project
        ksp = self.inner
        if ksp._monitors or ksp._monitor_flag or hasattr(ksp, "_history"):
            return False
        if ksp._norm_type != "default" or ksp.unroll != 1:
            return False
        from .megasolve import megasolve_supported
        if not megasolve_supported(ksp.get_type(), ksp.get_pc(),
                                   self._inner_op,
                                   nrhs=2 if many else None):
            return False
        return self._outer_operator() is not None

    def _solve_fused_impl(self, b):
        """ONE dispatch from refinement loop to verified answer: the
        whole Wilkinson recurrence — storage-eps-floored inner targets
        preserved — runs as the fused program's outer ``while_loop``,
        with the fp64 true residual as the exit gate
        (solvers/megasolve.py). Results mirror :meth:`_solve_impl`."""
        from ..resilience import faults as _faults
        from ..utils.convergence import ConvergedReason as _CR
        from ..utils.dtypes import tolerance_dtype
        from .krylov import donation_supported
        from .megasolve import build_megasolve_program
        import jax
        import jax.numpy as jnp
        ksp = self.inner
        op = self._inner_op
        outer = self._outer_operator()
        comm = op.comm
        b = np.asarray(b, dtype=np.float64)
        _faults.check("ksp.solve")
        ksp._check_guard()
        with _telemetry.span("ksp.setup"):
            ksp.set_up()
        pc = ksp.get_pc()
        self._arm_inner_guards()
        op_dt = np.dtype(op.dtype)
        guard = ksp._guard_requested()
        cs_args, abft_pc_on = ((), False)
        if guard:
            cs_args, abft_pc_on = ksp._guard_checksums(op, pc, op_dt)
        with _telemetry.span("ksp.setup"):
            prog = build_megasolve_program(
                comm, ksp.get_type(), pc, op,
                None if outer is op else outer, zero_guess=True,
                abft=guard and ksp.abft, abft_pc=abft_pc_on,
                rr=guard and ksp._effective_replacement() > 0,
                donate=True, sstep_s=ksp.sstep_s)
        dt_in = tolerance_dtype(op_dt)
        dt_out = np.dtype(np.float64)
        guard_scalars = ((dt_in.type(ksp.abft_tol),
                          np.int32(ksp._effective_replacement()))
                         if guard else ())
        if guard and ksp.get_type() == "sstep":
            guard_scalars += (np.int32(ksp.sstep_max_replacements),)
        xvec = Vec.from_global(comm, np.zeros_like(b), dtype=np.float64,
                               layout=outer.layout)
        bvec = Vec.from_global(comm, b, dtype=np.float64,
                               layout=outer.layout)
        x0d = (jnp.array(xvec.data) if donation_supported()
               else xvec.data)
        op_args = (() if outer is op else (outer.device_arrays(),)) \
            + (op.device_arrays(), pc.device_arrays()) + tuple(cs_args)
        fault = _faults.triggered("ksp.program")
        if fault is None:
            fault = _faults.mesh_fault("device.lost", comm.device_ids)
        if fault is not None:
            raise fault.error()
        t0 = time.perf_counter()
        with _telemetry.span("ksp.dispatch"):
            _telemetry.record_program_dispatch("megasolve")
            out = prog(*op_args, bvec.data, x0d,
                       dt_out.type(self.rtol), dt_out.type(self.atol),
                       dt_in.type(self._effective_inner_rtol()),
                       dt_in.type(ksp.divtol),
                       np.int32(self._INNER_MAX_IT),
                       np.int32(self.max_refine),
                       # stagnation reports DIVERGED_BREAKDOWN — the
                       # unfused Wilkinson loop's exact semantics
                       np.int32(_CR.DIVERGED_BREAKDOWN), *guard_scalars)
        xvec.data = out[0]
        with _telemetry.span("ksp.fetch"):
            fetch = jax.device_get(tuple(out[1:5])
                                   + (tuple(out[5:7]) if guard else ()))
        from ..utils.profiling import record_sync
        record_sync("KSP result fetch/solve")
        steps, iters = int(fetch[0]), int(fetch[1])
        rnorm, reason = float(fetch[2]), int(fetch[3])
        wall = time.perf_counter() - t0
        if guard:
            det, rrc = int(fetch[4]), int(fetch[5])
            checks = ((steps + iters * (1 + int(abft_pc_on)))
                      if ksp.abft else 0)
            from ..utils.profiling import record_sdc
            from ..utils.errors import SilentCorruptionError
            from .krylov import SDC_DEMOTE, SDC_DETECTOR_NAMES, SDC_NONE
            if det == SDC_DEMOTE:
                # CA-CG demotion inside the fused refinement: not
                # corruption — rerun through the UNFUSED loop, whose
                # inner solves demote to classic CG per correction
                # (KSP._demote_sstep)
                record_sdc(checks, 0, rrc)
                return self._solve_impl(b, _no_fuse=True)
            if det != SDC_NONE:
                record_sdc(checks, 1, rrc)
                raise SilentCorruptionError(
                    "KSPSolve", SDC_DETECTOR_NAMES.get(det, f"det{det}"),
                    iters,
                    detail=f"detected inside the fused refinement loop "
                           f"at outer step {steps} ({rrc} "
                           "replacement(s) passed)")
            record_sdc(checks, 0, rrc)
        fault = _faults.triggered("ksp.result")
        if fault is not None:
            rnorm = float("nan") if fault.kind == "nan" else float("inf")
        if not np.isfinite(rnorm):
            reason = _CR.DIVERGED_NANORINF
        self.refine_steps = steps
        self.result = SolveResult(iters, rnorm, int(reason), wall)
        from ..utils.profiling import record_event
        record_event(f"RefinedKSP({ksp.get_type()}+{pc.get_type()}+mega,"
                     f"{self.inner_precision})", op.shape[0], iters, wall,
                     int(reason))
        return xvec.to_numpy(), self.result

    def _solve_many_fused_impl(self, B):
        """Fused block refinement: the whole ``(n, nrhs)`` block's outer
        recurrence in ONE launch, per-column masked freezing at both
        loop levels. Results mirror :meth:`_solve_many_impl` (aggregate
        inner-iteration count, worst column's final residual)."""
        from ..resilience import faults as _faults
        from ..utils.convergence import ConvergedReason as _CR
        from ..utils.dtypes import tolerance_dtype
        from .krylov import donation_supported
        from .megasolve import build_megasolve_program_many
        import jax
        import jax.numpy as jnp
        ksp = self.inner
        op = self._inner_op
        outer = self._outer_operator()
        comm = op.comm
        B = np.asarray(B, dtype=np.float64)
        if B.ndim != 2:
            raise ValueError(f"solve_many needs an (n, nrhs) block, got "
                             f"{B.shape}")
        k = int(B.shape[1])
        _faults.check("ksp.solve")
        ksp._check_guard()
        with _telemetry.span("ksp.setup"):
            ksp.set_up()
        pc = ksp.get_pc()
        self._arm_inner_guards()
        op_dt = np.dtype(op.dtype)
        guard = ksp._guard_requested()
        cs_args, abft_pc_on = ((), False)
        if guard:
            cs_args, abft_pc_on = ksp._guard_checksums(op, pc, op_dt)
        with _telemetry.span("ksp.setup"):
            prog = build_megasolve_program_many(
                comm, ksp.get_type(), pc, op,
                None if outer is op else outer, nrhs=k, zero_guess=True,
                abft=guard and ksp.abft, abft_pc=abft_pc_on,
                rr=guard and ksp._effective_replacement() > 0,
                donate=True, sstep_s=ksp.sstep_s)
        dt_in = tolerance_dtype(op_dt)
        dt_out = np.dtype(np.float64)
        guard_scalars = ((dt_in.type(ksp.abft_tol),
                          np.int32(ksp._effective_replacement()))
                         if guard else ())
        if guard and ksp.get_type() == "sstep":
            guard_scalars += (np.int32(ksp.sstep_max_replacements),)
        Bd, Xd0 = comm.put_rows_many([B, np.zeros_like(B)])
        if donation_supported():
            Xd0 = jnp.array(Xd0)
        op_args = (() if outer is op else (outer.device_arrays(),)) \
            + (op.device_arrays(), pc.device_arrays()) + tuple(cs_args)
        fault = _faults.triggered("ksp.program")
        if fault is None:
            fault = _faults.mesh_fault("device.lost", comm.device_ids)
        if fault is not None:
            raise fault.error()
        t0 = time.perf_counter()
        with _telemetry.span("ksp.dispatch"):
            _telemetry.record_program_dispatch("megasolve_many")
            out = prog(*op_args, Bd, Xd0,
                       dt_out.type(self.rtol), dt_out.type(self.atol),
                       dt_in.type(self._effective_inner_rtol()),
                       dt_in.type(ksp.divtol),
                       np.int32(self._INNER_MAX_IT),
                       np.int32(self.max_refine),
                       np.int32(_CR.DIVERGED_BREAKDOWN), *guard_scalars)
        with _telemetry.span("ksp.fetch"):
            fetch = jax.device_get(tuple(out[:5])
                                   + (tuple(out[5:7]) if guard else ()))
        from ..utils.profiling import record_sync
        record_sync("KSP solve_many result fetch")
        n = op.shape[0]
        X = np.asarray(fetch[0])[:n].astype(np.float64, copy=False)
        steps = int(fetch[1])
        iters = np.asarray(fetch[2])
        rn = np.asarray(fetch[3], dtype=float)
        reasons = np.asarray(fetch[4])
        wall = time.perf_counter() - t0
        if guard:
            det_h = np.asarray(fetch[5])
            rrc_h = np.asarray(fetch[6])
            checks = ((k * steps + int(iters.sum())
                       * (1 + int(abft_pc_on))) if ksp.abft else 0)
            from ..utils.profiling import record_sdc
            from ..utils.errors import SilentCorruptionError
            from .krylov import SDC_DEMOTE, SDC_DETECTOR_NAMES, SDC_NONE
            bad = [j for j in range(k)
                   if int(det_h[j]) not in (SDC_NONE, SDC_DEMOTE)]
            if bad:
                record_sdc(checks, len(bad), int(rrc_h.sum()))
                raise SilentCorruptionError(
                    "KSPSolveMany",
                    SDC_DETECTOR_NAMES.get(int(det_h[bad[0]]),
                                           str(int(det_h[bad[0]]))),
                    int(iters.max(initial=0)),
                    detail=f"columns {bad} flagged inside the fused "
                           "refinement loop")
            if any(int(det_h[j]) == SDC_DEMOTE for j in range(k)):
                # CA-CG demotion: rerun the block unfused (see the
                # single-RHS twin)
                record_sdc(checks, 0, int(rrc_h.sum()))
                return self._solve_many_impl(B, _no_fuse=True)
            record_sdc(checks, 0, int(rrc_h.sum()))
        conv = np.isfinite(rn) & np.asarray(
            [int(r) > 0 for r in reasons])
        if bool(conv.all()):
            reason = _CR.CONVERGED_RTOL
        elif not np.all(np.isfinite(rn)):
            reason = _CR.DIVERGED_NANORINF
        elif all(int(r) == _CR.DIVERGED_BREAKDOWN
                 for r in reasons[~conv]):
            reason = _CR.DIVERGED_BREAKDOWN
        else:
            reason = _CR.DIVERGED_MAX_IT
        self.refine_steps = steps
        self.result = SolveResult(int(iters.max(initial=0)),
                                  float(rn.max(initial=0.0)),
                                  int(reason), wall)
        from ..utils.profiling import record_event
        record_event(f"RefinedKSP({ksp.get_type()}+{pc.get_type()}+mega,"
                     f"{self.inner_precision},k={k})", n,
                     self.result.iterations, wall, int(reason))
        return X, self.result

    def solve(self, b: np.ndarray) -> tuple[np.ndarray, SolveResult]:
        """Solve A x = b (fp64 in/out). Returns (x, result)."""
        A = self._A_host
        if A is None:
            raise RuntimeError("RefinedKSP.solve: no operators set")
        with _telemetry.span("refine.outer",
                             inner_precision=self.inner_precision,
                             ksp_type=self.inner.get_type(),
                             n=int(A.shape[0]), rtol=self.rtol) as osp:
            x, res = self._solve_impl(b)
            osp.set_attrs(refine_steps=self.refine_steps,
                          inner_iterations=res.iterations,
                          reason=res.reason)
            return x, res

    def _solve_impl(self, b: np.ndarray,
                    _no_fuse: bool = False) -> tuple[np.ndarray,
                                                     SolveResult]:
        if not _no_fuse and self._megasolve_available():
            return self._solve_fused_impl(b)
        A = self._A_host
        b = np.asarray(b, dtype=np.float64)
        bnorm = np.linalg.norm(b)
        tol = max(self.rtol * bnorm, self.atol)
        x = np.zeros_like(b)
        # low-precision inner solver on the correction equation
        self.inner.set_tolerances(rtol=self._effective_inner_rtol(),
                                  max_it=20000)
        self._arm_inner_guards()
        op_dt = np.dtype(self._inner_op.dtype)
        dx, rv = self._inner_op.get_vecs()

        t0 = time.perf_counter()
        total_inner = 0
        # ONE exact fp64 residual per outer step: the end-of-step
        # residual both decides convergence/stagnation AND feeds the
        # next correction (recomputing it at the loop top would double
        # the dominant host-side SpMV cost of the outer loop)
        r = b - A @ x
        rnorm = np.linalg.norm(r)
        reason = ConvergedReason.DIVERGED_MAX_IT
        it = 0

        def _conv(rn):
            return (ConvergedReason.CONVERGED_ATOL if rn <= self.atol
                    else ConvergedReason.CONVERGED_RTOL)

        if rnorm <= tol:
            reason = _conv(rnorm)
        else:
            for it in range(1, self.max_refine + 1):
                with _telemetry.span("refine.step", step=it) as ssp:
                    rv.set_global(r.astype(op_dt))
                    res = self.inner.solve(rv, dx)
                    total_inner += res.iterations
                    x = x + dx.to_numpy().astype(np.float64)
                    r = b - A @ x
                    r_new = np.linalg.norm(r)
                    ssp.set_attrs(inner_iterations=res.iterations,
                                  rnorm=float(r_new))
                # checked AFTER the correction, so a solve that lands on
                # tolerance at the max_refine-th step reports CONVERGED
                if r_new <= tol:
                    rnorm = r_new
                    reason = _conv(r_new)
                    break
                # stagnation guard: the inner precision can't represent
                # corrections below ~eps of the iterate; if the residual
                # stops improving, stop.
                if r_new >= 0.9 * rnorm:
                    rnorm = r_new
                    reason = ConvergedReason.DIVERGED_BREAKDOWN
                    break
                rnorm = r_new
        wall = time.perf_counter() - t0
        # how many fp64 outer corrections the inner-iteration total
        # splits across
        self.refine_steps = it
        self.result = SolveResult(total_inner, float(rnorm), int(reason),
                                  wall)
        return x, self.result

    def solve_many(self, B: np.ndarray) -> tuple[np.ndarray, SolveResult]:
        """Block refinement: solve ``A X = B`` for an fp64 ``(n, nrhs)``
        block. Each outer step computes the whole block's exact fp64
        residual and dispatches ONE low-precision ``KSP.solve_many``
        correction launch (the PR-4/PR-6 batched CG kernels — collective
        count independent of nrhs, all columns riding the inner precision
        plan). Columns that already meet tolerance contribute zero
        residual and freeze instantly under the masked batched kernel.
        Returns ``(X, result)`` with aggregate inner-iteration count and
        the worst column's final residual norm."""
        A = self._A_host
        if A is None:
            raise RuntimeError("RefinedKSP.solve_many: no operators set")
        with _telemetry.span("refine.outer",
                             inner_precision=self.inner_precision,
                             ksp_type=self.inner.get_type(),
                             n=int(A.shape[0]), rtol=self.rtol) as osp:
            X, res = self._solve_many_impl(B)
            osp.set_attrs(refine_steps=self.refine_steps,
                          inner_iterations=res.iterations,
                          reason=res.reason, nrhs=int(X.shape[1]))
            return X, res

    def _solve_many_impl(self, B, _no_fuse=False):
        if not _no_fuse and self._megasolve_available(many=True):
            return self._solve_many_fused_impl(B)
        A = self._A_host
        B = np.asarray(B, dtype=np.float64)
        if B.ndim != 2:
            raise ValueError(f"solve_many needs an (n, nrhs) block, got "
                             f"{B.shape}")
        bnorm = np.linalg.norm(B, axis=0)
        tol = np.maximum(self.rtol * bnorm, self.atol)
        X = np.zeros_like(B)
        self.inner.set_tolerances(rtol=self._effective_inner_rtol(),
                                  max_it=20000)
        self._arm_inner_guards()
        op_dt = np.dtype(self._inner_op.dtype)

        t0 = time.perf_counter()
        total_inner = 0
        # one fp64 block residual per outer step (see solve): it decides
        # convergence/stagnation and feeds the next correction block
        R = B - A @ X
        rnorm = np.linalg.norm(R, axis=0)
        reason = ConvergedReason.DIVERGED_MAX_IT
        it = 0
        if np.all(rnorm <= tol):
            reason = ConvergedReason.CONVERGED_RTOL
        else:
            for it in range(1, self.max_refine + 1):
                res = self.inner.solve_many(R.astype(op_dt))
                total_inner += int(max(res.iterations, default=0))
                X = X + np.asarray(res.X, dtype=np.float64)
                R = B - A @ X
                r_new = np.linalg.norm(R, axis=0)
                if np.all(r_new <= tol):   # post-correction check: a
                    rnorm = r_new          # last-step landing CONVERGES
                    reason = ConvergedReason.CONVERGED_RTOL
                    break
                if np.all(r_new >= 0.9 * np.maximum(rnorm, 1e-300)):
                    rnorm = r_new
                    reason = ConvergedReason.DIVERGED_BREAKDOWN
                    break
                rnorm = r_new
        wall = time.perf_counter() - t0
        self.refine_steps = it
        self.result = SolveResult(total_inner, float(rnorm.max(initial=0.0)),
                                  int(reason), wall)
        return X, self.result

    # ---- legacy spelling ---------------------------------------------------
    @property
    def _mat32(self):
        """The inner Mat (historical name from the fp32-only scheme)."""
        return self._mat_lp
