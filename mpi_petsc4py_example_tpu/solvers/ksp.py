"""KSP — Krylov solver object, TPU-native equivalent of PETSc KSP (SURVEY.md N3).

Reference usage (``test.py:33-50``): ``KSP().create(comm)``, ``setType``,
``getPC``, ``setOperators``, ``setFromOptions``, ``setUp``, ``solve(b, x)``.
The same surface is provided here (snake_case canonical, camelCase aliases for
facade/driver compatibility); ``solve`` dispatches to a cached jit-compiled
``shard_map`` program built by :mod:`.krylov`.

Solver types: ``cg``, ``pipecg`` (single-reduction CG), ``fcg``, ``gmres``,
``fgmres``, ``lgmres``, ``bcgs``, ``fbcgs``/``fbcgsr``, ``bcgsl``, ``cgs``,
``tfqmr``, ``cr``, ``gcr``, ``minres``, ``symmlq``, ``chebyshev``, ``bicg``,
``cgne``, ``lsqr``, ``preonly``, ``richardson``. Runtime override via the
options DB: ``-ksp_type``, ``-ksp_rtol``, ``-ksp_atol``, ``-ksp_max_it``,
``-ksp_gmres_restart``, ``-ksp_lgmres_augment``, ``-ksp_bcgsl_ell``,
``-ksp_monitor``, ``-pc_type`` (SURVEY.md §5.6).
"""

from __future__ import annotations

import contextlib
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.mat import Mat
from ..core.vec import Vec
from ..parallel.mesh import as_comm
from ..resilience import abft as _abft_defaults
from ..resilience import faults as _faults
from ..telemetry import spans as _telemetry
from ..utils import aot as _aot
from ..utils.convergence import (BatchedSolveResult, ConvergedReason,
                                 SolveResult)
from ..utils.errors import SilentCorruptionError, wrap_device_errors
from ..utils.options import global_options
from .krylov import (GUARDED_TYPES, KSP_KERNELS, NATURAL_TYPES,
                     SDC_DETECTOR_NAMES, SDC_NONE, build_ksp_program)
from .pc import PC

DEFAULT_RTOL = 1e-5   # PETSc's KSP default
DEFAULT_ATOL = 1e-50
DEFAULT_DIVTOL = 1e5  # PETSc's KSP dtol default (DIVERGED_DTOL trigger)
DEFAULT_MAX_IT = 10000


class KSP:
    """Krylov solver context."""

    def __init__(self, comm=None):
        self.comm = None
        self._type = "gmres"          # PETSc's default KSP type
        self._pc: PC | None = None
        self._mat: Mat | None = None
        self.rtol = DEFAULT_RTOL
        self.atol = DEFAULT_ATOL
        self.divtol = DEFAULT_DIVTOL
        self.max_it = DEFAULT_MAX_IT
        self.restart = 30
        self.lgmres_augment = 2       # -ksp_lgmres_augment (KSPLGMRES aug_k)
        self.bcgsl_ell = 2            # -ksp_bcgsl_ell (KSPBCGSL default)
        self.unroll = 1               # -ksp_unroll: masked steps per loop
                                      # dispatch (results identical). Default
                                      # 1: in-loop iteration dispatch is
                                      # cheap, and per-PROGRAM-CALL latency
                                      # is what unrolling cannot amortize; >1
                                      # also disables the fused stencil-CG
                                      # fast path (krylov.cg_stencil_kernel)
        self.batch_limit = 0          # -ksp_batch_limit: max RHS columns per
                                      # batched solve_many program; 0 = all k
                                      # in one launch. Set it when k resident
                                      # columns overflow the stencil kernel's
                                      # VMEM chunk plan (ops/pallas_stencil
                                      # _pick_chunk ncols) or HBM
        self._norm_type = "default"   # -ksp_norm_type (KSPSetNormType)
        self._monitors = []
        self._monitor_flag = False
        self._view_flag = False       # -ksp_view: print config after solve
        self._reason_flag = False     # -ksp_converged_reason: print after
        self._initial_guess_nonzero = False
        self.abft = False             # -ksp_abft: in-program ABFT checksum
                                      # verification of every operator (and,
                                      # where a PC checksum exists, PC)
                                      # apply — silent-data-corruption
                                      # detection folded into the existing
                                      # reduction phases (zero extra
                                      # collectives; CG only)
        self.abft_tol = _abft_defaults.DEFAULT_ABFT_TOL
                                      # -ksp_abft_tol: detection threshold
                                      # multiplier (x eps x |partials| —
                                      # comfortably above tree-reduction
                                      # rounding, far below any real
                                      # corruption); runtime scalar, no
                                      # recompile on change
        self.residual_replacement = 0  # -ksp_residual_replacement N: every
                                      # N iterations recompute the TRUE
                                      # residual in-program, gate it
                                      # against the recurrence norm (drift
                                      # = detected corruption), replace
                                      # r and promote the iterate to the
                                      # verified rollback target; 0 = off
        self.pipeline_auto_replacement = 0  # -ksp_pipeline_auto_replacement
                                      # N: when KSP 'pipecg' is selected
                                      # and -ksp_residual_replacement is
                                      # unset, arm the true-residual
                                      # replacement every N iterations —
                                      # the standard bound on pipelined
                                      # CG's u/w recurrence drift
                                      # (Ghysels-Vanroose); 0 = off.
                                      # Non-pipelined types ignore it.
        self.sstep_s = 4              # -ksp_sstep_s: s-step CG block size
                                      # (iterations per stacked Gram psum;
                                      # compiled into the program — part
                                      # of the cache key)
        self.sstep_max_replacements = 3  # -ksp_sstep_max_replacements:
                                      # CA-CG drift-restart budget — past
                                      # this many basis restarts the
                                      # solve DEMOTES to classic CG from
                                      # the current iterate (runtime
                                      # scalar, no recompile)
        self.sstep_auto_replacement = 0  # -ksp_sstep_auto_replacement N:
                                      # sstep only — arm the drift gate
                                      # every N iterations when
                                      # -ksp_residual_replacement is
                                      # unset (the CA-CG basis
                                      # ill-conditioning bound); 0 = off
        self.reduction_auto = False   # -ksp_reduction_auto: at setUp,
                                      # pick the reduction plan (cg /
                                      # pipecg / sstep + s) from the
                                      # MEASURED per-reduce-site latency
                                      # probe (solvers/autoselect.py)
        self.reduction_probe_refresh = False  # -ksp_reduction_probe_
                                      # refresh: ignore the on-disk
                                      # probe cache and re-measure
        self.megasolve = False        # -ksp_megasolve: route eligible
                                      # cg/pipecg solves through the
                                      # FUSED whole-solve program
                                      # (solvers/megasolve.py): the
                                      # outer verification/refinement
                                      # recurrence runs as an in-program
                                      # lax.while_loop wrapping the CG
                                      # plan loop, so a solve (or a
                                      # solve_many block) costs exactly
                                      # ONE compiled-program launch and
                                      # the returned iterate's TRUE
                                      # residual met the target by
                                      # construction (the gate's exit
                                      # condition IS the convergence
                                      # test). Ineligible
                                      # configurations (non-CG types,
                                      # nullspace, monitors, norm-type
                                      # overrides, unroll>1) fall
                                      # through to the unfused path.
        self.megasolve_stencil_fastpath = False  # -ksp_megasolve_
                                      # stencil_fastpath: inside the
                                      # fused program, route the INNER
                                      # loop of an eligible stencil
                                      # operator (cg, PC none/jacobi,
                                      # real dtype, unguarded) through
                                      # the Pallas fused-dot kernel
                                      # path (local_matvec_dot) instead
                                      # of the general flat-apply plan
                                      # (megasolve_stencil_supported)
        self._true_residual_check = False  # -ksp_true_residual_check
        self.true_residual_margin = 1.0    # -ksp_true_residual_margin: with
                                      # the gate on, the COMPILED program
                                      # converges to margin*rtol while the
                                      # gate still verifies the true
                                      # residual against rtol itself. A
                                      # margin < 1 buys a guard band
                                      # against recurrence drift: a few
                                      # extra in-loop iterations (~us each)
                                      # instead of a gate re-entry (a full
                                      # ~100 ms program dispatch on remote
                                      # runtimes). 1.0 = exact semantics
        self.result = SolveResult()
        self._prefix = ""
        if comm is not None:
            self.create(comm)

    # ---- lifecycle ---------------------------------------------------------
    def create(self, comm=None):
        self.comm = as_comm(comm)
        self._pc = PC(self.comm)
        return self

    def destroy(self):
        return self

    # ---- configuration (petsc4py-shaped) ------------------------------------
    def set_type(self, ksp_type: str):
        ksp_type = str(ksp_type).lower()
        if ksp_type not in KSP_KERNELS:
            raise ValueError(f"unknown KSP type {ksp_type!r}; "
                             f"available: {sorted(KSP_KERNELS)}")
        self._type = ksp_type
        return self

    setType = set_type

    def get_type(self) -> str:
        return self._type

    getType = get_type

    def get_pc(self) -> PC:
        if self._pc is None:
            self._pc = PC(self.comm)
        return self._pc

    getPC = get_pc

    def set_pc(self, pc: PC):
        self._pc = pc
        return self

    def set_operators(self, A: Mat, P_mat: Mat | None = None):
        self._mat = A
        if self.comm is None:
            self.create(A.comm)
        self.get_pc().set_operators(P_mat if P_mat is not None else A)
        return self

    setOperators = set_operators

    def set_tolerances(self, rtol=None, atol=None, divtol=None, max_it=None):
        if rtol is not None:
            self.rtol = float(rtol)
        if atol is not None:
            self.atol = float(atol)
        if divtol is not None:
            self.divtol = float(divtol)
        if max_it is not None:
            self.max_it = int(max_it)
        return self

    setTolerances = set_tolerances

    def set_true_residual_check(self, flag: bool):
        """Opt-in final TRUE-residual gate (``-ksp_true_residual_check``).

        Krylov recurrences converge on the RECURRENCE norm, which can drift
        from ``||b - A x||`` (PETSc's KSPSetNormType caveat — the reference
        inherits it through [external] KSPSolve); a solve can report
        CONVERGED_RTOL with a true relative residual slightly above rtol
        (measured: BASELINE cfg4's 1.81e-6 vs the 1e-6 target). With this
        flag, the solve program's EPILOGUE computes ``||b - A x||`` and
        ``||b||`` on device (one fused SpMV + two reductions, returned with
        the solve's own result fetch — see krylov.build_ksp_program
        ``true_res``); if the true residual misses ``max(rtol·||b||, atol)``
        the solve re-enters from the current iterate (a fresh recurrence
        STARTS from the true residual) until it passes, up to 3 re-entries.
        The honest case costs ZERO extra program dispatches; default off.
        """
        self._true_residual_check = bool(flag)
        return self

    setTrueResidualCheck = set_true_residual_check

    def set_initial_guess_nonzero(self, flag: bool):
        self._initial_guess_nonzero = bool(flag)
        return self

    setInitialGuessNonzero = set_initial_guess_nonzero

    # Which residual norm each kernel's convergence test monitors. PETSc's
    # KSPSetNormType switches this per solver; here each kernel has one
    # fixed monitoring norm (fused into its compiled recurrence), so setting
    # a matching type is a no-op, 'none' disables the test entirely
    # (KSP_NORM_NONE: fixed max_it iterations, reason CONVERGED_ITS — the
    # smoother configuration), and a mismatched type raises.
    _KERNEL_NORMS = {
        "gmres": "preconditioned", "lgmres": "preconditioned",
        "cr": "preconditioned", "symmlq": "unpreconditioned",
        "preonly": "none",
    }

    # petsc4py's integer KSP.NormType enum values
    _NORM_BY_INT = {-1: "default", 0: "none", 1: "preconditioned",
                    2: "unpreconditioned", 3: "natural"}

    # types whose recurrence already carries a natural-norm scalar
    # (KSP_NORM_NATURAL, PETSc's NormType 3): cg/fcg monitor sqrt <r, M r>,
    # cr monitors sqrt <r̃, A r̃> of its preconditioned residual. Shared
    # with the kernel dispatch so the two lists cannot drift.
    _NATURAL_TYPES = NATURAL_TYPES

    def set_norm_type(self, norm_type):
        if isinstance(norm_type, (int, np.integer)):
            norm_type = self._NORM_BY_INT.get(int(norm_type), norm_type)
        t = str(norm_type).lower().replace("ksp_norm_", "")
        if t not in ("default", "none", "preconditioned",
                     "unpreconditioned", "natural"):
            raise ValueError(f"unknown norm type {norm_type!r}")
        self._norm_type = t
        return self

    setNormType = set_norm_type

    def get_norm_type(self) -> str:
        if self._norm_type != "default":
            return self._norm_type
        return self._KERNEL_NORMS.get(self._type, "unpreconditioned")

    getNormType = get_norm_type

    # restarted solvers advance the counter a full cycle at a time — a
    # fixed-iteration contract can't hold for them (PETSc's KSPSetNormType
    # likewise rejects unsupported combinations)
    _CYCLE_GRANULAR = ("gmres", "fgmres", "lgmres", "bcgsl")

    def _check_norm_type(self):
        t = self._norm_type
        if t == "default":
            return
        if t == "none":
            if self._type in self._CYCLE_GRANULAR:
                raise ValueError(
                    f"norm type 'none' is unavailable for KSP "
                    f"{self._type!r} (iterations advance a whole restart "
                    "cycle — or ell steps for bcgsl — at a time, so a "
                    "fixed max_it contract cannot hold); use richardson/"
                    "chebyshev/cg for fixed-iteration smoothing")
            return
        if t == "natural":
            if self._type not in self._NATURAL_TYPES:
                raise ValueError(
                    f"norm type 'natural' is available for KSP "
                    f"{sorted(self._NATURAL_TYPES)} whose recurrences "
                    f"already carry a natural-norm scalar (cg/fcg: "
                    f"sqrt <r, M r>; cr: sqrt <r̃, A r̃> of the "
                    f"preconditioned residual); {self._type!r} does not — "
                    "use 'default'")
            return
        have = self._KERNEL_NORMS.get(self._type, "unpreconditioned")
        if t != have:
            raise ValueError(
                f"KSP {self._type!r} monitors the {have} residual norm "
                f"(fused into its compiled recurrence); norm type {t!r} is "
                "not available for it — use 'default', 'none', or a solver "
                "whose monitoring norm matches")

    def set_options_prefix(self, prefix: str):
        self._prefix = prefix or ""
        return self

    setOptionsPrefix = set_options_prefix

    def set_monitor(self, cb):
        """``cb(ksp, iteration, rnorm)`` per iteration (-ksp_monitor analog)."""
        self._monitors.append(cb)
        return self

    setMonitor = set_monitor

    def set_convergence_history(self, length: int | None = None,
                                reset: bool = False):
        """KSPSetResidualHistory analog: record the per-iteration residual
        norms of subsequent solves (retrievable via
        :meth:`get_convergence_history`). Like petsc4py, the iteration-0
        initial residual is included; one entry is recorded per convergence
        check — per iteration for most types (``iterations + 1`` entries),
        per restart cycle for the cycle-granular kernels
        (gmres/fgmres/lgmres, and per ℓ-step for bcgsl).

        Implemented through the monitored program variant — enabling it
        recompiles the solver once with the in-loop reporting callback.
        ``reset=False`` (petsc4py's default) accumulates across solves;
        ``reset=True`` clears at each solve. ``length`` truncates and
        defaults to petsc4py's 10000-entry bound (with ``reset=False`` the
        history grows across solves for the KSP's lifetime — unbounded
        would leak on long-running drivers). Calling again replaces the
        history (PETSc semantics), never stacks recorders — the recorder
        lives outside the user-monitor list, so it neither suppresses
        ``-ksp_monitor``'s default printout nor shows up as a user monitor.
        """
        self._history = []
        self._history_length = 10000 if length is None else int(length)
        self._history_reset = bool(reset)
        return self

    setConvergenceHistory = set_convergence_history

    def get_convergence_history(self):
        """The recorded residual norms (numpy array), oldest first."""
        return np.asarray(getattr(self, "_history", []), dtype=float)

    getConvergenceHistory = get_convergence_history

    def set_from_options(self):
        """Apply the global options DB (the reference's ``setFromOptions``)."""
        opt = global_options()
        p = self._prefix
        t = opt.get_string(p + "ksp_type")
        if t:
            self.set_type(t)
        self.rtol = opt.get_real(p + "ksp_rtol", self.rtol)
        self.atol = opt.get_real(p + "ksp_atol", self.atol)
        self.divtol = opt.get_real(p + "ksp_divtol", self.divtol)
        self.max_it = opt.get_int(p + "ksp_max_it", self.max_it)
        self.restart = opt.get_int(p + "ksp_gmres_restart", self.restart)
        self.lgmres_augment = opt.get_int(p + "ksp_lgmres_augment",
                                          self.lgmres_augment)
        self.bcgsl_ell = opt.get_int(p + "ksp_bcgsl_ell", self.bcgsl_ell)
        self.unroll = opt.get_int(p + "ksp_unroll", self.unroll)
        self.batch_limit = opt.get_int(p + "ksp_batch_limit",
                                       self.batch_limit)
        nt = opt.get_string(p + "ksp_norm_type")
        if nt:
            self.set_norm_type(nt)
        self.megasolve = opt.get_bool(p + "ksp_megasolve", self.megasolve)
        self.megasolve_stencil_fastpath = opt.get_bool(
            p + "ksp_megasolve_stencil_fastpath",
            self.megasolve_stencil_fastpath)
        self._true_residual_check = opt.get_bool(
            p + "ksp_true_residual_check", self._true_residual_check)
        self.true_residual_margin = opt.get_real(
            p + "ksp_true_residual_margin", self.true_residual_margin)
        self.abft = opt.get_bool(p + "ksp_abft", self.abft)
        self.abft_tol = opt.get_real(p + "ksp_abft_tol", self.abft_tol)
        self.residual_replacement = opt.get_int(
            p + "ksp_residual_replacement", self.residual_replacement)
        self.pipeline_auto_replacement = opt.get_int(
            p + "ksp_pipeline_auto_replacement",
            self.pipeline_auto_replacement)
        self.sstep_s = opt.get_int(p + "ksp_sstep_s", self.sstep_s)
        self.sstep_max_replacements = opt.get_int(
            p + "ksp_sstep_max_replacements", self.sstep_max_replacements)
        self.sstep_auto_replacement = opt.get_int(
            p + "ksp_sstep_auto_replacement", self.sstep_auto_replacement)
        self.reduction_auto = opt.get_bool(p + "ksp_reduction_auto",
                                           self.reduction_auto)
        self.reduction_probe_refresh = opt.get_bool(
            p + "ksp_reduction_probe_refresh",
            self.reduction_probe_refresh)
        self._monitor_flag = opt.get_bool(p + "ksp_monitor", False)
        self._view_flag = opt.get_bool(p + "ksp_view", False)
        self._reason_flag = opt.get_bool(p + "ksp_converged_reason", False)
        pct = opt.get_string(p + "pc_type")
        if pct:
            self.get_pc().set_type(pct)
        fst = opt.get_string(p + "pc_factor_mat_solver_type")
        if fst:
            self.get_pc().set_factor_solver_type(fst)
        pc = self.get_pc()
        pc.sor_omega = opt.get_real(p + "pc_sor_omega", pc.sor_omega)
        pc.asm_overlap = opt.get_int(p + "pc_asm_overlap", pc.asm_overlap)
        pc.factor_fill = opt.get_real(p + "pc_factor_fill", pc.factor_fill)
        pc.gamg_threshold = opt.get_real(p + "pc_gamg_threshold",
                                         pc.gamg_threshold)
        pc.gamg_coarse_size = opt.get_int(p + "pc_gamg_coarse_eq_limit",
                                          pc.gamg_coarse_size)
        pc.gamg_max_levels = opt.get_int(p + "pc_mg_levels",
                                         pc.gamg_max_levels)
        mst = opt.get_string(p + "pc_mg_smooth_type")
        if mst:                       # 'chebyshev' | 'jacobi' (solvers/mg)
            pc.mg_smoother = mst
        pc.bjacobi_blocks = opt.get_int(p + "pc_bjacobi_blocks",
                                        pc.bjacobi_blocks)
        sd = opt.get_string(p + "pc_setup_device")
        if sd:
            pc.setup_device = sd
        ct = opt.get_string(p + "pc_composite_type")
        if ct:
            pc.set_composite_type(ct)
        cp = opt.get_string(p + "pc_composite_pcs")
        if cp:
            pc.set_composite_pcs(*[s.strip() for s in cp.split(",")
                                   if s.strip()])
        return self

    setFromOptions = set_from_options

    def set_up(self):
        if self._mat is None:
            raise RuntimeError("KSP.set_up: no operators set")
        self.get_pc().set_up(self.get_pc()._mat or self._mat)
        if self.reduction_auto:
            # after PC set_up: the apply-cost probe runs the REAL
            # operator+PC apply on the placed factors
            self._autoselect_reduction()
        return self

    setUp = set_up

    def _autoselect_reduction(self):
        """``-ksp_reduction_auto``: pick the reduction plan — classic CG,
        pipelined CG, or s-step CG with its s — from the MEASURED
        per-reduce-site latency of this mesh (solvers/autoselect.py).
        Runs once per (operator, mesh); only CG-family starting types are
        re-routed (an explicit gmres/minres choice is an operator-class
        statement auto-selection must not override)."""
        if self._type not in ("cg", "pipecg", "sstep"):
            return
        mat = self._mat
        key = (id(mat), getattr(mat, "_state", 0),
               getattr(mat.comm, "mesh", None))
        if getattr(self, "_autoselect_key", None) == key:
            return
        from . import autoselect
        sp = _telemetry.span("ksp.autoselect",
                             starting_type=self._type)
        with sp:
            report = autoselect.select_reduction_plan(
                mat.comm, mat, self.get_pc(),
                refresh=self.reduction_probe_refresh)
            self._type = report.ksp_type
            if report.ksp_type == "sstep":
                self.sstep_s = int(report.s)
            self._reduction_report = report
            self._autoselect_key = key
            sp.set_attrs(choice=report.ksp_type, s=int(report.s or 0),
                         psum_us=float(report.psum_us),
                         apply_us=float(report.apply_us),
                         probe_cached=bool(report.probe_cached))

    # ---- silent-corruption guard plumbing -----------------------------------
    def _effective_replacement(self) -> int:
        """The replacement interval a solve actually arms:
        ``-ksp_residual_replacement`` when set, else — for the pipelined
        type only — the ``-ksp_pipeline_auto_replacement`` fallback (the
        drift bound pipelined CG's recurrences want by default)."""
        if self.residual_replacement > 0:
            return int(self.residual_replacement)
        if self._type == "pipecg":
            return int(self.pipeline_auto_replacement)
        if self._type == "sstep":
            return int(self.sstep_auto_replacement)
        return 0

    def _guard_requested(self) -> bool:
        return bool(self.abft or self._effective_replacement() > 0)

    def _check_guard(self):
        if self._guard_requested() and self._type not in GUARDED_TYPES:
            raise ValueError(
                f"-ksp_abft / -ksp_residual_replacement (the "
                f"silent-corruption guard) support KSP "
                f"{sorted(GUARDED_TYPES)}; KSP {self._type!r} has no "
                "guarded kernel — disable the guard or use cg")

    def _guard_checksums(self, mat, pc, op_dt):
        """Place (and cache) the ABFT checksum vectors for the guarded
        program: ``(cs_args, abft_pc_on)``. Recomputed when the operator
        or preconditioning matrix mutates (``Mat._state``)."""
        from ..resilience import abft as abft_mod
        if not self.abft:
            return (), False
        pmat = pc._mat
        key = (id(mat), getattr(mat, "_state", 0), pc.get_type(),
               id(pmat),
               getattr(pmat, "_state", 0) if pmat is not None else 0,
               str(op_dt))
        cached = getattr(self, "_abft_placed", None)
        if cached is not None and cached[0] == key:
            return cached[1], cached[2]
        cs = np.asarray(abft_mod.column_checksum(mat)).astype(
            op_dt, copy=False)
        csM = abft_mod.pc_checksum(pc, mat)
        host = [cs] + ([np.asarray(csM).astype(op_dt, copy=False)]
                       if csM is not None else [])
        placed = tuple(mat.comm.put_rows_many(host))
        self._abft_placed = (key, placed, csM is not None)
        return placed, csM is not None

    # reduce sites per iteration of the CG-family compiled loops, keyed
    # on (type, guarded) — pinned by tests/test_collective_volume.py's
    # HLO gates; carried as a span attribute so a trace names the
    # collective schedule a solve ran under (other types omit the attr)
    _REDUCE_SITES = {("cg", False): 3, ("cg", True): 2,
                     ("pipecg", False): 1, ("pipecg", True): 1,
                     # per s-BLOCK (the per-iteration count is 1/s)
                     ("sstep", False): 1, ("sstep", True): 1}

    # ---- solve --------------------------------------------------------------
    @wrap_device_errors("KSPSolve")
    def solve(self, b: Vec, x: Vec, *, _rtol=None, _atol=None,
              _guess_nonzero=None, _no_reenter=False,
              _mon_offset=0) -> SolveResult:
        """Solve ``A x = b`` (petsc4py ``KSPSolve`` shape). The body lives
        in :meth:`_solve_impl`; this wrapper is the telemetry boundary —
        one ``ksp.solve`` span per call (gate re-entries recurse through
        here and nest as child ``ksp.solve`` spans), structured attributes
        for operator/precision/mesh before and iterations/reason after."""
        mat = self._mat
        sp = _telemetry.span(
            "ksp.solve", ksp_type=self._type,
            pc=self._pc.get_type() if self._pc is not None else "",
            operator=type(mat).__name__ if mat is not None else "",
            n=int(mat.shape[0]) if mat is not None else 0,
            precision=str(getattr(mat, "dtype", "")) if mat is not None
            else "",
            devices=int(getattr(self.comm, "size", 0) or 0),
            reentry=bool(_no_reenter))
        if sp is not _telemetry.NOOP:
            sites = self._REDUCE_SITES.get(
                (self._type, self._guard_requested()))
            if sites is not None:
                sp.set_attr("reduce_sites", sites)
        with sp:
            res = self._solve_impl(b, x, _rtol=_rtol, _atol=_atol,
                                   _guess_nonzero=_guess_nonzero,
                                   _no_reenter=_no_reenter,
                                   _mon_offset=_mon_offset)
            sp.set_attrs(iterations=res.iterations, reason=res.reason,
                         converged=res.converged,
                         rnorm=res.residual_norm)
            return res

    def _solve_impl(self, b: Vec, x: Vec, *, _rtol=None, _atol=None,
                    _guess_nonzero=None, _no_reenter=False,
                    _mon_offset=0) -> SolveResult:
        # The underscore kwargs are the re-entry plumbing of the
        # true-residual gate: a re-entered sub-solve overrides tolerances
        # and the initial-guess flag THROUGH PARAMETERS (never by mutating
        # instance state — a monitor callback observing self mid-re-entry
        # sees the user's configuration) and offsets monitor iteration
        # numbering by the iterations already spent.
        mat = self._mat
        if mat is None:
            raise RuntimeError("KSP.solve: no operators set")
        _faults.check("ksp.solve")    # injectable pre-solve device failure
        self._check_norm_type()
        self._check_guard()
        with _telemetry.span("ksp.setup"):
            self.set_up()
        comm = mat.comm
        pc = self.get_pc()
        if pc.kind == "hostlu":
            # irreducible sparsity past every device-direct cap: the factor
            # lives on host (scipy SuperLU — as faithful as the reference's
            # CPU-side MUMPS, test.py:43) and preonly applies it host-side
            return self._solve_hostlu(b, x)
        # KSP_NORM_NONE: neutralize the convergence test — max_it iterations,
        # reason CONVERGED_ITS (the smoother configuration). The monitored
        # norm is still computed in-program (eliding it entirely would need a
        # per-kernel compile variant); only the exit condition is disabled.
        if getattr(self, "_history_reset", False):
            self._history.clear()
        norm_none = self._norm_type == "none" and self._type != "preonly"
        rtol = self.rtol if _rtol is None else _rtol
        atol = self.atol if _atol is None else _atol
        divtol = self.divtol
        guess_nonzero = (self._initial_guess_nonzero if _guess_nonzero is None
                         else _guess_nonzero)
        if norm_none:
            rtol, atol, divtol = 0.0, 0.0, 0.0
        # -ksp_megasolve: the fused whole-solve program — one launch,
        # in-program verification/re-entry (solvers/megasolve.py);
        # ineligible configurations continue on the unfused path below
        if self._megasolve_eligible():
            return self._solve_megasolve(b, x, rtol=rtol, atol=atol,
                                         guess_nonzero=guess_nonzero)
        # the gate computes its true-residual scalars in the solve program's
        # epilogue (krylov true_res) — the honest case costs ZERO extra
        # program dispatches (no re-dispatched mult + norm per solve)
        gate = (self._true_residual_check and self._type != "preonly"
                and not norm_none)
        # silent-corruption guard (-ksp_abft / -ksp_residual_replacement):
        # the guarded kernel detects in-program, the host maps detection
        # to a DETECTED_SDC failure (rollback target = the verified
        # iterate written into x before raising)
        guard = self._guard_requested() and self._type in GUARDED_TYPES

        monitors = None
        history_on = hasattr(self, "_history")
        monitored = bool(self._monitors or self._monitor_flag or history_on)
        if monitored:
            monitors = list(self._monitors)
            if self._monitor_flag and not self._monitors:
                monitors.append(
                    lambda ksp, k, rn:
                    print(f"  {int(k):4d} KSP Residual norm {float(rn):.12e}"))
            if history_on:
                def record(_ksp, _it, rn):
                    if len(self._history) < self._history_length:
                        self._history.append(float(rn))
                monitors.append(record)

        nullspace = getattr(mat, "nullspace", None)
        if nullspace is not None and nullspace.dim == 0:
            nullspace = None        # empty null space: nothing to project
        from .krylov import (acquire_live_monitor, hist_capacity,
                             live_monitor_sink, live_monitor_supported,
                             release_live_monitor)
        # live -ksp_monitor: stream each residual DURING the solve on
        # callback-capable backends (PETSc's semantics); elsewhere — and
        # for history-only monitoring, where per-record host callbacks buy
        # nothing — the in-program buffer is replayed after the fetch
        live = (bool(self._monitors or self._monitor_flag)
                and live_monitor_supported(comm))
        op_dt = np.dtype(mat.dtype)
        cs_args, abft_pc_on = ((), False)
        if guard:
            cs_args, abft_pc_on = self._guard_checksums(mat, pc, op_dt)
        with _telemetry.span("ksp.setup") as setup_span:
            prog = build_ksp_program(
                comm, self._type, pc, mat,
                restart=self.restart,
                monitored=monitored,
                zero_guess=not guess_nonzero,
                nullspace_dim=(nullspace.dim if nullspace else 0),
                aug=self.lgmres_augment,
                ell=self.bcgsl_ell,
                unroll=self.unroll,
                natural=self._norm_type == "natural",
                hist_cap=hist_capacity(
                    self.max_it,
                    # bcgsl records at k+ell, so cover the
                    # larger of the cycle-granular strides
                    max(self.restart, self.bcgsl_ell)),
                live=live, true_res=gate,
                abft=guard and self.abft,
                abft_pc=abft_pc_on,
                rr=guard and self._effective_replacement() > 0,
                donate=True, sstep_s=self.sstep_s)
            setup_span.set_attrs(aot=_aot.status(prog), loop=prog.loop)
        # host scalars travel with the execute call — no extra device
        # round-trips.
        # Tolerances are always REAL-typed: for complex operators the
        # kernels' norms take the real part (krylov pnorm). With the gate
        # on, the PROGRAM's stopping target is tightened by
        # true_residual_margin (see __init__) — the gate's own check below
        # still uses the un-margined rtol/atol, so semantics only ever get
        # stricter, never looser
        margin = self.true_residual_margin if gate else 1.0
        if not 0.0 < margin <= 1.0:
            raise ValueError(
                f"-ksp_true_residual_margin must be in (0, 1], got "
                f"{margin!r}: 0 makes every gated target unreachable, "
                ">1 would stop LOOSER than rtol and defeat the gate")
        # tolerance scalars travel in the REDUCE channel's real dtype
        # (f32 under bf16 storage — a bf16 rtol would quantize the
        # convergence target to 8 mantissa bits)
        from ..utils.dtypes import tolerance_dtype
        dt = tolerance_dtype(op_dt)
        ns_args = ((nullspace.device_array(comm, mat.shape[0], op_dt),)
                   if nullspace else ())
        # trailing runtime guard scalars (tolerance factor + replacement
        # interval; sstep appends its basis-restart budget) — runtime
        # args, so tuning them never recompiles
        guard_scalars = ((dt.type(self.abft_tol),
                          np.int32(self._effective_replacement()))
                         if guard else ())
        if guard and self._type == "sstep":
            guard_scalars += (np.int32(self.sstep_max_replacements),)
        # fault point 'ksp.program': a simulated worker crash DURING the
        # compiled solve. With iter=K the crash leaves real partial state —
        # the same cached program truncated to K iterations (max_it is a
        # runtime scalar, so no recompile) writes the iteration-K iterate
        # into x before the synthetic failure, exactly what a checkpoint
        # after a real mid-solve crash would hold (resilience/retry.py
        # resumes from it).
        # the program DONATES the initial-iterate argument (krylov
        # donate=True: the output x aliases the x0 buffer — zero extra
        # device allocations per repeat solve). x.data is rebound to the
        # program's output right after the call; an x0 that aliases the
        # RHS buffer must be copied first or the donation would delete b.
        from .krylov import donation_supported
        x0d = x.data
        if donation_supported() and x0d is b.data:
            # an x0 aliasing b must be copied or the donation would
            # delete the RHS
            x0d = jnp.array(x0d)
        fault = _faults.triggered("ksp.program")
        if fault is None:
            # persistent device loss: a mesh member is (or just became)
            # LOST — sticky 'unavailable' until heal() or an elastic
            # mesh shrink excludes the device (resilience/elastic.py);
            # iter=K clauses leave real partial state like ksp.program
            fault = _faults.mesh_fault("device.lost", comm.device_ids)
        if fault is not None:
            if fault.iter_k:
                _telemetry.record_program_dispatch("ksp")
                part = prog(mat.device_arrays(), pc.device_arrays(),
                            *ns_args, *cs_args, b.data, x0d,
                            dt.type(0.0), dt.type(0.0), dt.type(divtol),
                            np.int32(min(int(fault.iter_k), self.max_it)),
                            *guard_scalars)
                x.data = part[0]
            raise fault.error()
        # live mode: the in-program io_callback fires once per device per
        # record (replicated args); dispatch each NEW k to the monitors as
        # it arrives — k is monotone within a solve, so "k > max seen"
        # dedupes device copies even if devices interleave. The slot claim
        # is NON-blocking: a monitor that launches a monitored solve of its
        # own runs on a callback thread, and a blocking claim there would
        # deadlock against this solve's effects_barrier — the unclaimed
        # solve falls back to the always-correct buffered replay (the
        # history buffer is filled either way).
        delivered_live = False
        live_ctx = contextlib.nullcontext()
        monitor_errors = []
        if live and acquire_live_monitor():
            delivered_live = True
            seen = [-1]

            def _dispatch(k, rn):
                if k > seen[0]:
                    seen[0] = k
                    # the sink runs on the runtime's io_callback threads: a
                    # raising user monitor must not propagate into the XLA
                    # callback machinery (it would poison the effects
                    # barrier the solve waits on) — record it and re-raise
                    # on the solving thread after effects_barrier()
                    try:
                        for m in monitors:
                            m(self, k + _mon_offset, rn)
                    # tpslint: disable=TPS005 — user monitor callbacks can
                    # raise anything; it must not reach the XLA io_callback
                    # machinery, so record and re-raise after the barrier
                    except Exception as exc:  # noqa: BLE001
                        if not monitor_errors:
                            monitor_errors.append(exc)
            live_ctx = live_monitor_sink(_dispatch)
        self._last_monitor_mode = ("live" if delivered_live else
                                   "replay" if monitored else "off")
        t0 = time.perf_counter()
        try:
            with live_ctx:
                with _telemetry.span("ksp.dispatch"):
                    _telemetry.record_program_dispatch("ksp")
                    out = prog(
                        mat.device_arrays(), pc.device_arrays(), *ns_args,
                        *cs_args, b.data, x0d,
                        dt.type(rtol * margin), dt.type(atol * margin),
                        dt.type(divtol), np.int32(self.max_it),
                        *guard_scalars)
                # a loaded program that rejected the operands re-traced
                setup_span.set_attr("aot", _aot.status(prog))
                xd, iters, rnorm, reason, hist = out[:5]
                # rebind the caller's vector IMMEDIATELY: the donated x0
                # buffer is gone, so any exit path from here on (a raising
                # user monitor, the guard's rollback, a poisoned fetch)
                # must already see the program's output as x
                x.data = xd
                det = rrc = xv = None
                true_rn = bnorm = None
                rest = out[5:]
                if guard:
                    det, rrc, xv = rest[:3]
                    rest = rest[3:]
                if gate:
                    true_rn, bnorm = rest
                if delivered_live:
                    # drain pending io_callback effects INSIDE the sink
                    # scope — output-buffer readiness alone does not imply
                    # host-callback delivery (jax.effects_barrier is the
                    # documented drain)
                    jax.block_until_ready((iters, rnorm, reason))
                    jax.effects_barrier()
        finally:
            if delivered_live:
                release_live_monitor()
        if monitor_errors:
            raise monitor_errors[0]
        # one batched D2H fetch (int()/float() per scalar would pay a host
        # round trip three times). The residual
        # history is an in-program buffer (no host callbacks — works on
        # runtimes without callback support); fetch it in the same batch
        # and replay the recorded entries, in order, to the user monitors.
        fetch = [iters, rnorm, reason]
        if monitored:
            fetch.append(hist)
        if guard:
            fetch += [det, rrc]
        if gate:
            fetch += [true_rn, bnorm]
        with _telemetry.span("ksp.fetch"):
            fetch = jax.device_get(tuple(fetch))
        iters, rnorm, reason = fetch[:3]
        if monitored:
            hist = fetch[3]
        if gate:
            true_rn, bnorm = float(fetch[-2]), float(fetch[-1])
        if guard:
            i_det = 3 + (1 if monitored else 0)
            det, rrc = int(fetch[i_det]), int(fetch[i_det + 1])
        from ..utils.profiling import record_sync
        record_sync("KSP result fetch/solve")
        if monitored and not delivered_live:
            # -1 is the unwritten sentinel (norms are nonnegative); a
            # recorded NaN residual passes `!= -1` and reaches the
            # monitors, as the callback path used to deliver it. Live mode
            # already delivered every record during the solve.
            hist = np.asarray(hist)
            for k_it in np.nonzero(hist != -1.0)[0]:
                for m in monitors:
                    m(self, int(k_it) + _mon_offset, float(hist[k_it]))
        wall = time.perf_counter() - t0
        if guard:
            # ABFT check count: 1 init check + one per iteration on the
            # operator channel (+ one per iteration on the PC channel
            # when its checksum exists)
            checks = ((1 + int(iters) * (1 + int(abft_pc_on)))
                      if self.abft else 0)
            from ..utils.profiling import record_sdc
            from .krylov import SDC_DEMOTE
            if int(det) == SDC_DEMOTE:
                # NOT corruption: the s-step drift gate exhausted its
                # basis-restart budget (-ksp_sstep_max_replacements) —
                # the CA-CG basis cannot hold this operator at this s.
                # The iterate is trusted (the gate just measured its
                # true residual); continue as classic CG from it.
                record_sdc(checks, 0, int(rrc))
                return self._demote_sstep(
                    b, x, rtol=rtol, atol=atol, iters=int(iters),
                    rrc=int(rrc), checks=checks, t0=t0)
            if int(det) != SDC_NONE:
                # detection: the iterate is NOT trusted — roll the
                # caller's vector back to the last VERIFIED iterate and
                # raise the DETECTED_SDC failure the resilience layer
                # recovers from (resilience/retry.py)
                detector = SDC_DETECTOR_NAMES.get(int(det), f"det{det}")
                record_sdc(checks, 1, int(rrc))
                x.data = xv
                raise SilentCorruptionError(
                    "KSPSolve", detector, int(iters),
                    detail=f"{int(rrc)} residual replacement(s) passed "
                           "before detection")
            record_sdc(checks, 0, int(rrc))
        # fault point 'ksp.result': poison the fetched residual norm — the
        # deterministic stand-in for a recurrence blowing up at iteration
        # iter=K (real blow-ups reach this same fetch carrying their NaN)
        fault = _faults.triggered("ksp.result")
        if fault is not None:
            rnorm = float("nan") if fault.kind == "nan" else float("inf")
            if fault.iter_k is not None:
                iters = fault.iter_k
        # a NaN/Inf residual must never slip past the convergence
        # bookkeeping as a plausible exit code: NaN fails every `<= tol`
        # comparison, so the kernel reports DIVERGED_MAX_IT — map it to
        # PETSc's DIVERGED_NANORINF (-9) so callers (and the fallback
        # chain, resilience/fallback.py) see the blow-up for what it is.
        # KSP_NORM_NONE keeps PETSc semantics: no norm is monitored, so
        # there is nothing to classify.
        if not norm_none and not np.isfinite(rnorm):
            reason = ConvergedReason.DIVERGED_NANORINF
        # breakdown stays visible (PETSc's NORM_NONE does not mask it);
        # every other exit is the fixed-iteration contract. An exactly-zero
        # residual (b = 0) still exits immediately — running further steps
        # on a zero vector is a no-op.
        if norm_none and int(reason) != ConvergedReason.DIVERGED_BREAKDOWN:
            reason = ConvergedReason.CONVERGED_ITS
        self.result = SolveResult(int(iters), float(rnorm), int(reason), wall)
        if guard:
            self.result.abft_checks = checks
            self.result.residual_replacements = int(rrc)
        from ..utils.profiling import record_event
        record_event(f"KSPSolve({self._type}+{pc.get_type()})", mat.shape[0],
                     self.result.iterations, wall, self.result.reason)
        if self._view_flag:           # -ksp_view, PETSc prints after solve
            self.view()
        if self._reason_flag:         # -ksp_converged_reason
            verb = ("converged" if self.result.converged else
                    "did not converge")
            print(f"Linear solve {verb} due to "
                  f"{ConvergedReason.name(self.result.reason)} "
                  f"iterations {self.result.iterations}")
        # opt-in TRUE-residual gate (see set_true_residual_check): the
        # epilogue already returned ||b - A x|| with the solve's own fetch,
        # so the honest case is decided right here at zero extra dispatch
        # cost; only an actual recurrence-drift miss re-enters from the
        # current iterate (a fresh recurrence STARTS from the true residual,
        # so each re-entry closes the drift gap)
        if gate:
            self._last_true_res = (true_rn, bnorm)
            # margin tightening must never turn a TRUE-converged solve
            # into a reported failure: a recurrence that stalled between
            # margin*rtol and rtol (or broke down) whose ||b - A x||
            # meets the UN-margined target HAS converged
            if (not self.result.converged and np.isfinite(true_rn)
                    and true_rn <= max(rtol * bnorm, atol)):
                self.result = SolveResult(
                    self.result.iterations, true_rn,
                    ConvergedReason.CONVERGED_RTOL, self.result.wall_time)
        if not _no_reenter:
            self._last_reentries = 0   # gate re-entry count of this solve
        if gate and not _no_reenter and self.result.converged:
            with _telemetry.span("ksp.verify", true_rnorm=float(true_rn),
                                   bnorm=float(bnorm)) as vsp:
                target = max(rtol * bnorm, atol)
                trn_h = true_rn
                last_mon_rn = float(rnorm)   # monitored-norm value at x
                total_iters = self.result.iterations
                total_wall = self.result.wall_time
                attempts = 0
                while trn_h > target:
                    if attempts == 3:
                        # 3 re-entries couldn't close the drift: the gate's
                        # contract is that "converged" means the TRUE residual
                        # met the target, so report the failure honestly
                        self.result = SolveResult(
                            total_iters, trn_h,
                            ConvergedReason.DIVERGED_MAX_IT, total_wall)
                        break
                    attempts += 1
                    # the sub-solve's exit test runs in the KERNEL's monitored
                    # norm; for preconditioned/natural-norm kernels map the
                    # unpreconditioned target through the observed ratio at the
                    # current iterate so the sub-solve neither exits early nor
                    # over-iterates (the outer loop re-checks the TRUE residual
                    # either way)
                    sub_atol = target
                    mon_norm = self.get_norm_type()
                    if (mon_norm in ("preconditioned", "natural")
                            and np.isfinite(last_mon_rn) and last_mon_rn > 0
                            and trn_h > 0):
                        sub_atol = target * last_mon_rn / trn_h
                    sub = self.solve(b, x, _rtol=0.0, _atol=sub_atol,
                                     _guess_nonzero=True, _no_reenter=True,
                                     _mon_offset=_mon_offset + total_iters)
                    total_iters += sub.iterations
                    total_wall += sub.wall_time
                    last_mon_rn = sub.residual_norm
                    trn_h = self._last_true_res[0]
                    # the re-entered sub-solve's own reason may be a margin
                    # stall; what decides is the TRUE residual the loop
                    # re-checks (CONVERGED_RTOL when it passes)
                    reason = (ConvergedReason.CONVERGED_RTOL
                              if trn_h <= target else sub.reason)
                    self.result = SolveResult(total_iters, trn_h, reason,
                                              total_wall)
                    self._last_reentries = attempts
                vsp.set_attrs(reentries=attempts, passed=trn_h <= target)
        return self.result

    def _solve_hostlu(self, b: Vec, x: Vec) -> SolveResult:
        """Direct solve through the PC's HOST sparse-LU factor (the MUMPS
        slot's irreducible-sparsity path; see pc._build_host_splu).

        One gather + one SuperLU triangular solve + one scatter — the same
        host round trip the reference pays calling MUMPS from Python
        (``test.py:43-50`` [external]). Only 'preonly' reaches here by
        construction (PC.local_apply raises for every in-program apply).
        """
        if self._type != "preonly":
            raise ValueError(
                "PC 'lu'/'cholesky' fell back to the host sparse-LU mode "
                "(irreducible sparsity past the dense/banded device caps); "
                "the factor applies on HOST, which an in-program iterative "
                "KSP cannot call per iteration — use KSP 'preonly' (the "
                "reference's MUMPS configuration, test.py:38-43) or an "
                "iterative KSP with pc 'gamg'/'bjacobi'")
        pc = self.get_pc()
        factor, A64 = pc._hostlu
        self._last_reentries = 0      # direct path: no gate re-entries
        t0 = time.perf_counter()
        bh = np.asarray(b.to_numpy(), dtype=A64.dtype)
        xh = factor.solve(bh)
        x.set_global(xh.astype(np.dtype(str(self._mat.dtype))))
        rnorm = float(np.linalg.norm(bh - A64 @ xh))
        wall = time.perf_counter() - t0
        self.result = SolveResult(1, rnorm, ConvergedReason.CONVERGED_ITS,
                                  wall)
        from ..utils.profiling import record_event, record_sync
        record_sync("KSP hostlu gather/scatter", 2)
        record_event("KSPSolve(preonly+hostlu)", self._mat.shape[0], 1,
                     wall, self.result.reason)
        if self._view_flag:
            self.view()
        if self._reason_flag:
            print(f"Linear solve converged due to "
                  f"{ConvergedReason.name(self.result.reason)} iterations 1")
        return self.result

    # ---- s-step demotion: CA-CG basis-restart budget exhausted --------------
    def _demote_clone(self) -> "KSP":
        """A classic-CG twin sharing the operator and the already-set-up
        PC — the continuation solver a demoted s-step solve finishes on
        (never mutates ``self``: a monitor observing this KSP mid-solve
        keeps seeing the user's configuration)."""
        k2 = KSP()
        k2.comm = self.comm
        k2._mat = self._mat
        k2._pc = self._pc
        k2._type = "cg"
        k2.rtol, k2.atol = self.rtol, self.atol
        k2.divtol, k2.max_it = self.divtol, self.max_it
        k2.abft = self.abft
        k2.abft_tol = self.abft_tol
        # deliberately NOT inherited: the sstep-tuned replacement
        # interval (small, to catch basis stall early) would restart
        # classic CG's direction chain every few iterations and cripple
        # its superlinear convergence — the continuation runs plain
        # (ABFT-checked when armed) classic CG
        k2.residual_replacement = 0
        k2._monitors = list(self._monitors)
        k2._monitor_flag = self._monitor_flag
        k2._initial_guess_nonzero = True
        return k2

    def _demote_sstep(self, b, x, *, rtol, atol, iters, rrc, checks,
                      t0) -> SolveResult:
        """The ``SDC_DEMOTE`` exit of a guarded s-step solve: the drift
        gate restarted the basis ``-ksp_sstep_max_replacements`` times
        and the coordinate recurrences still drift — the monomial basis
        cannot hold this operator at this ``s``. The current iterate IS
        trusted (the gate measured its true residual), so the solve
        CONTINUES as classic CG from it, and the demotion is recorded as
        a :class:`RecoveryEvent` on the merged result."""
        from ..telemetry.metrics import registry
        from ..utils.convergence import RecoveryEvent
        registry.counter("sstep.demotions").inc()
        sub_ksp = self._demote_clone()
        sub_ksp.max_it = max(self.max_it - iters, 1)
        sub = sub_ksp.solve(b, x, _rtol=rtol, _atol=atol,
                            _guess_nonzero=True, _mon_offset=iters)
        res = SolveResult(iters + sub.iterations, sub.residual_norm,
                          sub.reason, time.perf_counter() - t0)
        res.abft_checks = checks + getattr(sub, "abft_checks", 0)
        res.residual_replacements = (rrc + getattr(
            sub, "residual_replacements", 0))
        res.recovery_events = [RecoveryEvent(
            "sstep_demote", 1,
            detail=(f"s={self.sstep_s}: {self.sstep_max_replacements} "
                    "basis restart(s) exhausted; demoted to classic cg"),
            iterations=iters, detector="drift")] \
            + list(sub.recovery_events)
        self.result = res
        return res

    def _demote_sstep_many(self, B, X, *, iters, rrc, checks, t0,
                           demoted) -> BatchedSolveResult:
        """Batched twin of :meth:`_demote_sstep`: any column hitting the
        basis-restart budget demotes the WHOLE block to classic CG from
        the current iterates — already-converged columns freeze at
        iteration 0 under the masked block kernel, so only the drifting
        stragglers pay."""
        from ..telemetry.metrics import registry
        from ..utils.convergence import RecoveryEvent
        registry.counter("sstep.demotions").inc(len(demoted))
        sub_ksp = self._demote_clone()
        # the continuation spends only the REMAINING iteration budget
        # (capped against the furthest column, so no column's total can
        # exceed max_it — the single-RHS twin's contract)
        sub_ksp.max_it = max(self.max_it - (max(iters) if iters else 0),
                             1)
        sub = sub_ksp.solve_many(B, X)
        res = BatchedSolveResult(
            iterations=[int(a) + int(c) for a, c in
                        zip(iters, sub.iterations)],
            residual_norms=sub.residual_norms, reasons=sub.reasons,
            wall_time=time.perf_counter() - t0, X=sub.X,
            histories=sub.histories)
        res.abft_checks = checks + getattr(sub, "abft_checks", 0)
        res.residual_replacements = (rrc + getattr(
            sub, "residual_replacements", 0))
        res.recovery_events = [RecoveryEvent(
            "sstep_demote", 1,
            detail=(f"s={self.sstep_s}: columns {sorted(demoted)} "
                    "exhausted the basis-restart budget; block demoted "
                    "to classic cg"),
            iterations=max(iters) if iters else 0, detector="drift")] \
            + list(sub.recovery_events)
        self.result_many = res
        return res

    # ---- megasolve: the fused whole-solve fast path -------------------------
    def _megasolve_eligible(self, many: bool = False) -> bool:
        """Route this solve through the fused whole-solve program
        (``-ksp_megasolve``, solvers/megasolve.py)? Conservative: any
        configuration without a fused equivalent — non-CG types, a null
        space, monitors/history (per-iteration records live in the
        unfused programs), norm-type overrides, unroll>1 — falls
        through to the unfused path silently."""
        if not self.megasolve:
            return False
        mat = self._mat
        if mat is None:
            return False
        nullspace = getattr(mat, "nullspace", None)
        if nullspace is not None and getattr(nullspace, "dim", 0) > 0:
            return False
        if self._norm_type != "default" or self.unroll != 1:
            return False
        if self._monitors or self._monitor_flag or hasattr(self, "_history"):
            return False
        from .megasolve import megasolve_supported
        return megasolve_supported(self._type, self.get_pc(), mat,
                                   nrhs=2 if many else None)

    def _solve_megasolve(self, b: Vec, x: Vec, *, rtol, atol,
                         guess_nonzero) -> SolveResult:
        """The ``-ksp_megasolve`` fast path: ONE fused program launch
        for the whole solve. The in-program outer loop re-enters the CG
        recurrence from the TRUE residual until ``max(rtol*||b||,
        atol)`` passes (the unfused ``-ksp_true_residual_check`` gate's
        semantics at zero re-entry dispatches), so the reported
        ``rnorm`` is the verified ``||b - A x||``. Guard detection
        surfaces the fused loop's verified-iterate carry: ``x`` is
        rolled back to it before the DETECTED_SDC raise, exactly as the
        unfused path does."""
        from .megasolve import (GATE_REFINE_MAX, build_megasolve_program,
                                megasolve_stencil_supported)
        mat = self._mat
        comm = mat.comm
        pc = self.get_pc()
        op_dt = np.dtype(mat.dtype)
        guard = self._guard_requested() and self._type in GUARDED_TYPES
        cs_args, abft_pc_on = ((), False)
        if guard:
            cs_args, abft_pc_on = self._guard_checksums(mat, pc, op_dt)
        sf = (self.megasolve_stencil_fastpath
              and megasolve_stencil_supported(self._type, pc, mat,
                                              guard=guard))
        with _telemetry.span("ksp.setup"):
            prog = build_megasolve_program(
                comm, self._type, pc, mat, None,
                zero_guess=not guess_nonzero,
                abft=guard and self.abft, abft_pc=abft_pc_on,
                rr=guard and self._effective_replacement() > 0,
                donate=True, sstep_s=self.sstep_s,
                stencil_fastpath=sf)
        from ..utils.dtypes import tolerance_dtype
        dt = tolerance_dtype(op_dt)
        guard_scalars = ((dt.type(self.abft_tol),
                          np.int32(self._effective_replacement()))
                         if guard else ())
        if guard and self._type == "sstep":
            guard_scalars += (np.int32(self.sstep_max_replacements),)
        from .krylov import donation_supported
        x0d = x.data
        if donation_supported() and x0d is b.data:
            # aliasing copy rule — see _solve_impl
            x0d = jnp.array(x0d)
        fault = _faults.triggered("ksp.program")
        if fault is None:
            fault = _faults.mesh_fault("device.lost", comm.device_ids)
        if fault is not None:
            if fault.iter_k:
                # truncated re-run leaves the iteration-K iterate: zero
                # targets + one outer step of iter_k inner iterations
                _telemetry.record_program_dispatch("megasolve")
                part = prog(mat.device_arrays(), pc.device_arrays(),
                            *cs_args, b.data, x0d,
                            dt.type(0.0), dt.type(0.0), dt.type(0.0),
                            dt.type(self.divtol),
                            np.int32(min(int(fault.iter_k), self.max_it)),
                            np.int32(1),
                            np.int32(ConvergedReason.DIVERGED_MAX_IT),
                            *guard_scalars)
                x.data = part[0]
            raise fault.error()
        t0 = time.perf_counter()
        with _telemetry.span("ksp.dispatch"):
            _telemetry.record_program_dispatch("megasolve")
            out = prog(mat.device_arrays(), pc.device_arrays(), *cs_args,
                       b.data, x0d,
                       dt.type(rtol), dt.type(atol), dt.type(rtol),
                       dt.type(self.divtol), np.int32(self.max_it),
                       np.int32(GATE_REFINE_MAX),
                       # drift-stall exit reports the unfused gate's
                       # DIVERGED_MAX_IT (genuine inner breakdown still
                       # surfaces as DIVERGED_BREAKDOWN in-program)
                       np.int32(ConvergedReason.DIVERGED_MAX_IT),
                       *guard_scalars)
        xd, steps, iters, rnorm, reason = out[:5]
        # rebind immediately: the donated x0 buffer is gone (see
        # _solve_impl) — every exit path must see the program's output
        x.data = xd
        det = rrc = xv = None
        if guard:
            det, rrc, xv = out[5:8]
        with _telemetry.span("ksp.fetch"):
            fetch = jax.device_get(
                (steps, iters, rnorm, reason)
                + ((det, rrc) if guard else ()))
        from ..utils.profiling import record_sync
        record_sync("KSP result fetch/solve")
        steps, iters = int(fetch[0]), int(fetch[1])
        rnorm, reason = float(fetch[2]), int(fetch[3])
        wall = time.perf_counter() - t0
        checks = 0
        if guard:
            det, rrc = int(fetch[4]), int(fetch[5])
            # one init check per outer step + one per inner iteration
            # per active channel (the unfused accounting, per step)
            checks = ((steps + iters * (1 + int(abft_pc_on)))
                      if self.abft else 0)
            from ..utils.profiling import record_sdc
            from .krylov import SDC_DEMOTE
            if det == SDC_DEMOTE:
                # CA-CG demotion surfaced through the fused loop: the
                # outer carry is the last gate-verified iterate —
                # continue as classic CG from it (see _demote_sstep)
                record_sdc(checks, 0, rrc)
                return self._demote_sstep(
                    b, x, rtol=rtol, atol=atol, iters=iters, rrc=rrc,
                    checks=checks, t0=t0)
            if det != SDC_NONE:
                detector = SDC_DETECTOR_NAMES.get(det, f"det{det}")
                record_sdc(checks, 1, rrc)
                # rollback target: the last outer iterate whose fp64
                # TRUE residual was measured by the fused exit gate
                x.data = xv
                raise SilentCorruptionError(
                    "KSPSolve", detector, iters,
                    detail=f"detected inside the fused megasolve loop "
                           f"({rrc} residual replacement(s) passed "
                           "before detection)")
            record_sdc(checks, 0, rrc)
        fault = _faults.triggered("ksp.result")
        if fault is not None:
            rnorm = float("nan") if fault.kind == "nan" else float("inf")
            if fault.iter_k is not None:
                iters = fault.iter_k
        if not np.isfinite(rnorm):
            reason = ConvergedReason.DIVERGED_NANORINF
        self.result = SolveResult(iters, rnorm, int(reason), wall)
        self.result.megasolve_steps = steps
        self._last_reentries = 0      # in-program re-entries aren't
        #                               host gate re-entries
        if guard:
            self.result.abft_checks = checks
            self.result.residual_replacements = rrc
        from ..utils.profiling import record_event
        record_event(f"KSPSolve({self._type}+{pc.get_type()}+mega)",
                     mat.shape[0], iters, wall, int(reason))
        if self._view_flag:
            self.view()
        if self._reason_flag:
            verb = ("converged" if self.result.converged else
                    "did not converge")
            print(f"Linear solve {verb} due to "
                  f"{ConvergedReason.name(self.result.reason)} "
                  f"iterations {self.result.iterations}")
        return self.result

    def _solve_many_megasolve(self, B, X) -> BatchedSolveResult:
        """Fused batched fast path: the whole block's refinement/
        verification recurrence in ONE launch — a coalesced serving
        block costs exactly one dispatch (megasolve module doc).
        Per-column results mirror the unfused batched path; guard
        detection rolls the block back to the fused loop's verified
        carry and raises, exactly like ``_solve_many_impl``."""
        from .megasolve import (GATE_REFINE_MAX,
                                build_megasolve_program_many,
                                megasolve_stencil_supported)
        mat = self._mat
        comm = mat.comm
        pc = self.get_pc()
        k = int(B.shape[1])
        op_dt = np.dtype(mat.dtype)
        guard = self._guard_requested()
        cs_args, abft_pc_on = ((), False)
        if guard:
            cs_args, abft_pc_on = self._guard_checksums(mat, pc, op_dt)
        sf = (self.megasolve_stencil_fastpath
              and megasolve_stencil_supported(self._type, pc, mat,
                                              nrhs=k, guard=guard))
        with _telemetry.span("ksp.setup"):
            prog = build_megasolve_program_many(
                comm, self._type, pc, mat, None, nrhs=k,
                zero_guess=not self._initial_guess_nonzero,
                abft=guard and self.abft, abft_pc=abft_pc_on,
                rr=guard and self._effective_replacement() > 0,
                donate=True, sstep_s=self.sstep_s,
                stencil_fastpath=sf)
        from ..utils.dtypes import tolerance_dtype
        dt = tolerance_dtype(op_dt)
        guard_scalars = ((dt.type(self.abft_tol),
                          np.int32(self._effective_replacement()))
                         if guard else ())
        if guard and self._type == "sstep":
            guard_scalars += (np.int32(self.sstep_max_replacements),)
        Bd, Xd0 = comm.put_rows_many([B.astype(op_dt, copy=False),
                                      X.astype(op_dt, copy=False)])
        from .krylov import donation_supported
        if donation_supported():
            Xd0 = jnp.array(Xd0)      # op output, donation-safe
        fault = _faults.triggered("ksp.program")
        if fault is None:
            fault = _faults.mesh_fault("device.lost", comm.device_ids)
        if fault is not None:
            if fault.iter_k:
                _telemetry.record_program_dispatch("megasolve_many")
                part = prog(mat.device_arrays(), pc.device_arrays(),
                            *cs_args, Bd, Xd0,
                            dt.type(0.0), dt.type(0.0), dt.type(0.0),
                            dt.type(self.divtol),
                            np.int32(min(int(fault.iter_k), self.max_it)),
                            np.int32(1),
                            np.int32(ConvergedReason.DIVERGED_MAX_IT),
                            *guard_scalars)
                X[...] = np.asarray(
                    jax.device_get(part[0]))[: mat.shape[0]].astype(
                        X.dtype, copy=False)
            raise fault.error()
        t0 = time.perf_counter()
        with _telemetry.span("ksp.dispatch"):
            _telemetry.record_program_dispatch("megasolve_many")
            out = prog(mat.device_arrays(), pc.device_arrays(), *cs_args,
                       Bd, Xd0,
                       dt.type(self.rtol), dt.type(self.atol),
                       dt.type(self.rtol), dt.type(self.divtol),
                       np.int32(self.max_it), np.int32(GATE_REFINE_MAX),
                       np.int32(ConvergedReason.DIVERGED_MAX_IT),
                       *guard_scalars)
        Xd, steps, ii, rn, rs = out[:5]
        det = rrc = Xv = None
        if guard:
            det, rrc, Xv = out[5:8]
        with _telemetry.span("ksp.fetch"):
            fetch = jax.device_get((Xd, steps, ii, rn, rs)
                                   + ((det, rrc) if guard else ()))
        from ..utils.profiling import (record_event, record_sdc,
                                       record_sync)
        record_sync("KSP solve_many result fetch")
        X[...] = np.asarray(fetch[0])[: mat.shape[0]].astype(
            X.dtype, copy=False)
        steps = int(fetch[1])
        iters = [int(i) for i in np.asarray(fetch[2])]
        rnorms = [float(v) for v in np.asarray(fetch[3])]
        reasons = [int(v) for v in np.asarray(fetch[4])]
        wall = time.perf_counter() - t0
        checks = 0
        if guard:
            det_h = np.asarray(fetch[5])
            rrc_h = np.asarray(fetch[6])
            checks = ((k * steps + sum(iters) * (1 + int(abft_pc_on)))
                      if self.abft else 0)
            from .krylov import SDC_DEMOTE
            bad = [j for j in range(k)
                   if int(det_h[j]) not in (SDC_NONE, SDC_DEMOTE)]
            if bad:
                detector = SDC_DETECTOR_NAMES.get(
                    int(det_h[bad[0]]), str(int(det_h[bad[0]])))
                record_sdc(checks, len(bad), int(rrc_h.sum()))
                X[...] = np.asarray(
                    jax.device_get(Xv))[: mat.shape[0]].astype(
                        X.dtype, copy=False)
                raise SilentCorruptionError(
                    "KSPSolveMany", detector,
                    int(max(iters[j] for j in bad)),
                    detail=f"columns {bad} flagged inside the fused "
                           "megasolve loop")
            demoted = [j for j in range(k)
                       if int(det_h[j]) == SDC_DEMOTE]
            if demoted:
                record_sdc(checks, 0, int(rrc_h.sum()))
                return self._demote_sstep_many(
                    B, X, iters=iters, rrc=int(rrc_h.sum()),
                    checks=checks, t0=t0, demoted=demoted)
            record_sdc(checks, 0, int(rrc_h.sum()))
        for j in range(k):
            if not np.isfinite(rnorms[j]):
                reasons[j] = ConvergedReason.DIVERGED_NANORINF
        res = BatchedSolveResult(iterations=iters, residual_norms=rnorms,
                                 reasons=reasons, wall_time=wall, X=X,
                                 histories=[[] for _ in range(k)])
        res.megasolve_steps = steps
        if guard:
            res.abft_checks = checks
            res.residual_replacements = int(rrc_h.sum())
        self.result_many = res
        record_event(f"KSPSolveMany({self._type}+{pc.get_type()}"
                     f"+mega,k={k})", mat.shape[0],
                     max(iters) if iters else 0, wall,
                     max(reasons) if res.converged else min(reasons))
        return res

    # ---- batched multi-RHS solve (PETSc KSPMatSolve analog) -----------------
    @wrap_device_errors("KSPSolveMany")
    def solve_many(self, B, X=None) -> BatchedSolveResult:
        """Solve ``A X = B`` for a block of ``nrhs`` right-hand sides in
        ONE compiled program launch (the PETSc ``KSPMatSolve`` analog —
        PARITY.md "Batched solves").

        ``B`` is an ``(n, nrhs)`` host array (or a list of Vecs, stacked
        column-wise); ``X`` an optional ``(n, nrhs)`` array receiving the
        solution in place (used as the initial guess block when
        ``set_initial_guess_nonzero(True)``). Returns a
        :class:`BatchedSolveResult` with PER-COLUMN iterations, residual
        norms, reasons, and (when monitoring is on) histories — a column
        that converges early freezes while the rest keep iterating
        (masked convergence, krylov.cg_kernel_many).

        Routing: KSP 'cg' with a batched-apply PC (none/jacobi/bjacobi/
        lu — krylov.batched_pc_supported) and no null space runs the
        batched block-CG kernel: one all_gather and one fused reduction
        per phase serve every column, and the stencil fast path keeps
        all k slabs in the fused Pallas pipeline. With
        ``-ksp_true_residual_check`` the batched program's epilogue
        returns per-column TRUE residuals and drifted columns re-enter
        as a block (single-RHS gate semantics, per column); the
        silent-corruption guard (``-ksp_abft`` /
        ``-ksp_residual_replacement``) runs mask-aware per-column
        detection (krylov.cg_kernel_many_guarded). Everything else —
        other KSP types, PCs without a batched apply, natural norm —
        falls back to ``nrhs`` sequential solves (same per-column
        results, none of the amortization).

        ``-ksp_batch_limit`` (``self.batch_limit``) chunks a batch whose
        k columns overflow the VMEM plan into ceil(k/limit) launches.
        """
        mat = self._mat
        sp = _telemetry.span(
            "ksp.solve_many", ksp_type=self._type,
            pc=self._pc.get_type() if self._pc is not None else "",
            operator=type(mat).__name__ if mat is not None else "",
            n=int(mat.shape[0]) if mat is not None else 0,
            precision=str(getattr(mat, "dtype", "")) if mat is not None
            else "",
            devices=int(getattr(self.comm, "size", 0) or 0))
        with sp:
            res = self._solve_many_impl(B, X)
            its = res.iterations
            sp.set_attrs(nrhs=len(its), iterations=max(its) if its else 0,
                         converged=res.converged)
            return res

    def _solve_many_impl(self, B, X=None) -> BatchedSolveResult:
        mat = self._mat
        if mat is None:
            raise RuntimeError("KSP.solve_many: no operators set")
        if isinstance(B, (list, tuple)):
            B = np.stack(
                [b.to_numpy() if isinstance(b, Vec) else np.asarray(b)
                 for b in B], axis=1)
        B = np.asarray(B)
        if B.ndim != 2 or B.shape[0] != mat.shape[0]:
            raise ValueError(
                f"KSP.solve_many: B must be ({mat.shape[0]}, nrhs), got "
                f"{B.shape}")
        k = int(B.shape[1])
        if k == 0:
            raise ValueError("KSP.solve_many: empty RHS block (nrhs=0)")
        op_dt = np.dtype(mat.dtype)
        if X is None:
            X = np.zeros((mat.shape[0], k), dtype=op_dt)
        else:
            X = np.asarray(X)
            if X.shape != B.shape:
                raise ValueError(
                    f"KSP.solve_many: X shape {X.shape} != B shape {B.shape}")
            if not X.flags.writeable:
                # asarray of a jax array is a READ-ONLY view; the solution
                # block is written in place, so take a writable host copy
                # (the caller reads it back from result.X)
                X = X.copy()
        limit = int(self.batch_limit)
        if limit > 0 and k > limit:
            # -ksp_batch_limit chunking: ceil(k/limit) batched launches
            res = BatchedSolveResult(X=X)
            t0 = time.perf_counter()
            for s in range(0, k, limit):
                sl = slice(s, min(s + limit, k))
                sub = self.solve_many(B[:, sl], X[:, sl])
                X[:, sl] = sub.X
                res.iterations += sub.iterations
                res.residual_norms += sub.residual_norms
                res.reasons += sub.reasons
                res.histories += sub.histories
            res.wall_time = time.perf_counter() - t0
            self.result_many = res
            return res

        _faults.check("ksp.solve")    # the one pre-solve fault point
        self._check_norm_type()
        self._check_guard()
        with _telemetry.span("ksp.setup"):
            self.set_up()
        pc = self.get_pc()
        comm = mat.comm
        from .krylov import (batched_pc_supported, build_ksp_program_many,
                             hist_capacity)
        nullspace = getattr(mat, "nullspace", None)
        batched = (self._type in ("cg", "pipecg", "sstep")
                   and batched_pc_supported(pc)
                   and (nullspace is None or nullspace.dim == 0)
                   and self._norm_type in ("default", "none"))
        if not batched:
            return self._solve_many_sequential(B, X)
        if self._megasolve_eligible(many=True):
            return self._solve_many_megasolve(B, X)

        norm_none = self._norm_type == "none"
        rtol, atol, divtol = self.rtol, self.atol, self.divtol
        if norm_none:
            rtol = atol = divtol = 0.0
        # per-column true-residual gate (-ksp_true_residual_check): the
        # batched program's EPILOGUE returns every column's ||b_j - A x_j||
        # and ||b_j|| with the solve's own fetch (zero extra dispatches);
        # drifted columns re-enter as a whole block — already-converged
        # columns freeze instantly under the masked kernel, so re-entry
        # costs only the drifted columns' iterations
        gate = self._true_residual_check and not norm_none
        guard = self._guard_requested()
        margin = self.true_residual_margin if gate else 1.0
        if not 0.0 < margin <= 1.0:
            raise ValueError(
                f"-ksp_true_residual_margin must be in (0, 1], got "
                f"{margin!r}: 0 makes every gated target unreachable, "
                ">1 would stop LOOSER than rtol and defeat the gate")
        guess_nonzero = self._initial_guess_nonzero
        monitored = bool(self._monitors or self._monitor_flag
                         or hasattr(self, "_history"))
        cs_args, abft_pc_on = ((), False)
        if guard:
            cs_args, abft_pc_on = self._guard_checksums(mat, pc, op_dt)
        # donate=True: the X0 block is consumed by the program (the
        # output X aliases it) — both the first launch and every gate
        # re-entry run at zero extra device allocations, the serving
        # dispatch loop's realloc-churn killer
        build_kw = dict(monitored=monitored,
                        hist_cap=hist_capacity(self.max_it, 0),
                        abft=guard and self.abft, abft_pc=abft_pc_on,
                        rr=guard and self._effective_replacement() > 0,
                        true_res=gate, donate=True,
                        sstep_s=self.sstep_s)
        with _telemetry.span("ksp.setup"):
            prog = build_ksp_program_many(
                comm, self._type, pc, mat, nrhs=k,
                zero_guess=not guess_nonzero, **build_kw)
        from ..utils.dtypes import tolerance_dtype
        dt = tolerance_dtype(op_dt)
        guard_scalars = ((dt.type(self.abft_tol),
                          np.int32(self._effective_replacement()))
                         if guard else ())
        if guard and self._type == "sstep":
            guard_scalars += (np.int32(self.sstep_max_replacements),)
        # ONE batched placement for both blocks (the PR-3 put_rows_many
        # discipline: sequential put_rows would pay the runtime's fixed
        # dispatch twice and fire the comm.put fault point twice)
        Bd, Xd0 = comm.put_rows_many([B.astype(op_dt, copy=False),
                                      X.astype(op_dt, copy=False)])
        # fault point 'ksp.program': a worker crash mid-batched-solve —
        # the truncated re-run leaves the iteration-K iterate BLOCK in X,
        # exactly what resilient_solve_many checkpoints and resumes from
        fault = _faults.triggered("ksp.program")
        if fault is None:
            # persistent device loss (see KSP.solve): sticky until
            # heal() or the elastic shrink rebuilds on a smaller mesh
            fault = _faults.mesh_fault("device.lost", comm.device_ids)
        if fault is not None:
            if fault.iter_k:
                _telemetry.record_program_dispatch("ksp_many")
                part = prog(mat.device_arrays(), pc.device_arrays(),
                            *cs_args, Bd, Xd0, dt.type(0.0), dt.type(0.0),
                            dt.type(divtol),
                            np.int32(min(int(fault.iter_k), self.max_it)),
                            *guard_scalars)
                X[...] = np.asarray(
                    jax.device_get(part[0]))[: mat.shape[0]].astype(
                        X.dtype, copy=False)
            raise fault.error()

        def _unpack(out):
            base = list(out[:5])
            rest = out[5:]
            det = rrc = Xv = trn = bn = None
            if guard:
                det, rrc, Xv = rest[:3]
                rest = rest[3:]
            if gate:
                trn, bn = rest
            return base, det, rrc, Xv, trn, bn

        t0 = time.perf_counter()
        with _telemetry.span("ksp.dispatch"):
            _telemetry.record_program_dispatch("ksp_many")
            out = prog(mat.device_arrays(), pc.device_arrays(), *cs_args,
                       Bd, Xd0,
                       dt.type(rtol * margin), dt.type(atol * margin),
                       dt.type(divtol), np.int32(self.max_it),
                       *guard_scalars)
        (Xd, iters, rnorm, reason, hist), det, rrc, Xv, trn, bn = \
            _unpack(out)
        # one batched D2H fetch for the block and every per-column scalar
        with _telemetry.span("ksp.fetch"):
            fetch = jax.device_get(
                (Xd, iters, rnorm, reason)
                + ((hist,) if monitored else ())
                + ((det, rrc) if guard else ())
                + ((trn, bn) if gate else ()))
        wall = time.perf_counter() - t0
        from ..utils.profiling import record_event, record_sdc, record_sync
        record_sync("KSP solve_many result fetch")
        Xh = np.asarray(fetch[0])[: mat.shape[0]]
        X[...] = Xh.astype(X.dtype, copy=False)
        iters = [int(i) for i in np.asarray(fetch[1])]
        rnorms = [float(r) for r in np.asarray(fetch[2])]
        reasons = [int(r) for r in np.asarray(fetch[3])]
        i_extra = 4 + (1 if monitored else 0)
        if guard:
            det_h = np.asarray(fetch[i_extra])
            rrc_h = np.asarray(fetch[i_extra + 1])
            i_extra += 2
            # k init checks + one per column-iteration per active channel
            # (the single-RHS '1 + iters*(1+pc)' accounting, per column)
            checks = ((k + sum(iters) * (1 + int(abft_pc_on)))
                      if self.abft else 0)
            from .krylov import SDC_DEMOTE
            bad = [j for j in range(k)
                   if int(det_h[j]) not in (SDC_NONE, SDC_DEMOTE)]
            if bad:
                # per-column detection: roll the whole block back to the
                # last VERIFIED iterates and raise DETECTED_SDC — clean
                # columns' verified state is preserved, the resilient
                # wrapper re-solves (frozen-instantly for already-good
                # columns under the masked kernel)
                detector = SDC_DETECTOR_NAMES.get(
                    int(det_h[bad[0]]), str(int(det_h[bad[0]])))
                record_sdc(checks, len(bad), int(rrc_h.sum()))
                X[...] = np.asarray(
                    jax.device_get(Xv))[: mat.shape[0]].astype(
                        X.dtype, copy=False)
                raise SilentCorruptionError(
                    "KSPSolveMany", detector,
                    int(max(iters[j] for j in bad)),
                    detail=f"columns {bad} flagged")
            demoted = [j for j in range(k)
                       if int(det_h[j]) == SDC_DEMOTE]
            if demoted:
                # CA-CG demotion (see _demote_sstep): trusted iterates,
                # classic-CG continuation for the whole block
                record_sdc(checks, 0, int(rrc_h.sum()))
                return self._demote_sstep_many(
                    B, X, iters=iters, rrc=int(rrc_h.sum()),
                    checks=checks, t0=t0, demoted=demoted)
            record_sdc(checks, 0, int(rrc_h.sum()))
        if gate:
            trn_h = np.asarray(fetch[i_extra], dtype=float)
            bn_h = np.asarray(fetch[i_extra + 1], dtype=float)
        # always k per-column entries (empty without monitoring) so the
        # result shape never depends on which path routed the solve
        histories = [[] for _ in range(k)]
        if monitored:
            # replay the recorded per-column entries to the user monitors
            # and the KSP history, column-major (the same delivery the
            # sequential fallback gives, so monitoring doesn't silently
            # flip off with the internal routing); slot index IS the
            # iteration number (-1 = never written, _HistMonitorMany)
            hh = np.asarray(fetch[4])
            monitors = list(self._monitors)
            if self._monitor_flag and not self._monitors:
                monitors.append(
                    lambda ksp, kk, rn:
                    print(f"  {int(kk):4d} KSP Residual norm "
                          f"{float(rn):.12e}"))
            if getattr(self, "_history_reset", False):
                self._history.clear()
            for j in range(k):
                recorded = np.nonzero(hh[:, j] != -1.0)[0]
                histories[j] = [float(hh[i, j]) for i in recorded]
                for i in recorded:
                    for m in monitors:
                        m(self, int(i), float(hh[i, j]))
                    if (hasattr(self, "_history")
                            and len(self._history) < self._history_length):
                        self._history.append(float(hh[i, j]))
        for j in range(k):
            # NaN/Inf residuals must surface as DIVERGED_NANORINF, and
            # KSP_NORM_NONE reports CONVERGED_ITS (breakdown stays
            # visible) — the same per-solve bookkeeping as KSP.solve
            if not norm_none and not np.isfinite(rnorms[j]):
                reasons[j] = ConvergedReason.DIVERGED_NANORINF
            elif (norm_none
                  and reasons[j] != ConvergedReason.DIVERGED_BREAKDOWN):
                reasons[j] = ConvergedReason.CONVERGED_ITS
        if gate:
            # per-column true-residual gate: every column that claims
            # convergence must meet max(rtol*||b_j||, atol) in its TRUE
            # residual (the single-RHS gate's semantics, per column)
            target = np.maximum(rtol * bn_h, atol)
            self._last_reentries = 0
            prog2 = None
            while True:
                for j in range(k):
                    # margin-stall rescue: a recurrence that missed the
                    # margin-tightened target whose TRUE residual meets
                    # the un-margined one HAS converged
                    if (reasons[j] <= 0
                            and reasons[j] != ConvergedReason.DIVERGED_BREAKDOWN
                            and np.isfinite(trn_h[j])
                            and trn_h[j] <= target[j]):
                        reasons[j] = ConvergedReason.CONVERGED_RTOL
                        rnorms[j] = float(trn_h[j])
                bad = [j for j in range(k)
                       if reasons[j] > 0
                       and not (np.isfinite(trn_h[j])
                                and trn_h[j] <= target[j])]
                if not bad:
                    break
                if self._last_reentries == 3:
                    # the gate's contract: "converged" means the TRUE
                    # residual met the target — report honestly
                    for j in bad:
                        reasons[j] = ConvergedReason.DIVERGED_MAX_IT
                        rnorms[j] = float(trn_h[j])
                    break
                self._last_reentries += 1
                if prog2 is None:
                    # the re-entry program starts from the current block
                    # (guess nonzero); frozen-instantly for columns whose
                    # entry residual already meets their tolerance
                    prog2 = build_ksp_program_many(
                        comm, self._type, pc, mat, nrhs=k,
                        zero_guess=False, **build_kw)
                _telemetry.record_program_dispatch("ksp_many")
                out = prog2(mat.device_arrays(), pc.device_arrays(),
                            *cs_args, Bd, Xd,
                            dt.type(rtol * margin), dt.type(atol * margin),
                            dt.type(divtol), np.int32(self.max_it),
                            *guard_scalars)
                (Xd, it2, rn2, rs2, _h2), det2, rrc2, Xv2, trn2, bn2 = \
                    _unpack(out)
                f2 = jax.device_get((Xd, it2, rn2, rs2)
                                    + ((det2, rrc2) if guard else ())
                                    + (trn2, bn2))
                X[...] = np.asarray(f2[0])[: mat.shape[0]].astype(
                    X.dtype, copy=False)
                if guard:
                    from .krylov import SDC_DEMOTE
                    det2_h = np.asarray(f2[4])
                    bad2 = [j for j in range(k)
                            if int(det2_h[j]) not in (SDC_NONE,
                                                      SDC_DEMOTE)]
                    if bad2:
                        record_sdc(0, len(bad2), int(np.asarray(
                            f2[5]).sum()))
                        X[...] = np.asarray(
                            jax.device_get(Xv2))[: mat.shape[0]].astype(
                                X.dtype, copy=False)
                        raise SilentCorruptionError(
                            "KSPSolveMany",
                            SDC_DETECTOR_NAMES.get(int(det2_h[bad2[0]]),
                                                   str(int(det2_h[bad2[0]]))),
                            int(np.asarray(f2[1]).max(initial=0)),
                            detail=f"columns {bad2} flagged on gate "
                                   "re-entry")
                    dem2 = [j for j in range(k)
                            if int(det2_h[j]) == SDC_DEMOTE]
                    if dem2:
                        X[...] = np.asarray(f2[0])[: mat.shape[0]].astype(
                            X.dtype, copy=False)
                        # merge the re-entry pass's counters BEFORE the
                        # demoted continuation — the first-pass values
                        # alone would under-report exactly the solves
                        # that needed re-entry
                        it_re = np.asarray(f2[1])
                        return self._demote_sstep_many(
                            B, X,
                            iters=[iters[j] + int(it_re[j])
                                   for j in range(k)],
                            rrc=int(rrc_h.sum())
                            + int(np.asarray(f2[5]).sum()),
                            checks=checks, t0=t0, demoted=dem2)
                it2 = np.asarray(f2[1])
                rn2 = np.asarray(f2[2])
                rs2 = np.asarray(f2[3])
                trn_h = np.asarray(f2[-2], dtype=float)
                bn_h = np.asarray(f2[-1], dtype=float)
                target = np.maximum(rtol * bn_h, atol)
                for j in range(k):
                    iters[j] += int(it2[j])
                    rnorms[j] = float(rn2[j])
                    reasons[j] = (ConvergedReason.DIVERGED_NANORINF
                                  if not np.isfinite(rnorms[j])
                                  else int(rs2[j]))
            wall = time.perf_counter() - t0
        res = BatchedSolveResult(iterations=iters, residual_norms=rnorms,
                                 reasons=reasons, wall_time=wall, X=X,
                                 histories=histories)
        if guard:
            res.abft_checks = checks
            res.residual_replacements = int(rrc_h.sum())
        self.result_many = res
        record_event(f"KSPSolveMany({self._type}+{pc.get_type()},k={k})",
                     mat.shape[0], max(iters) if iters else 0, wall,
                     max(reasons) if res.converged else min(reasons))
        return res

    def _solve_many_sequential(self, B, X) -> BatchedSolveResult:
        """Per-column fallback for configurations without a batched
        kernel (non-CG types, PCs without a batched apply, the gate):
        ``nrhs`` ordinary solves, same per-column results, assembled into
        one :class:`BatchedSolveResult`."""
        mat = self._mat
        k = B.shape[1]
        res = BatchedSolveResult(X=X)
        t0 = time.perf_counter()
        for j in range(k):
            xv = Vec.from_global(mat.comm, X[:, j], dtype=mat.dtype,
                                 layout=mat.layout)
            bv = Vec.from_global(mat.comm, B[:, j], dtype=mat.dtype,
                                 layout=mat.layout)
            # with reset=False (the petsc4py default) the KSP history
            # accumulates across solves — slice off only THIS column's
            # entries so per-column histories stay per-column
            prev = len(getattr(self, "_history", ()))
            sub = self.solve(bv, xv)
            X[:, j] = xv.to_numpy().astype(X.dtype, copy=False)
            res.iterations.append(sub.iterations)
            res.residual_norms.append(sub.residual_norm)
            res.reasons.append(sub.reason)
            if hasattr(self, "_history"):
                hist = self.get_convergence_history()
                res.histories.append([float(v) for v in
                                      hist[0 if self._history_reset
                                           else prev:]])
            else:
                res.histories.append([])
        res.wall_time = time.perf_counter() - t0
        self.result_many = res
        return res

    # ---- introspection (petsc4py-shaped) ------------------------------------
    def get_iteration_number(self) -> int:
        return self.result.iterations

    getIterationNumber = get_iteration_number

    def get_residual_norm(self) -> float:
        return self.result.residual_norm

    getResidualNorm = get_residual_norm

    def get_converged_reason(self) -> int:
        return self.result.reason

    getConvergedReason = get_converged_reason

    def get_tolerances(self):
        """(rtol, atol, divtol, max_it) — petsc4py's getTolerances."""
        return (self.rtol, self.atol, self.divtol, self.max_it)

    getTolerances = get_tolerances

    def get_operators(self):
        """(A, P) — the operator and the preconditioning matrix.

        Raises before ``set_operators``, like petsc4py."""
        if self._mat is None:
            raise RuntimeError("KSP.get_operators: no operators set")
        return (self._mat, self.get_pc()._mat)

    getOperators = get_operators

    def view(self, file=None):
        """Print the solver configuration (-ksp_view analog)."""
        import sys
        file = file or sys.stdout
        pc = self.get_pc()
        print(f"KSP Object: type={self._type}\n"
              f"  tolerances: rtol={self.rtol:g}, atol={self.atol:g}, "
              f"divtol={self.divtol:g}, max_it={self.max_it}\n"
              f"  norm type: {self.get_norm_type()}\n"
              f"  gmres restart: {self.restart}\n"
              f"  PC Object: type={pc.get_type()}, "
              f"factor solver: {pc._factor_solver_type}\n"
              f"  mesh devices: {self.comm.size if self.comm else '?'}",
              file=file)

    @property
    def converged(self) -> bool:
        return self.result.converged

    def __repr__(self):
        return (f"KSP(type={self._type!r}, pc={self.get_pc().get_type()!r}, "
                f"rtol={self.rtol}, max_it={self.max_it})")
