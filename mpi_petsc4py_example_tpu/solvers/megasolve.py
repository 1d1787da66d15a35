"""Megasolve — whole-solve fusion: one dispatch per request (ROADMAP 3a).

After AOT caching, what remains of a short solve's end-to-end wall is
per-PHASE dispatch. ``RefinedKSP``
drives its outer Wilkinson recurrence from the HOST — the inner
low-precision solve, the fp64 true residual, the correction AXPY, and
the epilogue re-verification each cost a separate compiled-program
launch per outer step. That host round-trip between device phases is
latency the hardware never sees ("Pipelined, Flexible Krylov Subspace
Methods" attacks it at the reduction level, the matrix-free-FEM
data-locality work at the kernel level — this module attacks it at the
PROGRAM level).

This module composes the existing :mod:`.cg_plans` loop bodies into ONE
device program per request class::

    outer lax.while_loop over the fp64 refinement recurrence
      r_lp  = store(r)                       # cast to the inner channel
      dx    = inner CG plan loop (A_lp dx = r_lp)   # nested while_loop
      x    += up(dx)                         # fp64 correction AXPY
      r     = b - A64 x                      # fp64 TRUE residual
      exit gate: ||r|| <= max(rtol*||b||, atol)     # verified answer

so a ``RefinedKSP.solve`` (and ``solve_many`` block) costs exactly ONE
dispatch — and because the exit gate IS the fp64 true residual, the
returned iterate is verified by construction (the unfused path's
``-ksp_true_residual_check`` epilogue, folded into the loop condition).
With the operator shared (``outer_op is None``) the same program is the
uniform-precision fused gate: KSP.solve re-enters in-program until the
TRUE residual passes, one launch instead of gate-re-entry dispatches.

The inner loop is a PLAN INVOCATION, not a kernel copy: classic
(:func:`cg_plans.classic_cg_loop`) or pipelined
(:func:`cg_plans.pipelined_cg_loop`), plain or silent-corruption
guarded, single-RHS or batched (``ManyBatch``) — and the preconditioner
is whatever ``pc.local_apply`` closes over, INCLUDING the geometric-MG
slab V-cycle (solvers/mg.py): the V-cycle runs as a callable inner plan
inside the fused body rather than a separately-launched phase.

Resilience semantics are preserved: the inner plan loops keep the
trace-time silent-fault applicators (``spmv.result``/``pc.apply``) and
the injectable ``comm.psum``, detection inside the fused loop freezes
the outer recurrence and surfaces ``(det, rrc, xv)`` — ``xv`` the last
outer iterate whose fp64 TRUE residual was measured (verified by the
exit-gate channel itself) — exactly the rollback carry the unfused path
hands ``resilience/retry.py``. The fp64 outer residual rides PLAIN
``lax.psum`` (the verifier-channel discipline: a corrupted verifier
would lie about recovery).

Program/AOT cache keys carry the refine configuration (both operators'
program keys + precision plans + guard flags); the refine PARAMETERS
(rtol/inner_rtol/refine_max/maxit) are runtime scalars, so tuning them
never recompiles. ``-ksp_megasolve`` routes KSP/RefinedKSP through
here; the telemetry dispatch counter
(``telemetry.spans.record_program_dispatch``) makes the "one launch" a
measured fact per root span.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..parallel.mesh import DeviceComm
from ..resilience import abft as _abft
from ..resilience import faults as _faults
from ..utils.convergence import ConvergedReason as CR
from ..utils.dtypes import is_complex
from . import cg_plans as _plans
from .krylov import (_consumed_zeros, _make_guard, _make_pipe_guard,
                     _make_sstep_guard, _psum, cg_stencil_kernel,
                     cg_stencil_kernel_many, donation_supported)

#: KSP types with a fused whole-solve program (the plan-built CG family)
MEGASOLVE_TYPES = ("cg", "pipecg", "sstep")

#: outer refinement-step cap the uniform-precision (gate-fusion) path
#: runs at: the first full solve + the unfused gate's 3 re-entries
GATE_REFINE_MAX = 4

_MEGASOLVE_CACHE: dict = {}
_MEGASOLVE_CACHE_MANY: dict = {}
#: the persistent-serving variants (serving/persistent.py): same traced
#: body as the batched program but AOT-labeled "persistent_serve" and
#: fed PER-SLOT (nrhs,)-shaped tolerance scalars
_PERSISTENT_CACHE: dict = {}


def megasolve_supported(ksp_type: str, pc, operator,
                        nrhs: int | None = None) -> bool:
    """Whether this (type, PC, operator) configuration has a fused
    whole-solve program — the KSP routing test (ineligible
    configurations fall through to the unfused path silently).

    Batched (``nrhs``) programs additionally need a batched PC apply
    (``krylov.batched_pc_supported``)."""
    if ksp_type not in MEGASOLVE_TYPES:
        return False
    if pc.kind == "hostlu":
        return False                  # host factor: no in-program apply
    if not hasattr(operator, "local_spmv"):
        return False
    if nrhs is not None:
        from .krylov import batched_pc_supported
        if not batched_pc_supported(pc):
            return False
    return True


def megasolve_stencil_supported(ksp_type: str, pc, operator,
                                nrhs: int | None = None,
                                guard: bool = False) -> bool:
    """Whether the fused megasolve INNER loop can take the stencil
    fused-dot fast path (``-ksp_megasolve_stencil_fastpath``): the
    uniform-diagonal stencil operator's Pallas ``local_matvec_dot``
    family replaces the general flat-apply plan, so the SpMV and the
    ``<p, Ap>`` reduction run in one VMEM-resident pass inside the
    fusion. Mirrors krylov's ``stencil_cg`` gate minus the guarded and
    MG flavors: the megasolve guard namespaces carry no stencil phases,
    and the slab V-cycle stays on the general plan."""
    if ksp_type != "cg" or guard:
        return False
    if is_complex(np.dtype(operator.dtype)):
        return False
    if pc.get_type() not in ("none", "jacobi"):
        return False
    if (pc.get_type() == "jacobi"
            and getattr(pc, "_mat", None) is not operator):
        return False
    need = ["local_matvec_dot", "grid3d"]
    if nrhs is not None:
        need.append("local_matvec_dot_many")
    if not all(hasattr(operator, h) for h in need):
        return False
    return getattr(operator, "uniform_diagonal", None) is not None


def _operators_compatible(inner_op, outer_op) -> None:
    if outer_op.shape != inner_op.shape:
        raise ValueError(
            f"megasolve: outer operator shape {outer_op.shape} != inner "
            f"{inner_op.shape} — both precisions of the SAME operator are "
            "required (the outer op supplies the exact residual)")


def _reason_outer(conv, rn, atol, brk, ibrk, stag_reason):
    """Outer-loop exit code: converged means the TRUE residual met the
    target (elementwise for the batched path). A stagnation exit whose
    last inner solve genuinely BROKE DOWN reports DIVERGED_BREAKDOWN
    (the fallback chain's escalation trigger — an indefinite operator
    must still escalate under fusion); plain drift stagnation reports
    ``stag_reason``, a RUNTIME scalar carrying the caller's semantics:
    DIVERGED_BREAKDOWN for the refinement recurrence (RefinedKSP's
    unfused Wilkinson loop reports exactly that), DIVERGED_MAX_IT for
    the uniform-precision gate (the unfused -ksp_true_residual_check
    loop's could-not-close-the-drift code, which resilience/fallback.py
    deliberately does NOT escalate on)."""
    return jnp.where(
        conv, jnp.where(rn <= atol, CR.CONVERGED_ATOL, CR.CONVERGED_RTOL),
        jnp.where(brk,
                  jnp.where(ibrk, CR.DIVERGED_BREAKDOWN, stag_reason),
                  CR.DIVERGED_MAX_IT)).astype(jnp.int32)


def build_megasolve_program(comm: DeviceComm, ksp_type: str, pc, inner_op,
                            outer_op=None, *, zero_guess: bool = True,
                            abft: bool = False, abft_pc: bool = False,
                            rr: bool = False, donate: bool = False,
                            sstep_s: int = 4,
                            stencil_fastpath: bool = False):
    """Build (or fetch cached) the fused whole-solve program.

    Signature of the returned callable::

        x, steps, iters, rnorm, reason = prog(
            [outer_arrays,] inner_arrays, pc_arrays, [cs, [csM,]] b, x0,
            rtol, atol, inner_rtol, dtol, maxit, refine_max, stag_reason
            [, abft_tol, rr_n])

    ``b``/``x0`` travel in the OUTER dtype (the exact-residual channel —
    fp64 under refinement, the operator dtype when shared);
    ``outer_arrays`` is present only when ``outer_op`` is a distinct
    operator (``None`` shares the inner operands — the uniform-precision
    gate-fusion form). ``steps`` is the outer refinement-step count,
    ``iters`` the TOTAL inner iterations across steps, ``rnorm`` the
    final fp64 TRUE residual norm (the exit gate's own measurement —
    there is no epilogue because the loop condition IS the
    verification). ``rtol``/``atol`` are the outer targets,
    ``inner_rtol`` the per-correction target (the caller floors it at a
    few storage epsilons — RefinedKSP._effective_inner_rtol), ``maxit``
    the inner per-correction iteration cap, ``refine_max`` the outer
    step cap — ALL runtime scalars (tuning never recompiles).

    With the guard on (``abft``/``rr``), three outputs append —
    ``(det, rrc, xv)``: the sticky detector code surfaced from the
    nested guarded plan loop, the replacement count, and the last outer
    iterate whose fp64 true residual was measured (the rollback carry).

    ``donate=True`` donates ``x0`` (the caller treats the buffer as
    consumed; zero extra device allocations per repeat solve).
    """
    axis = comm.axis
    shared = outer_op is None or outer_op is inner_op
    out_op = inner_op if shared else outer_op
    _operators_compatible(inner_op, out_op)
    n = inner_op.shape[0]
    in_dt = np.dtype(inner_op.dtype)
    out_dt = np.dtype(out_op.dtype)
    if is_complex(in_dt) != is_complex(out_dt):
        raise ValueError("megasolve: inner/outer operators must agree on "
                         "real vs complex scalars")
    prec = _plans.precision_plan(in_dt)
    guard_k = bool(abft or rr)
    abft_k = bool(abft)
    abft_pc_k = bool(abft and abft_pc)
    trace_nonce = _faults.trace_key()
    from ..utils import aot
    aot_on = aot.aot_enabled() and trace_nonce is None
    donate_k = bool(donate) and donation_supported()
    sstep_k = max(1, int(sstep_s)) if ksp_type == "sstep" else 0
    stencil_k = bool(stencil_fastpath)
    if stencil_k and not megasolve_stencil_supported(ksp_type, pc, inner_op,
                                                     guard=guard_k):
        raise ValueError(
            "megasolve: stencil fast path requested for an ineligible "
            "(type, PC, operator) configuration — gate the routing on "
            "megasolve_stencil_supported")
    key = (comm.mesh, axis, ksp_type, pc.program_key(), n, prec.key(),
           str(out_dt), shared, inner_op.program_key(),
           out_op.program_key(), bool(zero_guess), abft_k, abft_pc_k,
           bool(rr), donate_k, sstep_k, stencil_k, trace_nonce, aot_on)
    cached = _MEGASOLVE_CACHE.get(key)
    if cached is not None:
        return cached

    inner_spmv = inner_op.local_spmv(comm)
    outer_spmv = inner_spmv if shared else out_op.local_spmv(comm)
    pc_apply = pc.local_apply(comm, n)
    matvec_dot = inner_op.local_matvec_dot(comm) if stencil_k else None
    in_specs_inner = inner_op.op_specs(axis)
    in_specs_outer = None if shared else out_op.op_specs(axis)
    mixed = prec.mixed
    _up = prec.up
    stack_dt = prec.reduce

    def run(outer_arrays, inner_arrays, pc_arrays, cs, csM, b, x0, rtol,
            atol, inner_rtol, dtol, maxit, refine_max, stag_reason,
            abft_tol, rr_n, max_repl):
        if zero_guess:
            x0 = _consumed_zeros(x0) if donate_k else jnp.zeros_like(b)
        # inner plan closures: the SOLVER channel — injectable silent
        # faults + the faulted psum, exactly as the unfused programs
        A_in = lambda v: _abft.apply_silent_fault(
            "spmv.result", inner_spmv(inner_arrays, v))
        M_in = lambda r: _abft.apply_silent_fault(
            "pc.apply", pc_apply(pc_arrays, r))
        pdot = lambda u, v: _psum(jnp.vdot(_up(u), _up(v)), axis)
        pnorm = lambda u: jnp.sqrt(jnp.real(_psum(jnp.vdot(_up(u), _up(u)),
                                                  axis)))

        # OUTER (exact-residual) channel: plain lax.psum — the verifier
        # discipline; a corrupted exit gate would lie about the answer.
        # Norms accumulate in the outer REDUCE dtype (identity for fp64
        # refinement; f32 when a sub-f32 operator is fused directly)
        from ..utils.dtypes import reduce_dtype
        out_rdt = reduce_dtype(out_dt)
        ou = ((lambda v: v.astype(out_rdt)) if out_rdt != out_dt
              else (lambda v: v))

        def onorm(v):
            return jnp.sqrt(jnp.real(lax.psum(jnp.vdot(ou(v), ou(v)),
                                              axis)))

        A_out = (lambda v: outer_spmv(inner_arrays if shared
                                      else outer_arrays, v))
        bnorm = onorm(b)
        tol = jnp.maximum(rtol * bnorm, atol)
        itol_dt = jnp.real(jnp.zeros((), stack_dt)).dtype
        inner_atol = tol.astype(itol_dt)   # floor: never solve a
        #                                    correction deeper than the
        #                                    outer target itself

        if stencil_k:
            # fused-dot stencil fast path (krylov.cg_stencil_kernel):
            # SpMV + <p, Ap> in one VMEM-resident Pallas pass; jacobi
            # collapses to the scalar uniform-diagonal multiply
            idt = stack_dt if mixed else in_dt
            inv_diag = (jnp.asarray(1.0, idt) if pc.get_type() == "none"
                        else jnp.asarray(1.0 / inner_op.uniform_diagonal,
                                         idt))
            pdot3 = lambda u, v: _psum(jnp.sum(_up(u) * _up(v)), axis)
            pnorm3 = lambda u: jnp.sqrt(_psum(jnp.sum(_up(u) * _up(u)),
                                              axis))

            def Adot3(v):
                y, d = matvec_dot(inner_arrays, v)
                return _abft.apply_silent_fault("spmv.result", y), d

        g = None
        if guard_k:
            flavor = dict(dot=lambda u, v: jnp.vdot(_up(u), _up(v)),
                          tsum=lambda u: jnp.sum(_up(u)),
                          tasum=lambda u: jnp.sum(jnp.abs(_up(u))),
                          cmul=lambda c, v: _up(c) * _up(v),
                          no_bad=lambda v: False,
                          pdot=pdot, pnorm=pnorm,
                          eps_dtype=in_dt if mixed else None)
            mk = (_make_pipe_guard if ksp_type == "pipecg"
                  else _make_sstep_guard if ksp_type == "sstep"
                  else _make_guard)
            g = mk(stack_dt, axis, cs, csM, abft_tol, rr_n, **flavor)

        def inner_solve(r_lp):
            x0_lp = jnp.zeros_like(r_lp)
            kw = dict(dtol=dtol)
            if mixed:
                kw["prec"] = prec
            if ksp_type == "sstep":
                return _plans.sstep_cg_loop(
                    b=r_lp, x0=x0_lp, rtol=inner_rtol, atol=inner_atol,
                    maxit=maxit, s=sstep_k,
                    greduce=lambda parts: _plans.fuse_gram_psum(
                        parts, _psum, axis, stack_dt),
                    A=A_in, M=M_in, pnorm=pnorm, guard=g,
                    max_repl=max_repl, **kw)
            if ksp_type == "pipecg":
                if g is not None:
                    return _plans.pipelined_cg_loop(
                        b=r_lp, x0=x0_lp, rtol=inner_rtol, atol=inner_atol,
                        maxit=maxit, A=A_in, M=M_in, pnorm=pnorm,
                        fused=g.fused, guard=g, **kw)

                def fused(r_, u_, w_):
                    s = _plans.fuse_psum(
                        [jnp.vdot(_up(r_), _up(u_)),
                         jnp.vdot(_up(w_), _up(u_)),
                         jnp.vdot(_up(r_), _up(r_))], _psum, axis,
                        stack_dt)
                    return s[0], s[1], s[2]
                return _plans.pipelined_cg_loop(
                    b=r_lp, x0=x0_lp, rtol=inner_rtol, atol=inner_atol,
                    maxit=maxit, A=A_in, M=M_in, pnorm=pnorm, fused=fused,
                    **kw)
            if stencil_k:
                return cg_stencil_kernel(
                    Adot3, inv_diag, pdot3, pnorm3, r_lp, x0_lp,
                    inner_rtol, inner_atol, maxit, dtol=dtol,
                    grid3d=inner_op.grid3d,
                    prec=prec if mixed else None)
            return _plans.classic_cg_loop(
                b=r_lp, x0=x0_lp, rtol=inner_rtol, atol=inner_atol,
                maxit=maxit, A=A_in, M=M_in, pdot=pdot, pnorm=pnorm,
                guard=g, **kw)

        r0 = b - A_out(x0)
        rn0 = onorm(r0)
        i0 = jnp.int32(0)
        st0 = dict(x=x0, r=r0, rn=rn0, it=i0, ii=i0,
                   brk=jnp.asarray(False), ibrk=jnp.asarray(False))
        if guard_k:
            st0.update(det=i0, rrc=i0, xv=x0)

        def cond(st):
            live = ((st["rn"] > tol) & ~st["brk"]
                    & (st["it"] < refine_max))
            if guard_k:
                live = live & (st["det"] == 0)
            return live

        def body(st):
            r_lp = st["r"].astype(in_dt)
            out = inner_solve(r_lp)
            dx, it_i, in_reason = out[0], out[1], out[3]
            if guard_k:
                det_i, rrc_i = out[5], out[6]
                detected = det_i != 0
                # a poisoned correction is never applied: the carry
                # stays at the last iterate whose fp64 residual was
                # measured — the verified rollback target
                x_new = jnp.where(detected, st["x"],
                                  st["x"] + dx.astype(out_dt))
            else:
                x_new = st["x"] + dx.astype(out_dt)
            r_new = b - A_out(x_new)
            rn_new = onorm(r_new)
            # stagnation guard (RefinedKSP semantics): a correction the
            # inner precision cannot resolve stops the recurrence
            stag = (rn_new > tol) & (rn_new >= 0.9 * st["rn"])
            st2 = dict(x=x_new, r=r_new, rn=rn_new,
                       it=st["it"] + 1, ii=st["ii"] + it_i,
                       brk=st["brk"] | stag,
                       ibrk=st["ibrk"]
                       | (stag & (in_reason == CR.DIVERGED_BREAKDOWN)))
            if guard_k:
                st2.update(det=jnp.where(detected, det_i, st["det"]),
                           rrc=st["rrc"] + rrc_i,
                           xv=jnp.where(detected, st["xv"], x_new))
            return st2

        st = lax.while_loop(cond, body, st0)
        conv = st["rn"] <= tol
        out = (st["x"], st["it"], st["ii"], st["rn"],
               _reason_outer(conv, st["rn"], atol, st["brk"],
                             st["ibrk"], stag_reason))
        if guard_k:
            out = out + (st["det"], st["rrc"], st["xv"])
        return out

    # trailing runtime scalars: the sstep guard appends its
    # basis-restart budget (-ksp_sstep_max_replacements)
    nsc = 7 + ((3 if ksp_type == "sstep" else 2) if guard_k else 0)
    ncs = abft_k + abft_pc_k

    def local_fn(*args):
        i = 0
        outer_arrays = None
        if not shared:
            outer_arrays = args[i]
            i += 1
        inner_arrays, pc_arrays = args[i], args[i + 1]
        i += 2
        cs = csM = None
        if abft_k:
            cs = args[i]
            i += 1
        if abft_pc_k:
            csM = args[i]
            i += 1
        b, x0 = args[i], args[i + 1]
        scal = args[i + 2:]
        max_repl = None
        if guard_k and ksp_type == "sstep":
            (rtol, atol, inner_rtol, dtol, maxit, refine_max,
             stag_reason, abft_tol, rr_n, max_repl) = scal
        elif guard_k:
            (rtol, atol, inner_rtol, dtol, maxit, refine_max,
             stag_reason, abft_tol, rr_n) = scal
        else:
            (rtol, atol, inner_rtol, dtol, maxit, refine_max,
             stag_reason) = scal
            abft_tol = rr_n = None
        return run(outer_arrays, inner_arrays, pc_arrays, cs, csM, b, x0,
                   rtol, atol, inner_rtol, dtol, maxit, refine_max,
                   stag_reason, abft_tol, rr_n, max_repl)

    in_specs = (() if shared else (in_specs_outer,)) \
        + (in_specs_inner, pc.in_specs(axis)) \
        + tuple(P(axis) for _ in range(ncs)) \
        + (P(axis), P(axis)) + tuple(P() for _ in range(nsc))
    x0_idx = (0 if shared else 1) + 2 + ncs + 1
    out_specs = (P(axis), P(), P(), P(), P())
    if guard_k:
        out_specs = out_specs + (P(), P(), P(axis))
    dn = (x0_idx,) if donate_k else ()
    prog = jax.jit(comm.shard_map(local_fn, in_specs, out_specs),
                   donate_argnums=dn)
    if aot_on:
        prog = aot.wrap("megasolve", comm, key[1:], prog,
                        donate_argnums=dn)
    _MEGASOLVE_CACHE[key] = prog
    return prog


def build_megasolve_program_many(comm: DeviceComm, ksp_type: str, pc,
                                 inner_op, outer_op=None, *, nrhs: int,
                                 zero_guess: bool = True,
                                 abft: bool = False, abft_pc: bool = False,
                                 rr: bool = False, donate: bool = False,
                                 sstep_s: int = 4,
                                 stencil_fastpath: bool = False,
                                 persistent: bool = False):
    """Batched fused whole-solve program: ``nrhs`` refinement recurrences
    in lockstep over an ``(n_pad, nrhs)`` block, each outer step
    dispatching ONE nested batched CG plan loop — a served ``solve_many``
    block costs exactly one launch.

    Signature mirrors :func:`build_megasolve_program` with blocks for
    ``b``/``x0`` and per-column ``(nrhs,)`` outputs::

        X, steps, iters, rnorm, reason [, det, rrc, Xv] = prog(
            [outer_arrays,] inner_arrays, pc_arrays, [cs, [csM,]] B, X0,
            rtol, atol, inner_rtol, dtol, maxit, refine_max, stag_reason
            [, ...])

    Per-column masked freezing at BOTH levels: a column whose fp64 true
    residual meets its target freezes in the outer recurrence, and its
    zero correction RHS freezes instantly in the nested masked inner
    loop (its inner target — floored at the outer tolerance — already
    exceeds its residual), so converged columns cost nothing while
    stragglers refine. ``steps`` is the shared outer step count;
    ``iters`` per-column accumulated inner iterations. Outer stagnation
    is judged PER COLUMN (the unfused host loop can only stop when every
    column stagnates — the fused gate is strictly finer)."""
    axis = comm.axis
    shared = outer_op is None or outer_op is inner_op
    out_op = inner_op if shared else outer_op
    _operators_compatible(inner_op, out_op)
    n = inner_op.shape[0]
    in_dt = np.dtype(inner_op.dtype)
    out_dt = np.dtype(out_op.dtype)
    if is_complex(in_dt) != is_complex(out_dt):
        raise ValueError("megasolve: inner/outer operators must agree on "
                         "real vs complex scalars")
    prec = _plans.precision_plan(in_dt)
    guard_k = bool(abft or rr)
    abft_k = bool(abft)
    abft_pc_k = bool(abft and abft_pc)
    trace_nonce = _faults.trace_key()
    from ..utils import aot
    aot_on = aot.aot_enabled() and trace_nonce is None
    donate_k = bool(donate) and donation_supported()
    sstep_k = max(1, int(sstep_s)) if ksp_type == "sstep" else 0
    stencil_k = bool(stencil_fastpath)
    if stencil_k and not megasolve_stencil_supported(
            ksp_type, pc, inner_op, nrhs=nrhs, guard=guard_k):
        raise ValueError(
            "megasolve: stencil fast path requested for an ineligible "
            "(type, PC, operator) configuration — gate the routing on "
            "megasolve_stencil_supported")
    # the persistent-serving variant is the SAME traced body fed
    # (nrhs,)-shaped per-slot tolerance scalars — a distinct aval
    # signature, so it lives in its own cache under its own AOT kind
    kind = "persistent_serve" if persistent else "megasolve_many"
    cache = _PERSISTENT_CACHE if persistent else _MEGASOLVE_CACHE_MANY
    key = (comm.mesh, axis, ksp_type, pc.program_key(), n, prec.key(),
           str(out_dt), shared, int(nrhs), inner_op.program_key(),
           out_op.program_key(), bool(zero_guess), abft_k, abft_pc_k,
           bool(rr), donate_k, sstep_k, stencil_k, trace_nonce, aot_on)
    cached = cache.get(key)
    if cached is not None:
        return cached

    inner_spmv = inner_op.local_spmv_many(comm)
    outer_spmv = inner_spmv if shared else out_op.local_spmv_many(comm)
    pc_apply = pc.local_apply_many(comm, n)
    matvec_dot_many = (inner_op.local_matvec_dot_many(comm)
                       if stencil_k else None)
    if pc_apply is None:
        raise ValueError(
            f"pc {pc.get_type()!r} has no batched apply — batched "
            "megasolve needs one (krylov.batched_pc_supported)")
    in_specs_inner = inner_op.op_specs(axis)
    in_specs_outer = None if shared else out_op.op_specs(axis)
    mixed = prec.mixed
    _up = prec.up
    stack_dt = prec.reduce

    def run(outer_arrays, inner_arrays, pc_arrays, cs, csM, B, X0, rtol,
            atol, inner_rtol, dtol, maxit, refine_max, stag_reason,
            abft_tol, rr_n, max_repl):
        if zero_guess:
            X0 = _consumed_zeros(X0) if donate_k else jnp.zeros_like(B)
        A_in = lambda V: _abft.apply_silent_fault(
            "spmv.result", inner_spmv(inner_arrays, V))
        M_in = lambda R: _abft.apply_silent_fault(
            "pc.apply", pc_apply(pc_arrays, R))
        cdot = lambda U, V: jnp.sum(jnp.conj(_up(U)) * _up(V), axis=0)
        pdotc = lambda U, V: _psum(cdot(U, V), axis)
        pnormc = lambda U: jnp.sqrt(jnp.real(_psum(cdot(U, U), axis)))

        def pduo(R, Z):
            s = _psum(jnp.stack([cdot(R, Z), cdot(R, R)]), axis)
            return s[0], s[1]

        from ..utils.dtypes import reduce_dtype
        out_rdt = reduce_dtype(out_dt)
        ou = ((lambda V: V.astype(out_rdt)) if out_rdt != out_dt
              else (lambda V: V))

        def onormc(V):            # outer exact channel: plain psum
            Vu = ou(V)
            return jnp.sqrt(jnp.real(lax.psum(
                jnp.sum(jnp.conj(Vu) * Vu, axis=0), axis)))

        A_out = (lambda V: outer_spmv(inner_arrays if shared
                                      else outer_arrays, V))
        bnorm = onormc(B)
        tol = jnp.maximum(rtol * bnorm, atol)
        itol_dt = jnp.real(jnp.zeros((), stack_dt)).dtype
        inner_atol = tol.astype(itol_dt)

        if stencil_k:
            # batched fused-dot stencil fast path: state in
            # (nrhs,) + grid3d slabs, SpMV + per-column <p_j, A p_j>
            # in one fused pass (krylov.cg_stencil_kernel_many)
            idt = stack_dt if mixed else in_dt
            inv_diag = (jnp.asarray(1.0, idt) if pc.get_type() == "none"
                        else jnp.asarray(1.0 / inner_op.uniform_diagonal,
                                         idt))
            pdotc3 = lambda U, V: _psum(
                jnp.sum(_up(U) * _up(V), axis=(1, 2, 3)), axis)

            def Adot3(V):
                Y, d = matvec_dot_many(inner_arrays, V)
                return _abft.apply_silent_fault("spmv.result", Y), d

        g = None
        if guard_k:
            flavor = dict(
                dot=cdot, tsum=lambda U: jnp.sum(_up(U), axis=0),
                tasum=lambda U: jnp.sum(jnp.abs(_up(U)), axis=0),
                cmul=lambda c, V: _up(c)[:, None] * _up(V),
                no_bad=lambda V: jnp.zeros(V.shape[1], bool),
                pdot=pdotc, pnorm=pnormc,
                eps_dtype=in_dt if mixed else None)
            mk = (_make_pipe_guard if ksp_type == "pipecg"
                  else _make_sstep_guard if ksp_type == "sstep"
                  else _make_guard)
            g = mk(stack_dt, axis, cs, csM, abft_tol, rr_n, **flavor)

        def inner_solve(R_lp):
            X0_lp = jnp.zeros_like(R_lp)
            kw = dict(dtol=dtol, bp=_plans.ManyBatch("cols"))
            if mixed:
                kw["prec"] = prec
            if ksp_type == "sstep":
                return _plans.sstep_cg_loop(
                    b=R_lp, x0=X0_lp, rtol=inner_rtol, atol=inner_atol,
                    maxit=maxit, s=sstep_k,
                    greduce=lambda parts: _plans.fuse_gram_psum(
                        parts, _psum, axis, stack_dt, batched=True),
                    A=A_in, M=M_in, pnorm=pnormc, guard=g,
                    max_repl=max_repl, **kw)
            if ksp_type == "pipecg":
                if g is not None:
                    return _plans.pipelined_cg_loop(
                        b=R_lp, x0=X0_lp, rtol=inner_rtol,
                        atol=inner_atol, maxit=maxit, A=A_in, M=M_in,
                        pnorm=pnormc, fused=g.fused, guard=g, **kw)

                def fusedc(Rb, U, W):
                    s = _plans.fuse_psum(
                        [cdot(Rb, U), cdot(W, U), cdot(Rb, Rb)], _psum,
                        axis, stack_dt)
                    return s[0], s[1], s[2]
                return _plans.pipelined_cg_loop(
                    b=R_lp, x0=X0_lp, rtol=inner_rtol, atol=inner_atol,
                    maxit=maxit, A=A_in, M=M_in, pnorm=pnormc,
                    fused=fusedc, **kw)
            if stencil_k:
                return cg_stencil_kernel_many(
                    Adot3, inv_diag, pdotc3, R_lp, X0_lp,
                    inner_rtol, inner_atol, maxit, dtol=dtol,
                    grid3d=inner_op.grid3d,
                    prec=prec if mixed else None)
            return _plans.classic_cg_loop(
                b=R_lp, x0=X0_lp, rtol=inner_rtol, atol=inner_atol,
                maxit=maxit, A=A_in, M=M_in, pdot=pdotc, pnorm=pnormc,
                pduo=None if g is not None else pduo, guard=g, **kw)

        R0 = B - A_out(X0)
        rn0 = onormc(R0)
        k = B.shape[1]
        zc = jnp.zeros((k,), jnp.int32)
        st0 = dict(X=X0, R=R0, rn=rn0, it=jnp.int32(0), ii=zc,
                   brk=jnp.zeros((k,), bool),
                   ibrk=jnp.zeros((k,), bool))
        if guard_k:
            st0.update(det=zc, rrc=zc, Xv=X0)

        def active(st):
            live = (st["rn"] > tol) & ~st["brk"]
            if guard_k:
                live = live & (st["det"] == 0)
            return live

        def cond(st):
            return jnp.any(active(st)) & (st["it"] < refine_max)

        def body(st):
            act = active(st)
            R_lp = st["R"].astype(in_dt)
            out = inner_solve(R_lp)
            dX, it_i, in_reason = out[0], out[1], out[3]
            if guard_k:
                det_i, rrc_i = out[5], out[6]
                detected = act & (det_i != 0)
                applym = (act & ~detected)[None, :]
            else:
                detected = None
                applym = act[None, :]
            X_new = jnp.where(applym, st["X"] + dX.astype(out_dt),
                              st["X"])
            R_new = B - A_out(X_new)
            rn_new = onormc(R_new)
            stag = act & (rn_new > tol) & (rn_new >= 0.9 * st["rn"])
            st2 = dict(X=X_new, R=R_new, rn=rn_new, it=st["it"] + 1,
                       ii=st["ii"] + jnp.where(act, it_i, 0),
                       brk=st["brk"] | stag,
                       ibrk=st["ibrk"]
                       | (stag & (in_reason == CR.DIVERGED_BREAKDOWN)))
            if guard_k:
                st2.update(
                    det=jnp.where(detected, det_i, st["det"]),
                    rrc=st["rrc"] + jnp.where(act, rrc_i, 0),
                    Xv=jnp.where(detected[None, :], st["Xv"], X_new))
            return st2

        st = lax.while_loop(cond, body, st0)
        conv = st["rn"] <= tol
        out = (st["X"], st["it"], st["ii"], st["rn"],
               _reason_outer(conv, st["rn"], atol, st["brk"],
                             st["ibrk"], stag_reason))
        if guard_k:
            out = out + (st["det"], st["rrc"], st["Xv"])
        return out

    nsc = 7 + ((3 if ksp_type == "sstep" else 2) if guard_k else 0)
    ncs = abft_k + abft_pc_k

    def local_fn(*args):
        i = 0
        outer_arrays = None
        if not shared:
            outer_arrays = args[i]
            i += 1
        inner_arrays, pc_arrays = args[i], args[i + 1]
        i += 2
        cs = csM = None
        if abft_k:
            cs = args[i]
            i += 1
        if abft_pc_k:
            csM = args[i]
            i += 1
        B, X0 = args[i], args[i + 1]
        scal = args[i + 2:]
        max_repl = None
        if guard_k and ksp_type == "sstep":
            (rtol, atol, inner_rtol, dtol, maxit, refine_max,
             stag_reason, abft_tol, rr_n, max_repl) = scal
        elif guard_k:
            (rtol, atol, inner_rtol, dtol, maxit, refine_max,
             stag_reason, abft_tol, rr_n) = scal
        else:
            (rtol, atol, inner_rtol, dtol, maxit, refine_max,
             stag_reason) = scal
            abft_tol = rr_n = None
        return run(outer_arrays, inner_arrays, pc_arrays, cs, csM, B, X0,
                   rtol, atol, inner_rtol, dtol, maxit, refine_max,
                   stag_reason, abft_tol, rr_n, max_repl)

    in_specs = (() if shared else (in_specs_outer,)) \
        + (in_specs_inner, pc.in_specs(axis)) \
        + tuple(P(axis) for _ in range(ncs)) \
        + (P(axis, None), P(axis, None)) \
        + tuple(P() for _ in range(nsc))
    x0_idx = (0 if shared else 1) + 2 + ncs + 1
    out_specs = (P(axis, None), P(), P(), P(), P())
    if guard_k:
        out_specs = out_specs + (P(), P(), P(axis, None))
    dn = (x0_idx,) if donate_k else ()
    prog = jax.jit(comm.shard_map(local_fn, in_specs, out_specs),
                   donate_argnums=dn)
    if aot_on:
        prog = aot.wrap(kind, comm, key[1:], prog, donate_argnums=dn)
    cache[key] = prog
    return prog
