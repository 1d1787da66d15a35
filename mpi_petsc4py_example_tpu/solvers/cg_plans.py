"""Composable CG loop-body plans (ROADMAP items 2+5, landed together).

``krylov.py`` used to carry five hand-fused copies of the CG recurrence
(plain / stencil / many / guarded / guarded-many), and every new axis —
pipelined, batched, guarded, grid-shaped — multiplied the matrix again.
This module factors the recurrence into orthogonal *plans* assembled into
ONE ``lax.while_loop`` body per recurrence family:

* **operator-apply plan** — the (possibly fused-dot) operator closure:
  ``A(v)`` for general operators, ``Adot(v) -> (Av, psum<v,Av>)`` for the
  VMEM-resident stencil fast path;
* **PC plan** — how the preconditioned direction is produced: a
  materialized ``z = M r``, the scalar uniform-diagonal identity
  (``z = r/diag`` never materialized), or the 3D-native V-cycle ``M3``;
* **reduction plan** — how the iteration's inner products map onto psum
  SITES: classic 3-site (2 under the natural norm), the fused 2-site
  stacked pair, the guarded 2-site phases with the ABFT partials folded
  in, the PIPELINED 1-site plan (:func:`pipelined_cg_loop`) whose one
  stacked psum is overlapped against the next SpMV/PC apply, or the
  S-STEP communication-avoiding plan (:func:`sstep_cg_loop`) whose one
  stacked Gram psum serves s whole iterations;
* **guard plan** — ``None``, or the silent-corruption bookkeeping
  (NaN/monotonicity sentinels, periodic true-residual replacement with
  the drift gate, ``det``/``rrc``/verified-iterate outputs);
* **batching plan** — :class:`SingleBatch` / :class:`ManyBatch`: scalar
  broadcasting, per-column mask selects, and loop-condition aggregation.

The assembled bodies reproduce the retired kernels' arithmetic exactly
(masked selects with an always-true mask are the identity), so iteration
counts, reasons, and the collective-volume gates are unchanged — and
pipelined CG (Ghysels & Vanroose; PETSc's KSPPIPECG slot) lands as a new
reduction plan rather than a sixth kernel family.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax


# ---------------------------------------------------------------------------
# precision plans: the storage-vs-reduce dtype axis (PR 10)
# ---------------------------------------------------------------------------


class PrecisionPlan:
    """The precision axis of a compute plan: ``storage`` is the
    operator/PC/iterate channel's dtype (what the all-gathers, halo
    ppermutes, and AXPY traffic move — halving it halves the bytes per
    iterate), ``reduce`` the dot-product/norm/ABFT accumulation channel's
    dtype (kept wider, the pipelined-Krylov reduction-channel discipline).

    With ``storage == reduce`` (fp32/fp64/complex operators) every hook
    is the identity and the assembled loop bodies are the pre-plan ones
    bit for bit — the collective-volume and reduce-site gates see
    identical programs. The MIXED case (bf16 storage, fp32 reduce) casts
    each vector update back to storage (``store``) and lifts reduction
    operands up (``up``); scalars (alpha/beta/rz/norms) live in the
    reduce dtype throughout the carry.
    """

    def __init__(self, storage, reduce=None):
        from ..utils import dtypes as _dtypes
        self.storage = np.dtype(storage)
        self.reduce = np.dtype(reduce if reduce is not None
                               else _dtypes.reduce_dtype(self.storage))
        self.mixed = self.reduce != self.storage

    def store(self, v):
        """Cast a vector update back to the storage channel (identity
        for uniform-precision plans — no-op in the lowered HLO)."""
        return v.astype(self.storage) if self.mixed else v

    def up(self, v):
        """Lift a reduction operand into the accumulation channel."""
        return v.astype(self.reduce) if self.mixed else v

    def key(self):
        """The (storage, reduce) fingerprint compiled-program caches and
        serving compatibility keys carry."""
        return (str(self.storage), str(self.reduce))

    def __repr__(self):
        return f"PrecisionPlan(storage={self.storage}, reduce={self.reduce})"


def precision_plan(storage, reduce=None) -> PrecisionPlan:
    """Build the precision plan for an operator's storage dtype (the
    reduce dtype defaults to utils.dtypes.reduce_dtype: fp32 for
    sub-32-bit storage, the storage dtype itself otherwise)."""
    return PrecisionPlan(storage, reduce)


def _stc(prec):
    """The store-channel cast of a plan (identity without one)."""
    if prec is not None and prec.mixed:
        return prec.store
    return lambda v: v

# ---------------------------------------------------------------------------
# shared numeric helpers (moved here from krylov.py so both modules — and
# every plan — read ONE definition; krylov re-exports them unchanged)
# ---------------------------------------------------------------------------


def _dmax(rnorm0, dtol):
    """Divergence ceiling: ``dtol * rnorm0`` — the INITIAL residual norm, as
    in PETSc's KSPConvergedDefault DIVERGED_DTOL test (a merely-large initial
    guess must not trigger instant divergence). ``dtol`` None/<=0 disables."""
    if dtol is None:
        return jnp.inf
    return jnp.where(dtol > 0, dtol * rnorm0, jnp.inf)


def _tol(pnorm, b, rtol, atol):
    bnorm = pnorm(b)
    return bnorm, jnp.maximum(rtol * bnorm, atol)


def _nat(rz):
    """KSP_NORM_NATURAL: sqrt <r, M r> — the scalar the CG-family
    recurrences already carry (real by construction for the SPD/Hermitian
    operators these types require)."""
    return jnp.sqrt(jnp.maximum(jnp.real(rz), 0.0))


def _reason(rnorm, tol, atol, k, maxit, brk, dmax=None):
    from ..utils.convergence import ConvergedReason as CR
    diverged = (CR.DIVERGED_MAX_IT if dmax is None else
                jnp.where(rnorm >= dmax, CR.DIVERGED_DTOL,
                          CR.DIVERGED_MAX_IT))
    return jnp.where(
        brk, CR.DIVERGED_BREAKDOWN,
        jnp.where(rnorm <= tol,
                  jnp.where(rnorm <= atol, CR.CONVERGED_ATOL,
                            CR.CONVERGED_RTOL),
                  diverged)).astype(jnp.int32)


def _no_hist(dtype):
    """Zero-size placeholder carried when monitoring is off — compiled
    away entirely, but keeps every kernel's carry structure uniform."""
    return jnp.zeros((0,), jnp.real(jnp.zeros((), dtype)).dtype)


def _hist0(monitor, dtype):
    """The history carry every kernel threads through its loop: the real
    recorder when monitoring, a zero-size placeholder otherwise."""
    return monitor.init() if monitor is not None else _no_hist(dtype)


def _mon0(monitor, rn0, dtype):
    """Build the history carry and record the iteration-0 (initial)
    residual norm. petsc4py's monitors and KSPSetResidualHistory include
    it — history length is iterations+1, and drivers index history[0] for
    the starting norm."""
    hist = _hist0(monitor, dtype)
    if monitor is not None:
        return monitor(hist, jnp.int32(0), rn0)
    return hist


# ---------------------------------------------------------------------------
# silent-data-corruption detector codes + thresholds (single source; the
# guarded plans and solvers/ksp.py both read these via krylov's re-export)
# ---------------------------------------------------------------------------

(SDC_NONE, SDC_ABFT, SDC_ABFT_PC, SDC_DRIFT, SDC_NAN, SDC_MONO,
 SDC_DEMOTE) = range(7)
SDC_DETECTOR_NAMES = {SDC_ABFT: "abft", SDC_ABFT_PC: "abft_pc",
                      SDC_DRIFT: "drift", SDC_NAN: "nan",
                      SDC_MONO: "monotonic",
                      # NOT a corruption code: the s-step plan's drift gate
                      # exhausted its basis-restart budget
                      # (-ksp_sstep_max_replacements) — the host demotes
                      # the solve to classic CG from the current iterate
                      # instead of rolling back (solvers/ksp.py)
                      SDC_DEMOTE: "sstep_demote"}

# monotonicity sentinel: a residual norm this far above the best seen so
# far is beyond any healthy CG transient (bounded by sqrt(cond(A)))
_SDC_MONO_FACTOR = 1e4
# drift gate: recurrence-vs-true relative mismatch beyond this fraction
# (plus a rounding floor of _SDC_DRIFT_FLOOR_EPS * eps * ||b||) flags SDC
_SDC_DRIFT_REL = 0.25
_SDC_DRIFT_FLOOR_EPS = 1024.0

# s-step coordinate-resolution floor: the in-block residual² is computed
# as a DIFFERENCE of O(‖r_block_start‖²) Gram quadratics, so its absolute
# noise is ~eps·‖r₀‖²·O(m) — below _SSTEP_RR_FLOOR·m·eps·rr0 the value is
# rounding, the block freezes, and the next block restarts from the
# full-precision materialized residual (whose ‖·‖² the Gram psums
# DIRECTLY, restoring resolution). Caps the per-block reduction at
# ~16·sqrt(m·eps)× — deeper convergence just takes another block.
_SSTEP_RR_FLOOR = 256.0

# s-step stagnation gate: CA-CG basis ill-conditioning does NOT show up
# as r-vs-true drift (x and r are combined from the SAME coordinate
# vector, so they stay consistent by construction) — it shows up as the
# TRUE residual stalling while the coordinate recurrences spin. A
# replacement check that finds less than this reduction factor since the
# LAST check declares the basis ineffective at this s.
_SSTEP_STALL_FACTOR = 0.9


def _det4(badA, badM, badnan, badmono):
    """First-detector-wins detection code (elementwise for batched)."""
    return jnp.where(
        badA, SDC_ABFT,
        jnp.where(badM, SDC_ABFT_PC,
                  jnp.where(badnan, SDC_NAN,
                            jnp.where(badmono, SDC_MONO,
                                      SDC_NONE)))).astype(jnp.int32)


# ---------------------------------------------------------------------------
# batching plans
# ---------------------------------------------------------------------------


class SingleBatch:
    """One RHS: scalars are scalars, the continuation mask broadcasts
    trivially, and the loop condition is the mask itself."""

    many = False

    def ex(self, s):
        return s

    def agg(self, m):
        return m


class ManyBatch:
    """``nrhs`` lockstep recurrences: per-column ``(nrhs,)`` scalars, a
    column mask broadcast against the vector-block layout, and the loop
    running until the LAST active column exits.

    ``layout='cols'`` is the flat ``(lsize, nrhs)`` block (mask/scalars
    expand as ``s[None, :]``); ``layout='slabs'`` the grid-shaped
    ``(nrhs, lz, ny, nx)`` stencil block (``s[:, None, None, None]``).
    """

    many = True

    def __init__(self, layout: str = "cols"):
        if layout not in ("cols", "slabs"):
            raise ValueError(f"unknown ManyBatch layout {layout!r}")
        self._cols = layout == "cols"

    def ex(self, s):
        return s[None, :] if self._cols else s[:, None, None, None]

    def agg(self, m):
        return jnp.any(m)


def _false_like(rn):
    return jnp.zeros(jnp.shape(rn), bool)


def _it0(rn):
    return jnp.zeros(jnp.shape(rn), jnp.int32)


# ---------------------------------------------------------------------------
# the pipelined plan's single reduce site (test-injection seam)
# ---------------------------------------------------------------------------


def fuse_psum(parts, psum, axis, dtype):
    """ONE stacked collective for ALL of a pipelined iteration's scalar
    reductions — the 1-reduce-site contract of the pipelined plan.

    Kept as a module-level seam on purpose: the collective-volume gate's
    injected-regression test monkeypatches this into a two-psum split to
    prove the one-site assert has teeth. ``parts`` may be per-column
    ``(nrhs,)`` rows; everything is cast to the operator scalar so the
    stack is homogeneous (the callers re-take real parts of norms)."""
    return psum(jnp.stack([jnp.asarray(q, dtype) for q in parts]), axis)


def fuse_gram_psum(parts, psum, axis, dtype, batched=False):
    """ONE stacked collective for an s-step block's whole reduction
    payload — the tall-skinny Gram matrix plus every guard partial.

    ``parts`` is a list of arrays with mixed leading shapes (the
    ``(q, q[, nrhs])`` Gram block, ``(m[, nrhs])`` checksum rows,
    scalars); each is flattened over its leading (non-batch) dims,
    concatenated into one stack, reduced in a SINGLE psum, and split
    back to the input shapes. This is the s-step plan's 1-reduce-site
    contract (one collective per s iterations) and, like
    :func:`fuse_psum`, a deliberate module-level seam: the
    collective-volume gate's injected-regression test monkeypatches it
    into a two-psum split to prove the one-site assert has teeth.

    ``batched=True`` declares a trailing ``(nrhs,)`` batch axis on every
    part (the ManyBatch layout), preserved through the flatten.
    """
    parts = [jnp.asarray(p, dtype) for p in parts]
    tail_n = 1 if batched else 0
    tail = parts[0].shape[parts[0].ndim - tail_n:]
    flat = []
    lead_shapes = []
    for p in parts:
        lead = p.shape[: p.ndim - tail_n]
        lead_shapes.append(lead)
        flat.append(p.reshape((-1,) + tail))
    stacked = psum(jnp.concatenate(flat, axis=0), axis)
    out = []
    at = 0
    for p, lead in zip(flat, lead_shapes):
        rows = p.shape[0]
        out.append(stacked[at:at + rows].reshape(lead + tail))
        at += rows
    return out


# ---------------------------------------------------------------------------
# classic CG: one while_loop body serving plain/stencil/many/guarded
# ---------------------------------------------------------------------------


def classic_cg_loop(*, b, x0, rtol, atol, maxit, dtol=None,
                    A=None, M=None, Adot=None, inv_diag=None, M3=None,
                    pdot=None, pnorm=None, pduo=None, guard=None,
                    bp=None, monitor=None, unroll=1, natural=False,
                    prec=None):
    """Assemble and run the classic (two-phase) CG recurrence.

    Plan axes (module docstring): the operator plan is ``A`` or the fused
    ``Adot``; the PC plan is ``M`` (materialized z), ``inv_diag`` (scalar
    uniform-diagonal identity) or ``M3`` (3D-native V-cycle); the
    reduction plan is implied by what is supplied — plain ``pdot``/
    ``pnorm`` (3 sites; 2 under ``natural``), the stacked ``pduo`` pair
    (2 sites), or a ``guard`` namespace whose ``p1``/``p2``/
    ``p2_stencil`` phases carry the folded ABFT partials (2 sites);
    ``bp`` is the batching plan. Per-column masked freezing, unrolled
    multi-step dispatch, and the guard's replacement/rollback bookkeeping
    are all specializations of this one body.

    Returns the retired kernels' exact output tuples:
    ``(x, it, rnorm, reason, hist)`` and, guarded,
    ``(..., det, rrc, xv)``.

    ``prec`` is the :class:`PrecisionPlan`: with a mixed plan the vector
    carries (x/r/p/z) stay in the storage dtype — every update that
    mixes in a reduce-dtype scalar is cast back through ``prec.store`` —
    while the reduction closures (supplied by the program builder) lift
    their operands into the reduce dtype, so alpha/beta/rz/norms travel
    wide. Uniform plans leave the body untouched.
    """
    bp = bp or SingleBatch()
    g = guard
    st_ = _stc(prec)
    stencil = Adot is not None
    carry_z = not stencil

    # ---- init: initial residual + the plan's init reductions ---------------
    if stencil:
        if g is not None:
            r = b - Adot(x0)[0]
            bnorm, rnorm, badA0 = g.init(b, r, x0)
            rz = rnorm * rnorm * inv_diag
            p = st_(r * inv_diag)
            badM0 = _false_like(rnorm)
        else:
            bnorm = pnorm(b)
            r = b - Adot(x0)[0]
            rr0 = pdot(r, r)
            rnorm = jnp.sqrt(rr0)
            if M3 is None:
                rz = rr0 * inv_diag
                p = st_(r * inv_diag)
            else:
                z0 = M3(r)
                rz = pdot(r, z0)
                p = z0
        tol = jnp.maximum(rtol * bnorm, atol)
        brk0 = _false_like(rnorm)
        z = None
    else:
        r = b - A(x0)
        if g is not None:
            bnorm, badA0 = g.init(b, r, x0)
            z = M(r)
            rz, rn2, badM0 = g.p2(r, z)
            rnorm = jnp.sqrt(jnp.maximum(jnp.real(rn2), 0.0))
            p = z
            tol = jnp.maximum(rtol * bnorm, atol)
            brk0 = _false_like(rnorm)
        else:
            z = M(r)
            p = z
            rz = pdot(r, z)
            if natural:
                rnorm = _nat(rz)
                tol = jnp.maximum(rtol * rnorm, atol)
                # a negative <r, M r> means M (or A) is indefinite — the
                # natural norm is undefined there; flag breakdown instead
                # of letting the 0-clamped norm fake instant convergence
                brk0 = jnp.real(rz) < 0
            else:
                bnorm, tol = _tol(pnorm, b, rtol, atol)
                rnorm = pnorm(r)
                brk0 = _false_like(rnorm)
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)

    st0 = dict(it=_it0(rnorm), x=x0, r=r, p=p, rz=rz, rn=rnorm, brk=brk0,
               hist=hist)
    if carry_z:
        st0["z"] = z
    if g is not None:
        drift_floor = _SDC_DRIFT_FLOOR_EPS * g.eps * bnorm
        st0.update(det=_det4(badA0, badM0, ~jnp.isfinite(rnorm),
                             _false_like(rnorm)),
                   rrc=_it0(rnorm), xv=x0, rnb=rnorm)
        if bp.many:
            # the lockstep STEP counter the replacement interval runs on
            # (per-column iteration counts diverge once columns freeze)
            st0["ks"] = jnp.int32(0)

    def active(st):
        live = ((st["rn"] > tol) & (st["rn"] < dmax) & (st["it"] < maxit)
                & ~st["brk"])
        if g is not None:
            live = live & (st["det"] == SDC_NONE)
        return live

    def cond(st):
        return bp.agg(active(st))

    def step(st):
        cont = active(st)
        cm = bp.ex(cont)
        it, x, r, p, rz = st["it"], st["x"], st["r"], st["p"], st["rz"]

        # ---- operator apply + reduction phase 1 ----
        # (each phase in a jax.named_scope: cg.apply, cg.dot, cg.update,
        # cg.pc, which the HLO's op_name metadata carries)
        if stencil:
            with jax.named_scope("cg.apply"):
                Ap, pAp = Adot(p)              # fused matvec+dot (1 psum)
            badA = None
        else:
            with jax.named_scope("cg.apply"):
                Ap = A(p)
            with jax.named_scope("cg.dot"):
                if g is not None:
                    pAp, badA = g.p1(p, Ap)    # stacked phase 1 + A-ABFT
                else:
                    pAp = pdot(p, Ap)          # reduction phase 1
                    badA = None
        with jax.named_scope("cg.update"):
            brk_new = cont & (pAp == 0)
            alpha = jnp.where(pAp == 0, 0.0,
                              rz / jnp.where(pAp == 0, 1.0, pAp))
            # frozen steps/columns SELECT the old state rather than
            # multiplying by a zero gate: once a diverging active step
            # has produced inf/NaN, 0 * inf = NaN would destroy the
            # preserved iterate
            al = bp.ex(alpha)
            x = jnp.where(cm, st_(x + al * p), x)
            r = jnp.where(cm, st_(r - al * Ap), r)

        # ---- PC apply + reduction phase 2 ----
        z = None
        badM = None
        if stencil:
            if g is not None:
                with jax.named_scope("cg.dot"):
                    rr, badA = g.p2_stencil(r, p, Ap)  # phase 2 + ABFT
                rz_new = rr * inv_diag
                zdir = st_(r * inv_diag)
                rn_new = jnp.sqrt(rr)
            elif M3 is not None:
                with jax.named_scope("cg.dot"):
                    rr = pdot(r, r)
                with jax.named_scope("cg.pc"):
                    zn = M3(r)
                with jax.named_scope("cg.dot"):
                    rz_new = pdot(r, zn)
                zdir = zn
                rn_new = jnp.sqrt(rr)
            else:
                with jax.named_scope("cg.dot"):
                    rr = pdot(r, r)
                rz_new = rr * inv_diag
                zdir = st_(r * inv_diag)
                rn_new = jnp.sqrt(rr)
        else:
            with jax.named_scope("cg.pc"):
                z = jnp.where(cm, M(r), st["z"])
            zdir = z
            with jax.named_scope("cg.dot"):
                if g is not None:
                    rz_new, rn2, badM = g.p2(r, z)  # stacked phase 2
                    rn_new = jnp.sqrt(jnp.maximum(jnp.real(rn2), 0.0))
                elif pduo is not None:
                    rz_new, rr = pduo(r, z)         # fused (rz, rr) pair
                    rn_new = jnp.sqrt(jnp.maximum(jnp.real(rr), 0.0))
                else:
                    rz_new = pdot(r, z)             # reduction phase 2
                    rn_new = None                   # phase 3 / natural
        if natural and g is None and not stencil:
            brk_new = brk_new | (cont & (jnp.real(rz_new) < 0))
        with jax.named_scope("cg.update"):
            beta = jnp.where(rz == 0, 0.0,
                             rz_new / jnp.where(rz == 0, 1.0, rz))
            p = jnp.where(cm, st_(zdir + bp.ex(beta) * p), p)
            rz = jnp.where(cont, rz_new, rz)
        if rn_new is None:
            with jax.named_scope("cg.dot"):
                rn_new = _nat(rz_new) if natural else pnorm(r)
        rn = jnp.where(cont, rn_new, st["rn"])
        it = it + cont.astype(jnp.int32)

        st2 = dict(it=it, x=x, r=r, p=p, rz=rz, rn=rn,
                   brk=st["brk"] | brk_new, hist=st["hist"])
        if carry_z:
            st2["z"] = z

        # ---- guard plan: sentinels + periodic replacement ----
        if g is not None:
            if bp.many:
                badnan = cont & ~jnp.isfinite(rn)
                badmono = cont & jnp.isfinite(rn) & (rn > _SDC_MONO_FACTOR
                                                     * st["rnb"])
                rnb = jnp.where(cont & jnp.isfinite(rn),
                                jnp.minimum(st["rnb"], rn), st["rnb"])
                # STICKY per-column detection: a frozen column's code must
                # survive later passes (cont masks its checks once frozen)
                badA_m = cont & badA if badA is not None else badnan & False
                badM_m = cont & badM if badM is not None else badnan & False
                det = jnp.where(st["det"] == SDC_NONE,
                                _det4(badA_m, badM_m, badnan, badmono),
                                st["det"])
                ks = st["ks"] + 1
                clean = det == SDC_NONE
                do_rr = (jnp.any(cont & clean) & (g.rr_n > 0)
                         & (ks % jnp.maximum(g.rr_n, 1) == 0))
                st2["ks"] = ks
            else:
                badnan = ~jnp.isfinite(rn)
                badmono = jnp.isfinite(rn) & (rn > _SDC_MONO_FACTOR
                                              * st["rnb"])
                rnb = jnp.where(jnp.isfinite(rn),
                                jnp.minimum(st["rnb"], rn), st["rnb"])
                fA = badA if badA is not None else badnan & False
                fM = badM if badM is not None else badnan & False
                det = _det4(fA, fM, badnan, badmono)
                clean = det == SDC_NONE
                do_rr = ((det == SDC_NONE) & (g.rr_n > 0)
                         & (it % jnp.maximum(g.rr_n, 1) == 0) & (rn > tol))
            st2["rnb"] = rnb

            def replace(args):
                x, r, z, p, rz, rn, rrc, xv = args
                if stencil:
                    rt = b - Adot(x)[0]
                    rtn2 = g.vnorm2(rt)            # plain-psum verifier
                    rtn = jnp.sqrt(jnp.maximum(rtn2, 0.0))
                else:
                    rt = b - A(x)
                    zt = M(rt)
                    rtn2, rzt = g.vpair(rt, zt)    # plain-psum verifier
                    rtn = jnp.sqrt(jnp.maximum(rtn2, 0.0))
                drift = (jnp.abs(rtn - rn) > _SDC_DRIFT_REL * (rtn + rn)
                         + drift_floor)
                ok = (cont & clean & ~drift) if bp.many else ~drift
                okm = bp.ex(ok)
                # replacement restarts the direction from the true
                # residual, bounding recurrence drift; the passing iterate
                # is promoted to the rollback target xv
                r = jnp.where(okm, rt, r)
                if stencil:
                    p = jnp.where(okm, st_(rt * inv_diag), p)
                    rz = jnp.where(ok, rtn2 * inv_diag, rz)
                else:
                    z = jnp.where(okm, zt, z)
                    p = jnp.where(okm, zt, p)
                    rz = jnp.where(ok, rzt, rz)
                rn = jnp.where(ok, rtn, rn)
                xv = jnp.where(okm, x, xv)
                rrc = rrc + ok.astype(jnp.int32)
                bad = (cont & clean & drift) if bp.many else drift
                det_rr = jnp.where(bad, SDC_DRIFT,
                                   SDC_NONE).astype(jnp.int32)
                return (x, r, z, p, rz, rn, rrc, xv, det_rr)

            def keep(args):
                x, r, z, p, rz, rn, rrc, xv = args
                return (x, r, z, p, rz, rn, rrc, xv,
                        jnp.zeros(jnp.shape(rn), jnp.int32))

            zc = z if carry_z else jnp.zeros((0,), b.dtype)
            x, r, zc, p, rz, rn, rrc, xv, det_rr = lax.cond(
                do_rr, replace, keep,
                (x, r, zc, p, rz, rn, st["rrc"], st["xv"]))
            det = jnp.where(det == SDC_NONE, det_rr, det)
            st2.update(x=x, r=r, p=p, rz=rz, rn=rn, det=det, rrc=rrc,
                       xv=xv)
            if carry_z:
                st2["z"] = zc
        if monitor is not None:
            st2["hist"] = monitor(st2["hist"], it, st2["rn"])
        return st2

    def body(st):
        for _ in range(max(1, int(unroll))):
            st = step(st)
        return st

    st = lax.while_loop(cond, body, st0)
    out = (st["x"], st["it"], st["rn"],
           _reason(st["rn"], tol, atol, st["it"], maxit, st["brk"], dmax),
           st["hist"])
    if g is not None:
        out = out + (st["det"], st["rrc"], st["xv"])
    return out


# ---------------------------------------------------------------------------
# pipelined CG: the 1-reduce-site reduction plan (Ghysels & Vanroose)
# ---------------------------------------------------------------------------


def pipelined_cg_loop(*, b, x0, rtol, atol, maxit, dtol=None,
                      A=None, M=None, pnorm=None, fused=None,
                      guard=None, bp=None, monitor=None, prec=None):
    """Assemble and run the pipelined (single-reduction) CG recurrence.

    Ghysels–Vanroose pipelined CG ("Pipelined, Flexible Krylov Subspace
    Methods", PAPERS.md): every inner product of the iteration —
    ``gamma = <r, u>``, ``delta = <w, u>``, and the monitored
    ``||r||^2`` — is computed from the CURRENT vectors and issued as ONE
    stacked psum (``fused``; the :func:`fuse_psum` seam), while the next
    iteration's operator/PC applies ``m = M w``, ``n = A m`` are
    independent of the reduction results — XLA's async collectives
    overlap the reduce with the SpMV, the latency-hiding the two-stage
    multisplitting line of work gets from restructured communication.
    The extra recurrences (``s = A p``, ``q = M s``, ``z = A M s``) trade
    three more AXPYs for two fewer reduce sites and the overlap.

    The monitored norm lags one iteration (``rr`` is reduced before the
    update it gates), so convergence is detected one body later than
    classic CG — iterates match CG to rounding, iteration counts run one
    higher. The known residual-drift of the u/w recurrences is exactly
    what the guard plan's periodic replacement bounds: the replacement
    recomputes ``r``/``u``/``w`` from the iterate and zeroes the
    direction recurrences (``gamma = 0`` restarts the beta chain).

    ``fused(r, u, w)`` returns ``(gamma, delta, rr)``; guarded,
    ``fused(r, u, w, chk)`` additionally reduces the PREVIOUS body's
    locally-summed ABFT partials (``guard.chk_parts`` — checksum checks
    of that body's fresh ``m = M w``/``n = A m`` applies, carried one
    iteration) in the SAME single psum and returns
    ``(gamma, delta, rr, badA, badM)``.
    """
    bp = bp or SingleBatch()
    g = guard
    st_ = _stc(prec)
    # the scalar recurrences (gamma/alpha) and sgn live in the REDUCE
    # dtype under a mixed plan — fused() returns wide scalars there
    sdt = prec.reduce if (prec is not None and prec.mixed) else b.dtype

    r = b - A(x0)
    if g is not None:
        bnorm, badA0 = g.init(b, r, x0)
    else:
        bnorm = pnorm(b)
    tol = jnp.maximum(rtol * bnorm, atol)
    u = M(r)
    w = A(u)
    rn0 = pnorm(r)
    dmax = _dmax(rn0, dtol)
    hist = _mon0(monitor, rn0, b.dtype)
    sc0 = jnp.zeros(jnp.shape(rn0), sdt)

    # STACKED carries: the state block S = [w, u, r, x] and the direction
    # block V = [z, q, s, p] each update in ONE fused AXPY kernel
    # (S += alpha * sgn * V; V = C + beta * V) instead of eight separate
    # recurrences — on dispatch-bound meshes the kernel count, not the
    # bytes, is the per-iteration floor (measured ~15%/iter on the
    # 8-virtual-device CPU mesh). ``sgn`` encodes the update directions
    # (w/u/r subtract, x adds).
    sgn = jnp.asarray([-1.0, -1.0, -1.0, 1.0],
                      jnp.real(jnp.zeros((), sdt)).dtype
                      ).reshape((4,) + (1,) * b.ndim)
    S0 = jnp.stack([w, u, r, x0])
    st0 = dict(it=_it0(rn0), S=S0, V=jnp.zeros_like(S0),
               gamma=sc0, alpha=sc0, rn=rn0, brk=_false_like(rn0),
               hist=hist)
    if g is not None:
        drift_floor = _SDC_DRIFT_FLOOR_EPS * g.eps * bnorm
        st0.update(det=_det4(badA0, _false_like(rn0), ~jnp.isfinite(rn0),
                             _false_like(rn0)),
                   rrc=_it0(rn0), xv=x0, rnb=rn0,
                   # the init applies' checksum partials, checked by the
                   # FIRST body's stacked psum (one-iteration lag)
                   chk=g.chk_init(r, u, w))
        if bp.many:
            st0["ks"] = jnp.int32(0)

    def active(st):
        live = ((st["rn"] > tol) & (st["rn"] < dmax) & (st["it"] < maxit)
                & ~st["brk"])
        if g is not None:
            live = live & (st["det"] == SDC_NONE)
        return live

    def cond(st):
        return bp.agg(active(st))

    def body(st):
        cont = active(st)
        cm = bp.ex(cont)
        S = st["S"]
        w, u, r = S[0], S[1], S[2]
        if g is not None:                      # the ONE reduce site
            gamma, delta, rr, badA, badM = fused(r, u, w, st["chk"])
        else:
            gamma, delta, rr = fused(r, u, w)
            badA = badM = None
        # overlap work: both applies are independent of the reduction's
        # results, so the collective hides behind them
        m = M(w)
        n = A(m)
        if g is not None:
            # this body's fresh-apply checksum partials, reduced by the
            # NEXT body's stacked psum (w here is the pre-update M input)
            chk_new = g.chk_parts(m, n, w)
        # gamma==0 marks both the first iteration and a post-replacement
        # restart (the guard zeroes the carry): the beta chain starts fresh
        first = st["gamma"] == 0
        gold = jnp.where(first, 1.0, st["gamma"])
        beta = jnp.where(first, 0.0, gamma / gold)
        aold = jnp.where(st["alpha"] == 0, 1.0, st["alpha"])
        denom = jnp.where(first, delta, delta - beta * gamma / aold)
        brk_new = cont & (denom == 0)
        alpha = jnp.where(denom == 0, 0.0,
                          gamma / jnp.where(denom == 0, 1.0, denom))
        be, al = bp.ex(beta), bp.ex(alpha)
        # V = [z, q, s, p] <- [n, m, w, u] + beta V ; then the state rows
        # [w, u, r, x] -= / += alpha * V rows — two fused kernels total
        V = jnp.where(cm, st_(jnp.stack([n, m, w, u]) + be * st["V"]),
                      st["V"])
        S = jnp.where(cm, st_(S + al * (sgn * V)), S)
        # rr = <r, r> is real by construction; take the real part so the
        # carried norm stays real-typed for complex operators
        rn_new = jnp.sqrt(jnp.maximum(jnp.real(rr), 0.0))
        rn = jnp.where(cont, rn_new, st["rn"])
        gamma_c = jnp.where(cont, gamma, st["gamma"])
        alpha_c = jnp.where(cont, alpha, st["alpha"])
        it = st["it"] + cont.astype(jnp.int32)

        st2 = dict(it=it, S=S, V=V, gamma=gamma_c, alpha=alpha_c, rn=rn,
                   brk=st["brk"] | brk_new, hist=st["hist"])

        if g is not None:
            if bp.many:
                badnan = cont & ~jnp.isfinite(rn)
                badmono = cont & jnp.isfinite(rn) & (rn > _SDC_MONO_FACTOR
                                                     * st["rnb"])
                rnb = jnp.where(cont & jnp.isfinite(rn),
                                jnp.minimum(st["rnb"], rn), st["rnb"])
                det = jnp.where(st["det"] == SDC_NONE,
                                _det4(cont & badA, cont & badM, badnan,
                                      badmono),
                                st["det"])
                ks = st["ks"] + 1
                clean = det == SDC_NONE
                do_rr = (jnp.any(cont & clean) & (g.rr_n > 0)
                         & (ks % jnp.maximum(g.rr_n, 1) == 0))
                st2["ks"] = ks
            else:
                badnan = ~jnp.isfinite(rn)
                badmono = jnp.isfinite(rn) & (rn > _SDC_MONO_FACTOR
                                              * st["rnb"])
                rnb = jnp.where(jnp.isfinite(rn),
                                jnp.minimum(st["rnb"], rn), st["rnb"])
                det = _det4(badA, badM, badnan, badmono)
                clean = det == SDC_NONE
                do_rr = ((det == SDC_NONE) & (g.rr_n > 0)
                         & (it % jnp.maximum(g.rr_n, 1) == 0) & (rn > tol))
            st2["rnb"] = rnb

            def replace(args):
                S, V, gamma_c, alpha_c, rn, rrc, xv = args
                x = S[3]
                # full pipeline refill from the TRUE residual: the u/w
                # recurrences (the pipelined drift source) are recomputed
                # from scratch, the direction recurrences restart
                rt = b - A(x)
                ut = M(rt)
                wt = A(ut)
                # plain-psum verifier; the drift gate compares against the
                # CURRENT recurrence residual (the carried norm lags one
                # iteration — see _make_pipe_guard.vpair2)
                rtn2, rc2 = g.vpair2(rt, S[2])
                rtn = jnp.sqrt(jnp.maximum(rtn2, 0.0))
                rcur = jnp.sqrt(jnp.maximum(rc2, 0.0))
                drift = (jnp.abs(rtn - rcur)
                         > _SDC_DRIFT_REL * (rtn + rcur) + drift_floor)
                ok = (cont & clean & ~drift) if bp.many else ~drift
                okm = bp.ex(ok)
                S = jnp.where(okm, jnp.stack([wt, ut, rt, x]), S)
                V = jnp.where(okm, 0.0, V)
                gamma_c = jnp.where(ok, 0.0, gamma_c)  # fresh beta chain
                alpha_c = jnp.where(ok, 0.0, alpha_c)
                rn = jnp.where(ok, rtn, rn)
                xv = jnp.where(okm, x, xv)
                rrc = rrc + ok.astype(jnp.int32)
                bad = (cont & clean & drift) if bp.many else drift
                det_rr = jnp.where(bad, SDC_DRIFT,
                                   SDC_NONE).astype(jnp.int32)
                return (S, V, gamma_c, alpha_c, rn, rrc, xv, det_rr)

            def keep(args):
                return args + (jnp.zeros(jnp.shape(args[4]), jnp.int32),)

            (S, V, gamma_c, alpha_c, rn, rrc, xv, det_rr) = lax.cond(
                do_rr, replace, keep,
                (S, V, gamma_c, alpha_c, rn, st["rrc"], st["xv"]))
            det = jnp.where(det == SDC_NONE, det_rr, det)
            st2.update(S=S, V=V, gamma=gamma_c, alpha=alpha_c, rn=rn,
                       det=det, rrc=rrc, xv=xv, chk=chk_new)
        if monitor is not None:
            st2["hist"] = monitor(st2["hist"], it, st2["rn"])
        return st2

    st = lax.while_loop(cond, body, st0)
    xf = st["S"][3]
    # the monitored norm lags one iteration; report the exact final
    # residual (plain psum — the verifier channel, outside the loop) while
    # judging the reason on the norm the loop actually tested
    if g is not None:
        rn_true = jnp.sqrt(jnp.maximum(g.vnorm2(b - A(xf)), 0.0))
    else:
        rn_true = pnorm(b - A(xf))
    out = (xf, st["it"], rn_true,
           _reason(st["rn"], tol, atol, st["it"], maxit, st["brk"], dmax),
           st["hist"])
    if g is not None:
        out = out + (st["det"], st["rrc"], st["xv"])
    return out


# ---------------------------------------------------------------------------
# s-step communication-avoiding CG: ONE reduce site per s iterations
# ---------------------------------------------------------------------------


def _sstep_shift(s: int, m: int) -> np.ndarray:
    """The coordinate shift of ``(MA)`` over the two monomial sub-bases:
    column ``i`` of the p-chain maps to ``i+1`` (i < s), column ``i`` of
    the z-chain likewise (i < s-1); the last column of each chain has no
    image in the basis and by the degree bookkeeping of
    :func:`sstep_cg_loop` never carries a coefficient when shifted."""
    S = np.zeros((m, m))
    for i in range(s):
        S[i + 1, i] = 1.0
    for i in range(s - 1):
        S[s + 2 + i, s + 1 + i] = 1.0
    return S


def sstep_cg_loop(*, b, x0, rtol, atol, maxit, s, greduce,
                  A=None, M=None, pnorm=None, dtol=None,
                  guard=None, bp=None, monitor=None, prec=None,
                  max_repl=None):
    """Assemble and run the s-step (communication-avoiding) CG recurrence.

    Each ``lax.while_loop`` body advances CG by **s iterations** around a
    SINGLE stacked psum — the tall-skinny Gram matrix of the block's
    monomial Krylov bases (the CA-CG of Chronopoulos–Gear / Carson; the
    amortization the "two-stage multisplitting" scale-out tier wants on
    interconnects where even one reduction per iteration dominates):

    * **basis build** — from the carried ``(p, r)``, the two preconditioned
      monomial chains ``P̃ = [p, (MA)p, …, (MA)^s p]`` (s+1 columns) and
      ``R̃ = [z, (MA)z, …, (MA)^{s-1} z]`` with ``z = M r`` (s columns):
      ``2s-1`` operator applies + ``2s`` PC applies of LOCAL work and
      halo/gather traffic, ZERO reductions. The A-images ``W = A·[P̃, R̃]``
      are the chain intermediates — no extra applies.
    * **the ONE reduce site** — the Gram matrix of ``C = [V_Z, W, r]``
      (``V_Z = [P̃, R̃]``, m = 2s+1 columns): one ``(2m+1)²`` stacked psum
      (:func:`fuse_gram_psum`, the MXU-friendly tall-skinny matmul)
      carrying every inner product the s iterations need — ``⟨p,Ap⟩``,
      ``⟨r,z⟩``, ``‖r‖²`` — plus, guarded, the ABFT checksum partials of
      every basis-build apply in the SAME stack.
    * **coefficient recurrences** — the s CG iterations advance as
      HOST-FREE small-vector recurrences in basis coordinates
      (``p̂``, ``ẑ``, and the shared update vector ``ĉ`` with
      ``x_j = x_0 + V_Z ĉ_j``, ``r_j = r_0 - W ĉ_j``), statically
      unrolled inside the same body; per-step masked freezing gives exact
      classic-CG iteration counts and per-column convergence under the
      batching plan.
    * **block end** — three basis combinations materialize
      ``(x, r, p)`` for the next block (or exit).

    The known CA-CG instability — the monomial basis' conditioning grows
    like ``κ^{s/2}``, so coordinate inner products lose accuracy at large
    ``s`` — is handled by the guard plan's residual-replacement gate: on
    drift the TRUE residual restarts the recurrence (the next block
    rebuilds the basis from it), and past ``max_repl`` restarts
    (``-ksp_sstep_max_replacements``) the loop exits with the
    ``SDC_DEMOTE`` code so the host demotes the solve to classic CG.

    ``greduce(parts)`` is the builder-supplied fused reduction (the
    :func:`fuse_gram_psum` seam routed through the injectable psum);
    ``pnorm`` serves init/epilogue only — the loop body performs NO other
    collective. Output contract matches :func:`pipelined_cg_loop`
    (``rn`` reported as the exact final residual, reason judged on the
    recurrence norm; guarded: ``(…, det, rrc, xv)``).
    """
    bp = bp or SingleBatch()
    many = bp.many
    g = guard
    st_ = _stc(prec)
    up = (prec.up if prec is not None and prec.mixed else (lambda v: v))
    s = int(s)
    if s < 1:
        raise ValueError(f"-ksp_sstep_s must be >= 1, got {s}")
    m = 2 * s + 1
    cdt = (prec.reduce if prec is not None and prec.mixed else b.dtype)
    rdt = jnp.real(jnp.zeros((), cdt)).dtype
    Sm = jnp.asarray(_sstep_shift(s, m), rdt)
    # W columns with a valid A-image (the chain intermediates): the last
    # column of each sub-basis has none and is carried as zeros
    w_valid = np.zeros((m,), bool)
    w_valid[0:s] = True
    w_valid[s + 1:2 * s] = True
    tail = (b.shape[1],) if many else ()

    # ---- init --------------------------------------------------------------
    r = b - A(x0)
    if g is not None:
        bnorm, badA0 = g.init(b, r, x0)
    else:
        bnorm = pnorm(b)
    tol = jnp.maximum(rtol * bnorm, atol)
    rn0 = pnorm(r)
    p = M(r)                       # classic CG init direction p_0 = z_0
    dmax = _dmax(rn0, dtol)
    hist = _mon0(monitor, rn0, b.dtype)

    st0 = dict(it=_it0(rn0), x=x0, r=r, p=p, rn=rn0, brk=_false_like(rn0),
               hist=hist)
    if g is not None:
        st0.update(det=_det4(badA0, _false_like(rn0), ~jnp.isfinite(rn0),
                             _false_like(rn0)),
                   rrc=_it0(rn0), xv=x0, rnb=rn0, drc=_it0(rn0),
                   rn_rr=rn0, ks=jnp.int32(0))

    def active(st):
        live = ((st["rn"] > tol) & (st["rn"] < dmax) & (st["it"] < maxit)
                & ~st["brk"])
        if g is not None:
            live = live & (st["det"] == SDC_NONE)
        return live

    def cond(st):
        return bp.agg(active(st))

    # ---- coordinate helpers (shapes (m[,k]) / (m,m[,k])) -------------------
    def cmat(Gm, v):
        return jnp.einsum("ab...,b...->a...", Gm, v)

    def cdot(u, v):
        return jnp.sum(jnp.conj(u) * v, axis=0)

    def combine(basis, coef):
        c = coef[:, None, :] if many else coef[:, None]
        return jnp.sum(basis * c, axis=0)

    def colsum(Bst):
        return jnp.sum(up(Bst), axis=1)

    def colasum(Bst):
        return jnp.sum(jnp.abs(up(Bst)), axis=1)

    def cmul_basis(c, Bst):
        cc = up(c)
        cc = cc[None, :, None] if many else cc[None, :]
        return cc * up(Bst)

    def onehot(idx):
        return jnp.zeros((m,) + tail, cdt).at[idx].set(1.0)

    def body(st):
        cont = active(st)
        cm = bp.ex(cont)
        x, r, p = st["x"], st["r"], st["p"]

        # ---- basis build: 2s-1 A applies + 2s M applies, NO reductions ----
        Pcols = [p]
        Wp = []
        for _ in range(s):
            t = A(Pcols[-1])
            Wp.append(t)
            Pcols.append(st_(M(t)))
        z = st_(M(r))
        Rcols = [z]
        Wr = []
        for _ in range(s - 1):
            u = A(Rcols[-1])
            Wr.append(u)
            Rcols.append(st_(M(u)))
        zero = jnp.zeros_like(b)
        Bz = jnp.stack(Pcols[:s + 1] + Rcols)          # V_Z (m, …)
        Bw = jnp.stack(Wp + [zero] + Wr + [zero])      # A·V_Z (valid cols)

        # ---- the ONE reduce site: Gram + folded guard partials ----
        Cup = up(jnp.concatenate([Bz, Bw, r[None]], axis=0))
        if many:
            E_local = jnp.einsum("aLk,bLk->abk", jnp.conj(Cup), Cup)
        else:
            E_local = jnp.einsum("aL,bL->ab", jnp.conj(Cup), Cup)
        parts = [E_local]
        if g is not None and g.cs is not None:
            CsB = cmul_basis(g.cs, Bz)
            parts += [colsum(Bw), colsum(CsB), colasum(Bw), colasum(CsB)]
        if g is not None and g.csM is not None:
            CmW = cmul_basis(g.csM, Bw)
            cr_ = up(g.csM)[:, None] * up(r) if many else up(g.csM) * up(r)
            parts += [colsum(Bz), colsum(CmW), colasum(Bz), colasum(CmW),
                      jnp.sum(cr_, axis=0), jnp.sum(jnp.abs(cr_), axis=0)]
        outs = greduce(parts)
        E = outs[0]
        i_out = 1
        badA = badM = None
        if g is not None:
            thr = lambda scale: g.abft_tol * g.eps * scale
            vm = jnp.asarray(w_valid[:, None] if many else w_valid)
            if g.cs is not None:
                sW, cV, aW, aCV = outs[i_out:i_out + 4]
                i_out += 4
                badA = jnp.any((jnp.abs(sW - cV)
                                > thr(jnp.real(aW) + jnp.real(aCV))) & vm,
                               axis=0)
            else:
                badA = g.no_bad(r)
            if g.csM is not None:
                sV, cW, aV, aCW, cr, acr = outs[i_out:i_out + 6]
                i_out += 6
                # expected column sums of V_Z under the PC checksum: each
                # column is an M apply of (W column | r) — map inputs to
                # outputs positionally; column 0 (the carried p) has no
                # in-block apply and checks against itself (diff 0)
                exp = jnp.concatenate(
                    [sV[0:1], cW[0:s], cr[None], cW[s + 1:2 * s]], axis=0)
                aexp = jnp.concatenate(
                    [aV[0:1], aCW[0:s], acr[None], aCW[s + 1:2 * s]],
                    axis=0)
                badM = jnp.any(jnp.abs(sV - exp)
                               > thr(jnp.real(aV) + jnp.real(aexp)),
                               axis=0)
            else:
                badM = g.no_bad(r)

        # Gram blocks: G1 = ⟨V_Z, W⟩, G2 = ⟨W, W⟩, g0 = ⟨V_Z, r⟩,
        # w0 = ⟨W, r⟩, rr0 = ‖r‖²
        G1 = E[0:m, m:2 * m]
        G2 = E[m:2 * m, m:2 * m]
        g0 = E[0:m, 2 * m]
        w0 = E[m:2 * m, 2 * m]
        rr0 = jnp.real(E[2 * m, 2 * m])
        G1H = jnp.conj(jnp.swapaxes(G1, 0, 1))

        def rz_of(zh, ch):
            return cdot(g0, zh) - cdot(ch, cmat(G1H, zh))

        # ---- s CG iterations as host-free coordinate recurrences ----
        phat = onehot(0)
        zhat = onehot(s + 1)
        chat = jnp.zeros((m,) + tail, cdt)
        rz = rz_of(zhat, chat)
        it, brk, hist = st["it"], st["brk"], st["hist"]
        # block-start norm REFRESH: rr0 is psummed directly (not a
        # difference), so this heals any resolution noise the previous
        # block's coordinate norms carried — and is what the guard's
        # monotonicity sentinel watches (coordinate norms at stalled
        # basis conditioning are noise; flagging them would turn the
        # CA-CG stability artifact into a false corruption verdict)
        rn_bs = jnp.where(cont, jnp.sqrt(jnp.maximum(rr0, 0.0)),
                          st["rn"])
        rn = rn_bs
        # in-block resolution floor (see _SSTEP_RR_FLOOR): below it the
        # coordinate residual is rounding noise — clamp the reported
        # norm at the floor (never fake convergence on noise) and freeze
        # the block; the next block restarts at full precision
        eps_r = jnp.finfo(rdt).eps
        rr_floor = _SSTEP_RR_FLOOR * m * eps_r * jnp.maximum(rr0, 0.0)
        rn_floor = jnp.sqrt(rr_floor)
        a = cont & (rn > tol)
        for _ in range(s):
            pAp = cdot(phat, cmat(G1, phat))
            brk_j = a & (pAp == 0)
            brk = brk | brk_j
            a = a & ~brk_j
            alpha = jnp.where(pAp == 0, 0.0,
                              rz / jnp.where(pAp == 0, 1.0, pAp))
            chat = jnp.where(a, chat + alpha * phat, chat)
            zhat = jnp.where(a, zhat - alpha * cmat(Sm, phat), zhat)
            rz_new = rz_of(zhat, chat)
            rr_new = (rr0 - 2.0 * jnp.real(cdot(chat, w0))
                      + jnp.real(cdot(chat, cmat(G2, chat))))
            floor_hit = rr_new <= rr_floor
            rn_new = jnp.maximum(jnp.sqrt(jnp.maximum(rr_new, 0.0)),
                                 rn_floor)
            beta = jnp.where(rz == 0, 0.0,
                             rz_new / jnp.where(rz == 0, 1.0, rz))
            phat = jnp.where(a, zhat + beta * phat, phat)
            rz = jnp.where(a, rz_new, rz)
            rn = jnp.where(a, rn_new, rn)
            it = it + a.astype(jnp.int32)
            if monitor is not None:
                hist = monitor(hist, it, rn)
            a = (a & ~floor_hit & (rn > tol) & (rn < dmax)
                 & (it < maxit))

        # ---- block end: materialize (x, r, p) from coordinates ----
        x_new = jnp.where(cm, st_(x + combine(Bz, chat)), x)
        r_new = jnp.where(cm, st_(r - combine(Bw, chat)), r)
        p_new = jnp.where(cm, st_(combine(Bz, phat)), p)
        st2 = dict(it=it, x=x_new, r=r_new, p=p_new, rn=rn, brk=brk,
                   hist=hist)

        if g is not None:
            # sentinels run on the EXACT block-start norm (one-block
            # detection lag; the ABFT channel catches apply corruption
            # immediately) — in-block coordinate norms are excluded on
            # purpose, see the rn_bs comment above. With the
            # replacement gate armed, a NaN/blow-up anomaly is the
            # CA-CG instability signature (a garbage coordinate step at
            # stalled basis conditioning can explode the iterate): it
            # ROLLS BACK to the verified carry in-program and counts
            # against the demotion budget, instead of raising a false
            # corruption verdict the host would deterministically
            # re-trip. Without the gate (abft-only), the sentinels keep
            # the classic det-code semantics.
            badnan = cont & ~jnp.isfinite(rn_bs)
            badmono = cont & jnp.isfinite(rn_bs) & (rn_bs
                                                    > _SDC_MONO_FACTOR
                                                    * st["rnb"])
            rnb = jnp.where(cont & jnp.isfinite(rn_bs),
                            jnp.minimum(st["rnb"], rn_bs), st["rnb"])
            gated = g.rr_n > 0
            det = jnp.where(st["det"] == SDC_NONE,
                            _det4(cont & badA, cont & badM,
                                  badnan & ~gated, badmono & ~gated),
                            st["det"])
            ks = st["ks"] + 1
            clean = det == SDC_NONE
            anomaly = (badnan | badmono) & gated & clean
            # the replacement interval is in ITERATIONS (-ksp_residual_
            # replacement N); an s-block covers s of them
            interval = jnp.maximum((g.rr_n + s - 1) // s, 1)
            do_rr = ((bp.agg(cont & clean) & gated
                      & (ks % interval == 0))
                     | bp.agg(anomaly))
            st2["rnb"] = rnb
            st2["ks"] = ks

            def replace(args):
                x_, r_, p_, rn_, rrc, xv, drc, rn_rr = args
                # an anomalous iterate resumes from the VERIFIED carry;
                # TRUE residual + fresh direction either way, norms on
                # plain psum (the verifier channel — a corrupted
                # verifier would lie)
                xr = jnp.where(bp.ex(anomaly), xv, x_)
                rt = b - A(xr)
                zt = M(rt)
                rtn2, _rzt = g.vpair(rt, zt)
                rtn = jnp.sqrt(jnp.maximum(rtn2, 0.0))
                # CA-CG stability gate: basis ill-conditioning shows as
                # STAGNATION of the true residual between checks (see
                # _SSTEP_STALL_FACTOR) or as the anomaly above — on
                # either, restart the recurrence from the true residual
                # (the next block rebuilds the basis), and past the
                # max_repl budget demote to classic CG (SDC_DEMOTE)
                stall = (anomaly
                         | ((rtn > tol)
                            & (rtn >= _SSTEP_STALL_FACTOR * rn_rr)))
                base = cont & clean
                ok = base & ~stall
                restart = base & stall & (drc < max_repl)
                demote = base & stall & (drc >= max_repl)
                take = bp.ex(ok | restart)
                x2_ = jnp.where(bp.ex(anomaly), xv, x_)
                r2 = jnp.where(take, st_(rt), r_)
                p2 = jnp.where(take, st_(zt), p_)
                rn2 = jnp.where(ok | restart | demote, rtn, rn_)
                xv2 = jnp.where(bp.ex(ok), x_, xv)
                rrc2 = rrc + ok.astype(jnp.int32)
                drc2 = drc + restart.astype(jnp.int32)
                rn_rr2 = jnp.where(ok | restart, rtn, rn_rr)
                det_rr = jnp.where(demote, SDC_DEMOTE,
                                   SDC_NONE).astype(jnp.int32)
                return (x2_, r2, p2, rn2, rrc2, xv2, drc2, rn_rr2,
                        det_rr)

            def keep(args):
                return args + (jnp.zeros(jnp.shape(args[3]), jnp.int32),)

            (x2, r2, p2, rn2, rrc, xv, drc, rn_rr, det_rr) = lax.cond(
                do_rr, replace, keep,
                (x_new, r_new, p_new, rn, st["rrc"], st["xv"],
                 st["drc"], st["rn_rr"]))
            det = jnp.where(det == SDC_NONE, det_rr, det)
            st2.update(x=x2, r=r2, p=p2, rn=rn2, det=det, rrc=rrc,
                       xv=xv, drc=drc, rn_rr=rn_rr)
        return st2

    st = lax.while_loop(cond, body, st0)
    xf = st["x"]
    # coordinate norms drift with the basis conditioning; report the exact
    # final residual (the pipelined plan's epilogue discipline) while
    # judging the reason on the norm the loop actually tested
    if g is not None:
        rn_true = jnp.sqrt(jnp.maximum(g.vnorm2(b - A(xf)), 0.0))
    else:
        rn_true = pnorm(b - A(xf))
    out = (xf, st["it"], rn_true,
           _reason(st["rn"], tol, atol, st["it"], maxit, st["brk"], dmax),
           st["hist"])
    if g is not None:
        out = out + (st["det"], st["rrc"], st["xv"])
    return out
