"""Krylov iteration kernels as single jit-compiled SPMD programs.

The reference's hot loop lives inside PETSc's C ``KSPSolve`` (``test.py:50``):
per iteration one MatMult (local CSR SpMV + VecScatter halo), a few
VecDot/VecNorm (local BLAS + ``MPI_Allreduce``) and VecAXPYs (SURVEY.md §3.5).
Here the *entire* Krylov iteration is one ``lax.while_loop`` inside one
``shard_map``-decorated, jit-compiled XLA program: SpMV is the ELL kernel with
an ``all_gather`` of the input vector, dots/norms are ``lax.psum`` reductions
over the mesh axis, and AXPYs fuse into neighbouring ops. Per-iteration
launch/latency overhead — PETSc's main scaling limit at small local sizes —
disappears.

Kernels are written over *local* shards and are backend-agnostic: they take
the operator ``A`` and preconditioner ``M`` as closures, so matrix-free
stencil operators plug in unchanged.
"""

from __future__ import annotations

import contextlib as _contextlib
import functools as _functools
import threading as _threading
import types as _types

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..ops.spmv import ell_spmv_local
from ..resilience import faults as _faults
from ..resilience import abft as _abft
from ..utils import aot
from ..utils.dtypes import is_complex
from ..parallel.mesh import DeviceComm, faulted_psum
from ..utils.convergence import ConvergedReason as CR
from . import cg_plans as _plans
# shared numeric helpers + SDC detector codes live in cg_plans (the plan
# assemblies and this module's non-CG kernels read ONE definition);
# re-imported here so every existing import site keeps working
from .cg_plans import (SDC_NONE, SDC_ABFT, SDC_ABFT_PC, SDC_DRIFT, SDC_NAN,
                       SDC_MONO, SDC_DEMOTE, SDC_DETECTOR_NAMES, _det4,
                       _SDC_MONO_FACTOR, _SDC_DRIFT_REL,
                       _SDC_DRIFT_FLOOR_EPS, _dmax, _tol, _nat, _reason,
                       _no_hist, _hist0, _mon0)


# ---------------------------------------------------------------------------
# kernel bodies: (A, M, pdot, pnorm, b, x0, rtol, atol, maxit) ->
#                (x, iters, rnorm, reason)
# ---------------------------------------------------------------------------

# The solver-loop reductions route through the injectable psum (the
# ``comm.psum`` fault point, parallel/mesh.faulted_psum). The
# true-residual verification epilogue stays on plain lax.psum on purpose —
# a corrupted verifier would make the gate lie about recovery.
_psum = faulted_psum


# the in-program history buffer has a STATIC capacity (maxit is a runtime
# scalar); the KSP solve sizes it from max_it + restart (cycle-granular
# kernels record at k+restart) rounded up to a power of two so capacity
# changes rarely recompile, under this hard ceiling (2M f64 entries = 16 MB)
_HIST_CAP_CEIL = 1 << 21


def hist_capacity(max_it: int, restart: int) -> int:
    """Power-of-two history capacity covering every recordable slot
    (iterations 0..max_it, plus the restart overshoot of cycle kernels)."""
    need = int(max_it) + int(restart) + 2
    return min(1 << max(need - 1, 1).bit_length(), _HIST_CAP_CEIL)


class _HistMonitor:
    """Functional in-program residual recorder.

    Kernels call ``hist = monitor(hist, k, rn)`` — a pure ``.at[k].set``
    into a (-1)-initialized buffer threaded through the loop carry, so
    monitoring needs NO host callback (a callback costs an in-loop host
    round trip). The KSP solve fetches the buffer
    once afterwards and replays the written entries, in order, to the
    user monitors — cycle-granular kernels (gmres: one entry per restart)
    leave gaps, which replay skips naturally. The sentinel is -1 because
    every monitored quantity is a nonnegative norm, while NaN (a
    legitimately recordable blown-up residual) must survive the replay
    filter. Writes beyond the capacity are dropped (mode='drop'), never
    clamped onto the last slot.
    """

    def __init__(self, dtype, cap):
        # norms are real scalars whatever the operator dtype
        self.dtype = jnp.real(jnp.zeros((), dtype)).dtype
        self.cap = int(cap)

    def init(self):
        return jnp.full((self.cap,), -1.0, self.dtype)

    def __call__(self, hist, k, rn):
        return hist.at[k].set(rn.astype(self.dtype), mode="drop")


# ---- live monitor streaming (callback-capable backends) --------------------
# NOT thread-local: io_callback host functions run on the runtime's
# callback threads, not the solving thread. One live solve owns the sink
# at a time; claiming is NON-blocking (see acquire_live_monitor) — a
# blocking claim would deadlock when a monitor itself launches a monitored
# solve (the nested claim happens on the callback thread while the outer
# solve's effects_barrier waits for that very callback to return).
_LIVE_LOCK = _threading.RLock()
_LIVE_SINK_FN = None


def acquire_live_monitor() -> bool:
    """Claim the live-monitor slot without blocking.

    Returns False when another live-monitored solve owns it (including a
    monitored solve launched FROM a monitor callback) — the caller must
    then fall back to the buffered-replay delivery, which is always
    correct. Pair with :func:`release_live_monitor`."""
    return _LIVE_LOCK.acquire(blocking=False)


def release_live_monitor():
    _LIVE_LOCK.release()


@_contextlib.contextmanager
def live_monitor_sink(fn):
    """Route in-program live monitor emissions (see :class:`_LiveMonitor`)
    to ``fn(k, rn)`` for the duration of a solve. The caller must hold the
    live-monitor slot (:func:`acquire_live_monitor`)."""
    global _LIVE_SINK_FN
    prev = _LIVE_SINK_FN
    _LIVE_SINK_FN = fn
    try:
        yield
    finally:
        _LIVE_SINK_FN = prev


def _live_emit(k, rn):
    fn = _LIVE_SINK_FN
    if fn is not None:
        fn(int(k), float(rn))


def live_monitor_supported(comm=None) -> bool:
    """Whether the mesh the solve runs on can stream monitor lines DURING
    the solve.

    CPU and TPU meshes support ordered io_callback inside shard_map: one
    call per device per record, in order — on the CPU mesh (tests) and on
    one v5e chip (chip_smoke.py's ``io_callback`` gate line, PR 21: 5 of
    5 calls, in order). Gates on the SOLVE MESH's platform, not the
    process default backend; other platforms take the buffered replay.
    """
    if comm is not None:
        return comm.devices[0].platform in _LIVE_PLATFORMS
    import jax
    return jax.default_backend() in _LIVE_PLATFORMS


_LIVE_PLATFORMS = ("cpu", "tpu")


class _LiveMonitor(_HistMonitor):
    """A :class:`_HistMonitor` that ALSO streams each record to the host
    WHILE the program runs — PETSc's live ``-ksp_monitor`` semantics — via
    ordered ``io_callback``. Only for callback-capable runtimes
    (:func:`live_monitor_supported`). Inside shard_map the callback fires
    once per device with identical (replicated) arguments; the host sink
    dedupes on ``k`` (solvers/ksp.py). The history buffer is still
    threaded and fetched, so history semantics are unchanged."""

    def __call__(self, hist, k, rn):
        from jax.experimental import io_callback
        io_callback(_live_emit, None, k, jnp.real(rn), ordered=True)
        return super().__call__(hist, k, rn)


def cg_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
              dtol=None, unroll=1, natural=False, prec=None):
    """Preconditioned conjugate gradients (KSPCG equivalent).

    Assembled from the composable plans in :mod:`.cg_plans` (classic
    recurrence, 3-site reduction plan — 2 under ``natural``), as are every
    other CG variant in this module: one ``while_loop`` body serves
    plain/stencil/batched/guarded, and pipelined CG is a reduction plan
    (``pipecg_kernel``) rather than another kernel copy.

    ``unroll`` packs that many CG steps into each ``while_loop`` body with
    per-step continuation masking: active steps run arithmetic identical to
    unroll=1 and a frozen step re-derives its own state, so iteration
    counts and reasons match exactly and iterates agree to compiler
    scheduling noise (XLA fuses/contracts the differently-shaped bodies
    differently — ulp-level only) — while the loop-iteration count drops
    by the unroll factor. On runtimes with per-loop-iteration dispatch
    overhead larger than the compute of a step, this overhead, not FLOPs
    or HBM, is the iteration-rate ceiling.

    ``natural`` switches the monitored norm to KSP_NORM_NATURAL
    (sqrt <r, M r> — the rz scalar CG already computes, zero extra
    reductions); the relative tolerance is then taken against the initial
    natural norm (= the natural norm of b for the default zero guess).
    """
    return _plans.classic_cg_loop(
        b=b, x0=x0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        A=A, M=M, pdot=pdot, pnorm=pnorm, monitor=monitor,
        unroll=unroll, natural=natural, prec=prec)


def cg_stencil_kernel(Adot, inv_diag, pdot, pnorm, b, x0, rtol, atol, maxit,
                      monitor=None, dtol=None, grid3d=None, M3=None,
                      prec=None):
    """CG fast path for uniform-diagonal stencil operators (the BASELINE
    cfg1/cfg5 hot loop, reference ``test.py:50``'s iterative analog).

    Identical recurrence to :func:`cg_kernel` with PC none/jacobi/mg —
    the same :func:`cg_plans.classic_cg_loop` body with the stencil
    operator-apply and PC plans:

    - the SpMV and the ``<p, Ap>`` reduction run in ONE fused Pallas pass
      (``Adot``) while both operands are VMEM-resident;
    - the Jacobi apply collapses to a scalar multiply (the stencil diagonal
      is uniform), folded into the p-update — no ``z`` vector exists at all;
    - ``rz = <r, M r> = inv_diag * ||r||²`` reuses the residual-norm
      reduction;
    - the loop state lives in the operator's GRID shape (``grid3d``),
      reshaped once at entry/exit: a flat->3D reshape around the Pallas
      call inside the loop body materializes full-array copies (measured
      +9 HBM passes / 2.5x per-iteration at 256³); on 3D carries the whole
      step runs in ~6 passes (~0.51 ms at 256³ fp32 vs the 11-pass model's
      0.90 — the model overcounted, XLA fuses the update chain);
    - with ``M3`` (a 3D-native preconditioner apply, the slab V-cycle from
      PC.local_apply_grid3d) the scalar Jacobi identities are replaced by
      ``z = M3(r)``, ``rz = <r, z>`` — the general PCG recurrence, still on
      grid-shaped carries with zero in-loop reshapes.

    Convergence, breakdown, and divergence semantics match ``cg_kernel`` at
    ``unroll=1`` exactly; iteration counts and the monitored norm
    (unpreconditioned ``||r||``) are the same.
    """
    flat = b.shape
    if grid3d is not None:
        b = b.reshape(grid3d)
        x0 = x0.reshape(grid3d)
    out = _plans.classic_cg_loop(
        b=b, x0=x0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        Adot=Adot, inv_diag=inv_diag, M3=M3, pdot=pdot, pnorm=pnorm,
        monitor=monitor, prec=prec)
    x = out[0].reshape(flat) if grid3d is not None else out[0]
    return (x,) + out[1:]


# ---------------------------------------------------------------------------
# silent-data-corruption guard: ABFT-checksummed CG kernels + invariant
# monitors (README "Silent-error detection", resilience/abft.py)
# ---------------------------------------------------------------------------

# detector codes (SDC_*), sentinels, and thresholds are defined once in
# cg_plans.py and re-exported at the top of this module

# KSP types with a guarded (ABFT + invariant-monitor) kernel variant:
# cg's two-phase plan folds the checksums into its stacked psums, pipecg's
# single-reduction plan folds them into its ONE stacked psum, and sstep's
# basis-build checksums ride its one stacked Gram psum per s-block
GUARDED_TYPES = ("cg", "pipecg", "sstep")


def _make_guard(dtype, axis, cs_l, csM_l, abft_tol, rr_n, *, dot, tsum,
                tasum, cmul, no_bad, pdot, pnorm, eps_dtype=None):
    """The guard closure bundle shared by the single-RHS and batched
    guarded kernels — ONE definition of the ABFT check algebra.

    The two callers differ only in reduction shape: single-RHS reduces
    vectors to scalars (``dot=jnp.vdot``, ``tsum=jnp.sum``), the batched
    path reduces ``(lsize, nrhs)`` blocks to per-column ``(nrhs,)``
    vectors. ``cmul`` broadcasts the checksum vector against an operand
    of that shape, ``no_bad`` builds the shape-matched "nothing fired"
    verdict, and ``pdot``/``pnorm`` are the plain solver reductions the
    checksum-less fallbacks use. All checksum partials fold into ONE
    stacked (possibly faulted) psum per phase; ``vpair`` — the
    replacement VERIFIER — uses plain ``lax.psum`` on purpose (a
    corrupted verifier would lie about recovery).

    Under a mixed precision plan ``dtype`` is the REDUCE dtype (the
    stacked psum's accumulation channel) while ``eps_dtype`` carries the
    STORAGE dtype whose rounding sets the detection threshold — a bf16
    apply's benign error is bf16-sized however wide the accumulator is.
    """
    eps = _abft.checksum_tolerance_dtype(eps_dtype or dtype)

    def _stack_psum(parts):
        return _psum(jnp.stack([jnp.asarray(q, dtype) for q in parts]),
                     axis)

    thr = lambda scale: abft_tol * eps * scale

    if cs_l is not None:
        def init_g(b_, r_, x0_):
            # verifies the INITIAL apply r = b - A x0:
            # Σr - (Σb - ⟨c, x0⟩) ≈ 0, folded into the ‖b‖ reduction
            # (complex: plain transpose checksum, no conjugation —
            # Σ(Ax) = (Aᵀ1)ᵀx)
            cx = cmul(cs_l, x0_)
            s = _stack_psum([dot(b_, b_), tsum(r_), tsum(b_), tsum(cx),
                             tasum(r_), tasum(b_), tasum(cx)])
            bad = (jnp.abs(s[1] - s[2] + s[3])
                   > thr(jnp.real(s[4]) + jnp.real(s[5])
                         + jnp.real(s[6])))
            return jnp.sqrt(jnp.maximum(jnp.real(s[0]), 0.0)), bad

        def p1_g(p_, Ap_):
            cp = cmul(cs_l, p_)
            s = _stack_psum([dot(p_, Ap_), tsum(Ap_), tsum(cp),
                             tasum(Ap_), tasum(cp)])
            bad = (jnp.abs(s[1] - s[2])
                   > thr(jnp.real(s[3]) + jnp.real(s[4])))
            return s[0], bad
    else:
        def init_g(b_, r_, x0_):
            return pnorm(b_), no_bad(b_)

        def p1_g(p_, Ap_):
            return pdot(p_, Ap_), no_bad(p_)

    if csM_l is not None:
        def p2_g(r_, z_):
            cr = cmul(csM_l, r_)
            s = _stack_psum([dot(r_, z_), dot(r_, r_), tsum(z_),
                             tsum(cr), tasum(z_), tasum(cr)])
            bad = (jnp.abs(s[2] - s[3])
                   > thr(jnp.real(s[4]) + jnp.real(s[5])))
            return s[0], jnp.real(s[1]), bad
    else:
        def p2_g(r_, z_):
            s = _stack_psum([dot(r_, z_), dot(r_, r_)])
            return s[0], jnp.real(s[1]), no_bad(r_)

    def vpair(rt, zt):
        s = lax.psum(jnp.stack([jnp.asarray(dot(rt, rt), dtype),
                                jnp.asarray(dot(rt, zt), dtype)]), axis)
        return jnp.real(s[0]), s[1]

    return _types.SimpleNamespace(init=init_g, p1=p1_g, p2=p2_g,
                                  vpair=vpair, rr_n=rr_n, eps=eps)


def _make_pipe_guard(dtype, axis, cs_l, csM_l, abft_tol, rr_n, *, dot,
                     tsum, tasum, cmul, no_bad, pdot, pnorm,
                     eps_dtype=None):
    """The guard bundle for the PIPELINED reduction plan.

    Pipelined CG's one stacked psum per iteration reduces ``<r,u>``,
    ``<w,u>`` and ``||r||²`` from the CURRENT vectors; the ABFT partials
    ride the SAME stack, so the guarded pipelined program still has
    exactly ONE reduce site per iteration.

    What is checked: each body's FRESH applies — ``m = M w`` and
    ``n = A m`` are computed in the same body (they are the overlap
    work), so their checksum identities ``Σn ≈ ⟨c, m⟩`` (``c = Aᵀ1``)
    and ``Σm ≈ ⟨c_M, w⟩`` (``c_M = Mᵀ1``) compare same-magnitude
    same-iteration quantities, exactly like the classic guard's phases.
    The local (collective-free) partials are carried ONE iteration and
    folded into the NEXT body's stacked psum (``chk_parts`` ->
    ``fused``), so detection lags one iteration and the collective count
    stays at one. Checking the u/w RECURRENCES against the checksums
    instead would false-positive by construction: their drift is the
    classic pipelined-CG rounding loss, which grows relative to the
    decaying residual scale — that drift is the replacement gate's job,
    not ABFT's. ``init``/``vnorm2`` reuse the classic guard's init check
    and plain-psum verifier (:func:`_make_guard` — the replacement
    verifier must never ride the injectable psum).
    """
    base = _make_guard(dtype, axis, cs_l, csM_l, abft_tol, rr_n, dot=dot,
                       tsum=tsum, tasum=tasum, cmul=cmul, no_bad=no_bad,
                       pdot=pdot, pnorm=pnorm, eps_dtype=eps_dtype)
    eps = base.eps
    thr = lambda scale: abft_tol * eps * scale

    def chk_parts(mv, nv, wv):
        """Local checksum partials of THIS body's fresh applies, reduced
        in the NEXT body's single stacked psum: operator channel
        ``n = A m`` -> ``Σn`` vs ``⟨c, m⟩``; PC channel ``m = M w`` ->
        ``Σm`` vs ``⟨c_M, w⟩``. At init the same identities read
        ``(u0, w0, r0)`` for ``(m, n, w)`` — ``w0 = A u0``,
        ``u0 = M r0``."""
        parts = ()
        if cs_l is not None:
            cm_ = cmul(cs_l, mv)
            parts += (tsum(nv), tsum(cm_), tasum(nv), tasum(cm_))
        if csM_l is not None:
            cw_ = cmul(csM_l, wv)
            parts += (tsum(mv), tsum(cw_), tasum(mv), tasum(cw_))
        return parts

    def chk_init(r0, u0, w0):
        return chk_parts(u0, w0, r0)

    def fused(r, u, w, chk):
        parts = [dot(r, u), dot(w, u), dot(r, r)] + list(chk)
        s = _plans.fuse_psum(parts, _psum, axis, dtype)
        gamma, delta, rr = s[0], s[1], s[2]
        i = 3
        if cs_l is not None:
            badA = (jnp.abs(s[i] - s[i + 1])
                    > thr(jnp.real(s[i + 2]) + jnp.real(s[i + 3])))
            i += 4
        else:
            badA = no_bad(r)
        if csM_l is not None:
            badM = (jnp.abs(s[i] - s[i + 1])
                    > thr(jnp.real(s[i + 2]) + jnp.real(s[i + 3])))
        else:
            badM = no_bad(r)
        return gamma, delta, rr, badA, badM

    def vnorm2(rt):
        return jnp.real(lax.psum(jnp.asarray(dot(rt, rt), dtype), axis))

    def vpair2(rt, rc):
        """Replacement verifier: ‖true residual‖² and ‖CURRENT recurrence
        residual‖² in one plain stacked psum. The pipelined loop's carried
        norm lags one iteration, so the drift gate must compare the true
        residual against the current recurrence residual — gating on the
        lagged norm would flag every superlinear convergence drop as
        corruption."""
        s = lax.psum(jnp.stack([jnp.asarray(dot(rt, rt), dtype),
                                jnp.asarray(dot(rc, rc), dtype)]), axis)
        return jnp.real(s[0]), jnp.real(s[1])

    return _types.SimpleNamespace(init=base.init, fused=fused,
                                  chk_parts=chk_parts, chk_init=chk_init,
                                  vnorm2=vnorm2, vpair2=vpair2,
                                  rr_n=rr_n, eps=eps)


def _make_sstep_guard(dtype, axis, cs_l, csM_l, abft_tol, rr_n, *, dot,
                      tsum, tasum, cmul, no_bad, pdot, pnorm,
                      eps_dtype=None):
    """The guard bundle for the S-STEP reduction plan.

    The s-step loop checks its basis-build applies itself — every chain
    apply's checksum partials (``Σ(A v) ≈ ⟨c, v⟩`` per basis column,
    ``Σ(M w) ≈ ⟨c_M, w⟩`` per PC pair) are column sums the loop folds
    into its one stacked Gram psum (:func:`cg_plans.fuse_gram_psum`), so
    the per-s-block collective count stays at ONE. This bundle therefore
    carries the raw checksum shards (``cs``/``csM``) and the threshold
    inputs for the loop's in-body algebra, plus the shared init check and
    the plain-psum replacement verifier from :func:`_make_guard` — the
    verifier must never ride the injectable psum. The drift gate's
    CA-CG-specific semantics (basis restart, demotion budget) live in
    :func:`cg_plans.sstep_cg_loop`."""
    base = _make_guard(dtype, axis, cs_l, csM_l, abft_tol, rr_n, dot=dot,
                       tsum=tsum, tasum=tasum, cmul=cmul, no_bad=no_bad,
                       pdot=pdot, pnorm=pnorm, eps_dtype=eps_dtype)

    def vnorm2(rt):
        return jnp.real(lax.psum(jnp.asarray(dot(rt, rt), dtype), axis))

    return _types.SimpleNamespace(init=base.init, vpair=base.vpair,
                                  vnorm2=vnorm2, rr_n=rr_n, eps=base.eps,
                                  cs=cs_l, csM=csM_l, abft_tol=abft_tol,
                                  no_bad=no_bad)


def cg_kernel_guarded(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, g,
                      monitor=None, dtol=None, prec=None):
    """Preconditioned CG with the in-program silent-corruption guard.

    Per-iteration arithmetic matches :func:`cg_kernel` at unroll=1; the
    guard adds, at ZERO extra collectives per iteration:

    * ABFT checks on the operator apply (``⟨1, Ap⟩ ≈ ⟨c, p⟩`` folded into
      the phase-1 ``⟨p, Ap⟩`` psum — ``g.p1``) and, when the PC checksum
      exists, on the preconditioner apply (folded into the phase-2 psum
      that also carries ``⟨r, z⟩`` and ``‖r‖²`` — ``g.p2``; the guarded
      program actually has FEWER reduction sites than the plain kernel,
      which psums rz and ‖r‖ separately);
    * NaN and monotonicity sentinels on the monitored norm;
    * every ``g.rr_n`` iterations (``-ksp_residual_replacement``), a
      TRUE-residual replacement: ``r ← b - A x`` with a direction restart
      (``p ← z``), a recurrence-vs-true drift gate, and promotion of the
      current iterate to the VERIFIED iterate ``xv`` the host rolls back
      to on detection. The replacement's reductions use plain
      ``lax.psum`` (``g.vpair``) — a corrupted verifier would lie.

    Returns ``(x, k, rnorm, reason, hist, det, rrc, xv)``: ``det`` is the
    first detector code that fired (0 = clean), ``rrc`` the replacement
    count, ``xv`` the last verified iterate (``x0`` until a replacement
    passes).
    """
    return _plans.classic_cg_loop(
        b=b, x0=x0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        A=A, M=M, pdot=pdot, pnorm=pnorm, guard=g, monitor=monitor,
        prec=prec)


def cg_stencil_kernel_guarded(Adot, inv_diag, pdot3, pnorm3, b, x0, rtol,
                              atol, maxit, g, monitor=None, dtol=None,
                              grid3d=None, prec=None):
    """Guarded twin of :func:`cg_stencil_kernel` (uniform-diagonal stencil
    fast path, PC none/jacobi — the scalar Jacobi identities mean there is
    no in-program PC apply, so only the operator ABFT channel exists).

    The fused ``Adot`` already psums ``⟨p, Ap⟩`` internally, so the ABFT
    partials fold into the PHASE-2 reduction (``‖r‖²``) instead — the
    per-iteration collective count still does not grow. Checksum ``cs``
    rides grid-shaped through ``g``.
    """
    flat = b.shape
    if grid3d is not None:
        b = b.reshape(grid3d)
        x0 = x0.reshape(grid3d)
    out = _plans.classic_cg_loop(
        b=b, x0=x0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        Adot=Adot, inv_diag=inv_diag, pdot=pdot3, pnorm=pnorm3, guard=g,
        monitor=monitor, prec=prec)
    if grid3d is not None:
        out = ((out[0].reshape(flat),) + out[1:7]
               + (out[7].reshape(flat),))
    return out


# BiCGStab's omega floor (Sleijpen & van der Vorst, "Maintaining convergence
# properties of BiCGstab methods in finite precision arithmetic", Numer.
# Algorithms 10, 1995): where t and s are nearly orthogonal the
# minimal-residual omega is small, the next BiCG coefficients lose accuracy
# and convergence stalls for a stretch whose length rounding decides. Scaling
# omega to a cosine of at least OMEGA_KAPPA keeps them accurate: on fp64 2D
# convection-diffusion at 2048^2 with 64 line-block ILU(0) blocks (a TPU v5e
# chip) a solve to rtol 1e-8 takes 726-739 iterations, against 919-1057 with
# the plain omega, a count that follows the rounding of each right-hand side.
OMEGA_KAPPA = 0.7


def _limit_omega(omega, ts, tt, ss):
    """``omega = (t, s) / (t, t)`` scaled by ``OMEGA_KAPPA / |cos(t, s)|``
    where that cosine is below ``OMEGA_KAPPA`` (and not 0, a breakdown)."""
    # the norms apart: tt * ss underflows near convergence in float32
    den = jnp.sqrt(jnp.real(tt)) * jnp.sqrt(jnp.real(ss))
    cos = jnp.abs(ts) / jnp.where(den > 0, den, 1.0)
    lift = (den > 0) & (cos > 0) & (cos < OMEGA_KAPPA)
    return jnp.where(lift, omega * (OMEGA_KAPPA / jnp.where(lift, cos, 1.0)),
                     omega)


def bcgs_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
                dtol=None):
    """Right-preconditioned BiCGStab (KSPBCGS equivalent), its omega kept
    from collapsing (:data:`OMEGA_KAPPA`), which PETSc's KSPBCGS does not."""
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    r = b - A(x0)
    rhat = r
    rnorm = pnorm(r)
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)
    one = jnp.asarray(1.0, b.dtype)
    z = jnp.zeros_like(b)

    def cond(st):
        k, x, r, p, v, rho, alpha, omega, rn, brk, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit) & ~brk

    def body(st):
        k, x, r, p, v, rho, alpha, omega, rn, brk, hist = st
        rho_new = pdot(rhat, r)
        brk = (rho_new == 0) | (omega == 0)
        beta = jnp.where(brk, 0.0,
                         (rho_new / jnp.where(rho == 0, 1.0, rho))
                         * (alpha / jnp.where(omega == 0, 1.0, omega)))
        p = r + beta * (p - omega * v)
        phat = M(p)
        v = A(phat)
        rv = pdot(rhat, v)
        brk = brk | (rv == 0)
        alpha = jnp.where(brk, 0.0, rho_new / jnp.where(rv == 0, 1.0, rv))
        s = r - alpha * v
        shat = M(s)
        t = A(shat)
        tt = pdot(t, t)
        ts = pdot(t, s)
        omega = jnp.where(tt == 0, 0.0, ts / jnp.where(tt == 0, 1.0, tt))
        omega = _limit_omega(omega, ts, tt, pdot(s, s))
        x = x + alpha * phat + omega * shat
        r = s - omega * t
        rn = pnorm(r)
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, x, r, p, v, rho_new, alpha, omega, rn, brk, hist)

    st0 = (jnp.int32(0), x0, r, z, z, one, one, one, rnorm, rnorm <= -1.0,
           hist)
    out = lax.while_loop(cond, body, st0)
    k, x, r, p, v, rho, alpha, omega, rnorm, brk, hist = out
    return (x, k, rnorm, _reason(rnorm, tol, atol, k, maxit, brk, dmax),
            hist)


def fbcgsr_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
                  dtol=None, preduce=None):
    """Flexible BiCGStab with rearranged, merged reductions (KSPFBCGSR).

    Mathematically equivalent to right-preconditioned BiCGStab (so it
    tolerates a variable preconditioner, like ``fbcgs``), but the recurrence
    is reorganized the way PETSc's FBCGSR is: instead of four separate global
    reductions per iteration (rho, r̂·v, t·s/t·t, ‖r‖), the scalars are
    re-derived so one psum covers the ``r̂·v`` phase and one *fused* psum
    covers ``(t·s, t·t, r̂·t, s·s)`` — two reduction phases per iteration.
    The next rho and the residual norm come from scalar identities::

        r       = s - ω t
        (r̂, r)  = (r̂, s) - ω (r̂, t) = (ρ - α r̂·v) - ω r̂·t
        ‖r‖²    = s·s - 2ω t·s + ω² t·t

    The final residual norm is recomputed as ‖b - A x‖ on exit, so the
    scalar-recurrence drift never leaks into the reported norm.
    """
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    r = b - A(x0)
    rhat = r
    rnorm = pnorm(r)
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)
    one = jnp.asarray(1.0, b.dtype)
    z = jnp.zeros_like(b)

    def cond(st):
        k, x, r, p, v, rho, rho_cur, alpha, omega, rn, brk, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit) & ~brk

    def body(st):
        k, x, r, p, v, rho, rho_cur, alpha, omega, rn, brk, hist = st
        brk = (rho_cur == 0) | (omega == 0)
        beta = jnp.where(brk, 0.0,
                         (rho_cur / jnp.where(rho == 0, 1.0, rho))
                         * (alpha / jnp.where(omega == 0, 1.0, omega)))
        p = r + beta * (p - omega * v)
        phat = M(p)
        v = A(phat)
        rv = pdot(rhat, v)                       # reduction phase 1
        brk = brk | (rv == 0)
        alpha = jnp.where(brk, 0.0, rho_cur / jnp.where(rv == 0, 1.0, rv))
        s = r - alpha * v
        shat = M(s)
        t = A(shat)
        # reduction phase 2: all remaining dots in ONE fused psum
        ts, tt, rt, ss = preduce(jnp.vdot(t, s), jnp.vdot(t, t),
                                 jnp.vdot(rhat, t), jnp.vdot(s, s))
        omega = jnp.where(tt == 0, 0.0, ts / jnp.where(tt == 0, 1.0, tt))
        omega = _limit_omega(omega, ts, tt, ss)
        x = x + alpha * phat + omega * shat
        r = s - omega * t
        # ω = t·s/t·t minimizes this quantity, so near stagnation the
        # subtraction cancels; its noise floor is O(eps·s·s). Clamping to
        # exactly 0 would fake an instant-convergence exit (breaking the
        # fixed-iteration contract under tol=0 and mislabeling ATOL), so
        # floor at the noise level instead — below it the recurrence cannot
        # resolve the norm anyway (an exactly-zero r costs at most one
        # extra iteration before the floor itself falls under tolerance).
        # Complex form: ‖s - ωt‖² = s·s - 2Re(ω̄·(t,s)) + |ω|²·t·t with the
        # Hermitian inner product ((t,s) = vdot(t,s)); (s,s)/(t,t) are real
        # by construction. Reduces exactly to the textbook real identity.
        eps = jnp.finfo(b.dtype).eps
        rn2 = (jnp.real(ss) - 2 * jnp.real(jnp.conj(omega) * ts)
               + jnp.abs(omega) ** 2 * jnp.real(tt))
        rn = jnp.sqrt(jnp.maximum(rn2, eps * jnp.real(ss)))
        rho_next = (rho_cur - alpha * rv) - omega * rt
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, x, r, p, v, rho_cur, rho_next, alpha, omega, rn,
                brk, hist)

    # rho_cur starts at (r̂, r₀) = ‖r₀‖² — real-valued, but typed to the
    # operator scalar so the carry stays dtype-consistent on complex builds
    st0 = (jnp.int32(0), x0, r, z, z, one,
           jnp.asarray(rnorm * rnorm, b.dtype), one, one,
           rnorm, rnorm <= -1.0, hist)
    out = lax.while_loop(cond, body, st0)
    k, x, rn, brk, hist = out[0], out[1], out[9], out[10], out[11]
    # judge convergence on the norm the loop actually tested (the scalar
    # recurrence), report the recomputed true norm — as bcgsl does; judging
    # on rn_true could mislabel a converged exit as DIVERGED_MAX_IT when the
    # recurrence drifts marginally across the tolerance
    rn_true = pnorm(b - A(x))
    return (x, k, rn_true, _reason(rn, tol, atol, k, maxit, brk, dmax),
            hist)


def _hessenberg_lstsq(H, beta):
    """Solve ``min ||beta*e1 - H y||`` for upper-Hessenberg H of shape (m+1, m).

    Givens rotations + masked back-substitution — only elementwise ops and
    small matvecs, so it compiles on every backend/dtype (XLA:TPU lacks f64
    LU/SVD, ruling out jnp.linalg.lstsq/solve here). Returns (y, |g[m]|) —
    the second value is the least-squares residual estimate.
    """
    m = H.shape[1]
    g = jnp.zeros(m + 1, H.dtype).at[0].set(beta)

    def rotate(j, Hg):
        # complex-capable Givens: c real, s = sign(a)·conj(b)/r, applied as
        # [c, s; -conj(s), c] — zeroes H[j+1, j] for any scalar field and
        # reduces to the textbook real rotation (conj = identity) otherwise
        H, g = Hg
        a, bb = H[j, j], H[j + 1, j]
        aa = jnp.abs(a)
        r = jnp.sqrt(aa * aa + jnp.abs(bb) ** 2)
        safe = jnp.where(r == 0, 1.0, r)
        sgn = jnp.where(aa == 0, 1.0, a / jnp.where(aa == 0, 1.0, aa))
        c = jnp.where(r == 0, 1.0, aa / safe)
        s = jnp.where(r == 0, 0.0, sgn * jnp.conj(bb) / safe)
        sc = jnp.conj(s)
        rj, rj1 = H[j], H[j + 1]
        H = H.at[j].set(c * rj + s * rj1).at[j + 1].set(-sc * rj + c * rj1)
        gj, gj1 = g[j], g[j + 1]
        g = g.at[j].set(c * gj + s * gj1).at[j + 1].set(-sc * gj + c * gj1)
        return (H, g)

    H, g = lax.fori_loop(0, m, rotate, (H, g))

    def back(i_rev, y):
        i = m - 1 - i_rev
        rii = H[i, i]
        # y entries below i are still zero, so the full row product is the
        # already-solved tail sum.
        s = g[i] - H[i, :m] @ y
        yi = jnp.where(rii == 0, 0.0, s / jnp.where(rii == 0, 1.0, rii))
        return y.at[i].set(yi)

    y = lax.fori_loop(0, m, back, jnp.zeros(m, H.dtype))
    return y, jnp.abs(g[m])


def _cgs2_step(V, w, pmatdot, pnorm):
    """One CGS2 orthogonalization step shared by GMRES/FGMRES/Arnoldi.

    Projects ``w`` against the basis rows of ``V`` twice (classical
    Gram-Schmidt, re-applied — two fused whole-basis psums); rows of V
    beyond the current column are zero, so no masking is needed. Returns
    ``(h, hnorm, v_next)``.
    """
    h1 = pmatdot(V, w)
    w = w - h1 @ V
    h2 = pmatdot(V, w)
    w = w - h2 @ V
    hnorm = pnorm(w)
    return h1 + h2, hnorm, w / jnp.where(hnorm == 0, 1.0, hnorm)


def gmres_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
                 restart=30, pmatdot=None, monitor=None, dtol=None):
    """Left-preconditioned restarted GMRES (KSPGMRES equivalent).

    Convergence is monitored in the preconditioned residual norm, matching
    PETSc's default (KSP_NORM_PRECONDITIONED). Arnoldi orthogonalizes with
    twice-applied classical Gram-Schmidt (CGS2): two fused whole-basis
    psums per step instead of j sequential ones — communication-optimal on
    the mesh, no dynamic basis-row indexing, and as stable as modified GS.
    The small least-squares problem is solved per cycle with Givens
    rotations (portable across backends/dtypes).
    """
    m = restart
    lsize = b.shape[0]
    pb = M(b)
    bnorm = pnorm(pb)
    tol = jnp.maximum(rtol * bnorm, atol)
    r0 = M(b - A(x0))
    rnorm0 = pnorm(r0)
    dmax = _dmax(rnorm0, dtol)
    hist0 = _mon0(monitor, rnorm0, b.dtype)

    def cycle(st):
        k, x, rn, hist = st
        r = M(b - A(x))
        beta = pnorm(r)
        V = jnp.zeros((m + 1, lsize), b.dtype)
        V = V.at[0].set(r / jnp.where(beta == 0, 1.0, beta))
        H = jnp.zeros((m + 1, m), b.dtype)

        def arnoldi(j, VH):
            V, H = VH
            w = M(A(V[j]))
            h, hnorm, vnext = _cgs2_step(V, w, pmatdot, pnorm)
            H = H.at[:, j].set(h)
            H = H.at[j + 1, j].set(hnorm)
            V = V.at[j + 1].set(vnext)
            return (V, H)

        V, H = lax.fori_loop(0, m, arnoldi, (V, H))
        y, _ = _hessenberg_lstsq(H, beta)
        x = x + y @ V[:m]
        rn = pnorm(M(b - A(x)))
        if monitor is not None:
            hist = monitor(hist, k + m, rn)
        return (k + m, x, rn, hist)

    def cond(st):
        k, x, rn, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit)

    k, x, rnorm, hist = lax.while_loop(
        cond, cycle, (jnp.int32(0), x0, rnorm0, hist0))
    brk = rnorm <= -1.0
    return (x, k, rnorm, _reason(rnorm, tol, atol, k, maxit, brk, dmax),
            hist)


def preonly_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
                   dtol=None, refine=False):
    """Apply the preconditioner exactly once (KSPPREONLY equivalent).

    With PC 'lu' this is the reference's direct-solve path
    (``test.py:38-43``: preonly + PCLU + MUMPS). ``refine`` is set by the
    program builder ONLY for direct-factorization PC kinds (dense lu /
    cyclic-reduction modes): there, iterative refinement recovers
    accuracy lost to reduced-precision application of the factorization
    (the fp32-on-TPU story, SURVEY.md §7.3) — steps repeat while the true
    residual keeps halving, so an exact inverse exits after two applies,
    while a reduced-precision factorization (fp32 device BPCR, dense-cast
    factors) polishes on at ~one SpMV + apply per step until its
    factor-limited accuracy floor (cap 20). A non-improving step is
    discarded, so the returned iterate is never worse than the plain
    single apply. Non-direct PCs keep PETSc's literal KSPPREONLY
    semantics — exactly one application, no refinement (a contracting
    PC like gamg would otherwise silently run a 20-step Richardson).
    """
    x = M(b)
    r = b - A(x)
    rn = pnorm(r)
    if not refine:
        return (x, jnp.int32(1), rn,
                jnp.full((), CR.CONVERGED_ITS, jnp.int32),
                _hist0(monitor, b.dtype))

    def cond(st):
        k, x, r, rn, go = st
        return go

    def body(st):
        k, x, r, rn, _ = st
        x2 = x + M(r)
        r2 = b - A(x2)
        rn2 = pnorm(r2)
        better = rn2 < rn
        x2 = jnp.where(better, x2, x)
        r2 = jnp.where(better, r2, r)
        rn_keep = jnp.where(better, rn2, rn)
        go = (rn2 < 0.5 * rn) & (k + 1 < 20)
        return (k + 1, x2, r2, rn_keep, go)

    _, x, _, rnorm, _ = lax.while_loop(
        cond, body, (jnp.int32(0), x, r, rn, rn > 0))
    return (x, jnp.int32(1), rnorm,
            jnp.full((), CR.CONVERGED_ITS, jnp.int32),
            _hist0(monitor, b.dtype))


def richardson_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
                      scale=1.0, monitor=None, dtol=None):
    """Preconditioned Richardson iteration (KSPRICHARDSON equivalent)."""
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    r = b - A(x0)
    rnorm = pnorm(r)
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)

    def cond(st):
        k, x, r, rn, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit)

    def body(st):
        k, x, r, rn, hist = st
        x = x + scale * M(r)
        r = b - A(x)
        rn = pnorm(r)
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, x, r, rn, hist)

    k, x, r, rnorm, hist = lax.while_loop(
        cond, body, (jnp.int32(0), x0, r, rnorm, hist))
    return (x, k, rnorm,
            _reason(rnorm, tol, atol, k, maxit, rnorm <= -1.0, dmax), hist)


def minres_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
                  dtol=None):
    """MINRES for symmetric (possibly indefinite) systems (KSPMINRES).

    Paige & Saunders recurrences with left preconditioning (M must be SPD,
    as in PETSc); the QR of the tridiagonal is updated with Givens rotations
    in-loop, so each iteration is one SpMV + one PC apply + two psums.
    """
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    r1 = b - A(x0)
    y = M(r1)
    # Hermitian A + SPD M: every Lanczos/rotation scalar is real in exact
    # arithmetic — carry them real-typed (complex vectors, real scalars)
    beta1 = jnp.sqrt(jnp.maximum(jnp.real(pdot(r1, y)), 0.0))
    dmax = _dmax(pnorm(r1), dtol)
    zero = jnp.zeros_like(b)
    dt = jnp.real(jnp.zeros((), b.dtype)).dtype

    def cond(st):
        return ((st["rn"] > tol) & (st["rn"] < dmax) & (st["k"] < maxit)
                & ~st["brk"])

    def body(st):
        k = st["k"]
        beta = st["beta"]
        safe_b = jnp.where(beta == 0, 1.0, beta)
        v = st["y"] / safe_b
        yv = A(v)
        yv = yv - jnp.where(k > 0, beta / jnp.where(st["beta_old"] == 0, 1.0,
                                                    st["beta_old"]), 0.0) \
            * st["r1"]
        alfa = jnp.real(pdot(v, yv))
        yv = yv - (alfa / safe_b) * st["r2"]
        y_new = M(yv)
        beta_new = jnp.sqrt(jnp.maximum(jnp.real(pdot(yv, y_new)), 0.0))
        # QR via Givens
        oldeps = st["epsln"]
        delta = st["cs"] * st["dbar"] + st["sn"] * alfa
        gbar = st["sn"] * st["dbar"] - st["cs"] * alfa
        epsln = st["sn"] * beta_new
        dbar = -st["cs"] * beta_new
        gamma = jnp.sqrt(gbar * gbar + beta_new * beta_new)
        gamma = jnp.where(gamma == 0, jnp.asarray(1e-30, dt), gamma)
        cs = gbar / gamma
        sn = beta_new / gamma
        phi = cs * st["phibar"]
        phibar = sn * st["phibar"]
        w1 = st["w2"]
        w2 = st["w"]
        w = (v - oldeps * w1 - delta * w2) / gamma
        x = st["x"] + phi * w
        rn = jnp.abs(phibar) * st["rn0_scale"]
        hist = st["hist"]
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return dict(k=k + 1, x=x, r1=st["r2"], r2=yv, y=y_new,
                    beta_old=beta, beta=beta_new, dbar=dbar, epsln=epsln,
                    phibar=phibar, cs=cs, sn=sn, w=w, w2=w2,
                    rn=rn, rn0_scale=st["rn0_scale"], brk=st["brk"],
                    hist=hist)

    rnorm0 = pnorm(r1)
    scale = rnorm0 / jnp.where(beta1 == 0, 1.0, beta1)
    hist = _mon0(monitor, rnorm0, b.dtype)
    st0 = dict(k=jnp.int32(0), x=x0, r1=r1, r2=r1, y=y,
               beta_old=jnp.asarray(1.0, dt), beta=beta1,
               dbar=jnp.asarray(0.0, dt), epsln=jnp.asarray(0.0, dt),
               phibar=beta1, cs=jnp.asarray(-1.0, dt),
               sn=jnp.asarray(0.0, dt), w=zero, w2=zero,
               rn=rnorm0, rn0_scale=scale, brk=beta1 < 0, hist=hist)
    st = lax.while_loop(cond, body, st0)
    # exact final residual (the phibar estimate tracks the M-norm)
    rn_true = pnorm(b - A(st["x"]))
    return (st["x"], st["k"], rn_true,
            _reason(rn_true, tol, atol, st["k"], maxit, st["brk"], dmax),
            st["hist"])


def chebyshev_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
                     monitor=None, dtol=None):
    """Chebyshev iteration (KSPCHEBYSHEV) — the cheapest distributed smoother.

    Saad's three-term form on the preconditioned operator. Eigenvalue bounds
    follow PETSc's default recipe — ``[0.1 λmax, 1.1 λmax]`` of M⁻¹A with
    λmax estimated by power iteration (10 steps, in-program); only the
    convergence check and the estimation need psums, the iteration itself is
    collective-free.
    """
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    dt = b.dtype

    # power iteration for λmax of M⁻¹A (SPD assumption, as PETSc's default)
    def power(i, v):
        w = M(A(v))
        return w / jnp.maximum(pnorm(w), jnp.asarray(1e-30, dt))

    v0 = b / jnp.maximum(bnorm, jnp.asarray(1e-30, dt))
    v = lax.fori_loop(0, 10, power, v0)
    lam_max = pdot(v, M(A(v))) / jnp.maximum(pdot(v, v),
                                             jnp.asarray(1e-30, dt))
    emax = 1.1 * lam_max
    emin = 0.1 * lam_max
    theta = (emax + emin) / 2.0
    delta = (emax - emin) / 2.0
    sigma = theta / delta

    r = b - A(x0)
    z = M(r)
    rnorm = pnorm(r)
    dmax = _dmax(rnorm, dtol)
    rho = 1.0 / sigma
    d = z / theta
    hist = _mon0(monitor, rnorm, b.dtype)

    def cond(st):
        k, x, r, d, rho, rn, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit)

    def body(st):
        k, x, r, d, rho, rn, hist = st
        x = x + d
        r = r - A(d)
        z = M(r)
        rho_new = 1.0 / (2.0 * sigma - rho)
        d = rho_new * rho * d + (2.0 * rho_new / delta) * z
        rn = pnorm(r)
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, x, r, d, rho_new, rn, hist)

    st0 = (jnp.int32(0), x0, r, d, rho, rnorm, hist)
    k, x, r, d, rho, rnorm, hist = lax.while_loop(cond, body, st0)
    return (x, k, rnorm,
            _reason(rnorm, tol, atol, k, maxit, rnorm <= -1.0, dmax), hist)


def pipecg_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
                  preduce=None, monitor=None, dtol=None, prec=None):
    """Pipelined single-reduction CG (Ghysels–Vanroose; KSPPIPECG slot).

    Standard CG needs three separate reductions per iteration ((p,Ap),
    (r,z), ||r||); here all three inner products are computed from the
    CURRENT vectors and fused into ONE stacked ``lax.psum``
    (:func:`cg_plans.fuse_psum`) — and, unlike the Chronopoulos–Gear
    form, the next iteration's PC+operator applies (``m = M w``,
    ``n = A m``) are INDEPENDENT of the reduction's results, so XLA's
    async collectives overlap the reduce with the SpMV (the
    latency-hiding the two-stage multisplitting line of work gets from
    restructured communication). Mathematically equivalent to CG in
    exact arithmetic; the extra u/w recurrences drift in finite
    precision — the residual-replacement gate of the guarded variant
    (:func:`pipecg_kernel_guarded`) is the bound. PETSc's KSPPIPECG
    needs ``MPI_Iallreduce`` for the same overlap (PARITY.md).
    """
    up = (prec.up if prec is not None and prec.mixed else (lambda v: v))

    def fused(r, u, w):
        ru, uu, wu = up(r), up(u), up(w)
        s = preduce(jnp.vdot(ru, uu), jnp.vdot(wu, uu), jnp.vdot(ru, ru))
        return s[0], s[1], s[2]

    return _plans.pipelined_cg_loop(
        b=b, x0=x0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        A=A, M=M, pnorm=pnorm, fused=fused, monitor=monitor, prec=prec)


def pipecg_kernel_guarded(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, g,
                          monitor=None, dtol=None, prec=None):
    """Guarded pipelined CG: the GV recurrences with the ABFT partials
    folded into the ONE stacked psum (:func:`_make_pipe_guard` — the
    guarded pipelined program keeps exactly one reduce site per
    iteration), NaN/monotonicity sentinels, and the periodic
    true-residual replacement that both bounds the pipelined drift and
    promotes verified iterates (``xv``) for rollback. Output contract
    matches :func:`cg_kernel_guarded`."""
    return _plans.pipelined_cg_loop(
        b=b, x0=x0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        A=A, M=M, pnorm=pnorm, fused=g.fused, guard=g,
        monitor=monitor, prec=prec)


def pipecg_stencil_kernel(A3, inv_diag, pnorm3, fused, b, x0, rtol, atol,
                          maxit, monitor=None, dtol=None, grid3d=None,
                          prec=None):
    """Pipelined-CG fast path for uniform-diagonal stencil operators:
    grid-shaped carries (zero in-loop reshapes — the
    :func:`cg_stencil_kernel` traffic discipline), the 3D-native apply
    (``StencilPoisson3D.local_apply_grid3``), and the scalar-Jacobi
    identity ``m = w / diag`` — still exactly ONE stacked psum per
    iteration (the fused matvec+dot kernel is deliberately NOT used
    here: its internal ``<u, Au>`` psum would be a second reduce
    site)."""
    flat = b.shape
    if grid3d is not None:
        b = b.reshape(grid3d)
        x0 = x0.reshape(grid3d)
    Mdiag = ((lambda r: (r * inv_diag).astype(prec.storage))
             if prec is not None and prec.mixed
             else (lambda r: r * inv_diag))
    out = _plans.pipelined_cg_loop(
        b=b, x0=x0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        A=A3, M=Mdiag, pnorm=pnorm3, fused=fused,
        monitor=monitor, prec=prec)
    x = out[0].reshape(flat) if grid3d is not None else out[0]
    return (x,) + out[1:]


def pipecg_kernel_many(A, M, pdotc, pnormc, fused, B, X0, rtol, atol,
                      maxit, monitor=None, dtol=None, prec=None):
    """Batched pipelined CG: ``nrhs`` GV recurrences in lockstep with
    per-column masked convergence (the :func:`cg_kernel_many`
    discipline); ``fused`` reduces every column's (gamma, delta, ||r||²)
    rows in ONE stacked psum, so the per-iteration collective count is
    ONE — independent of both nrhs and, vs the classic plan, the phase
    count."""
    return _plans.pipelined_cg_loop(
        b=B, x0=X0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        A=A, M=M, pnorm=pnormc, fused=fused,
        bp=_plans.ManyBatch("cols"), monitor=monitor, prec=prec)


def pipecg_kernel_many_guarded(A, M, pdotc, pnormc, B, X0, rtol, atol,
                               maxit, g, monitor=None, dtol=None,
                               prec=None):
    """Batched guarded pipelined CG: mask-aware per-column detection
    (sticky det codes, frozen columns keep verified state) with all
    guard partials riding the single stacked psum. Output contract
    matches :func:`cg_kernel_many_guarded`."""
    return _plans.pipelined_cg_loop(
        b=B, x0=X0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        A=A, M=M, pnorm=pnormc, fused=g.fused, guard=g,
        bp=_plans.ManyBatch("cols"), monitor=monitor, prec=prec)


def sstep_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, *, s,
                 greduce, monitor=None, dtol=None, prec=None):
    """s-step communication-avoiding CG (CA-CG; no PETSc KSP analog —
    KSPPIPECG is the nearest, PARITY.md round 16).

    Advances CG s iterations per ``while_loop`` body around ONE stacked
    psum — the tall-skinny Gram matrix of the block's monomial Krylov
    bases — with the s iterations run as host-free coefficient
    recurrences in basis coordinates (:func:`cg_plans.sstep_cg_loop`).
    The per-iteration reduction count drops to 1/s at the cost of
    ~2x the operator applies (the two-basis monomial CA-CG trade): the
    win is real exactly where per-reduction latency dominates per-apply
    cost — the high-latency-interconnect regime."""
    return _plans.sstep_cg_loop(
        b=b, x0=x0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol, s=s,
        greduce=greduce, A=A, M=M, pnorm=pnorm, monitor=monitor,
        prec=prec)


def sstep_kernel_guarded(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, g,
                         *, s, greduce, max_repl, monitor=None, dtol=None,
                         prec=None):
    """Guarded s-step CG: basis-build ABFT partials folded into the one
    stacked Gram psum (:func:`_make_sstep_guard`), NaN/monotonicity
    sentinels at block ends, and the periodic true-residual gate with
    CA-CG semantics — drift restarts the basis from the true residual,
    and past ``max_repl`` restarts (``-ksp_sstep_max_replacements``)
    the loop exits with the ``SDC_DEMOTE`` code so KSP demotes the solve
    to classic CG. Output contract matches :func:`cg_kernel_guarded`."""
    return _plans.sstep_cg_loop(
        b=b, x0=x0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol, s=s,
        greduce=greduce, A=A, M=M, pnorm=pnorm, guard=g,
        max_repl=max_repl, monitor=monitor, prec=prec)


def sstep_kernel_many(A, M, pdotc, pnormc, B, X0, rtol, atol, maxit, *, s,
                      greduce, monitor=None, dtol=None, prec=None):
    """Batched s-step CG: ``nrhs`` lockstep CA-CG recurrences with
    per-column bases and per-column masked convergence — the one stacked
    Gram psum reduces every column's ``(2m+1)²`` block in a single
    collective, so the per-s-block collective count is ONE independent
    of nrhs."""
    return _plans.sstep_cg_loop(
        b=B, x0=X0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol, s=s,
        greduce=greduce, A=A, M=M, pnorm=pnormc,
        bp=_plans.ManyBatch("cols"), monitor=monitor, prec=prec)


def sstep_kernel_many_guarded(A, M, pdotc, pnormc, B, X0, rtol, atol,
                              maxit, g, *, s, greduce, max_repl,
                              monitor=None, dtol=None, prec=None):
    """Batched guarded s-step CG: mask-aware per-column detection (sticky
    det codes, frozen columns keep verified state) with every guard
    partial riding the single stacked Gram psum. Output contract matches
    :func:`cg_kernel_many_guarded`."""
    return _plans.sstep_cg_loop(
        b=B, x0=X0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol, s=s,
        greduce=greduce, A=A, M=M, pnorm=pnormc, guard=g,
        max_repl=max_repl, bp=_plans.ManyBatch("cols"), monitor=monitor,
        prec=prec)


def fgmres_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
                  restart=30, pmatdot=None, monitor=None, dtol=None):
    """Flexible (right-preconditioned) restarted GMRES (KSPFGMRES).

    Stores the preconditioned basis ``Z[j] = M(V[j])`` explicitly, so M may
    change between applications — required when the preconditioner is itself
    an iterative method (multigrid with variable cycles, inner Krylov
    solves). Convergence is monitored in the UNpreconditioned residual norm
    (PETSc's KSP_NORM_UNPRECONDITIONED default for FGMRES).
    """
    m = restart
    lsize = b.shape[0]
    bnorm = pnorm(b)
    tol = jnp.maximum(rtol * bnorm, atol)
    rnorm0 = pnorm(b - A(x0))
    dmax = _dmax(rnorm0, dtol)
    hist0 = _mon0(monitor, rnorm0, b.dtype)

    def cycle(st):
        k, x, rn, hist = st
        r = b - A(x)
        beta = pnorm(r)
        V = jnp.zeros((m + 1, lsize), b.dtype)
        V = V.at[0].set(r / jnp.where(beta == 0, 1.0, beta))
        Z = jnp.zeros((m, lsize), b.dtype)
        H = jnp.zeros((m + 1, m), b.dtype)

        def arnoldi(j, VZH):
            V, Z, H = VZH
            z = M(V[j])
            Z = Z.at[j].set(z)
            w = A(z)
            h, hnorm, vnext = _cgs2_step(V, w, pmatdot, pnorm)
            H = H.at[:, j].set(h)
            H = H.at[j + 1, j].set(hnorm)
            V = V.at[j + 1].set(vnext)
            return (V, Z, H)

        V, Z, H = lax.fori_loop(0, m, arnoldi, (V, Z, H))
        y, _ = _hessenberg_lstsq(H, beta)
        x = x + y @ Z
        rn = pnorm(b - A(x))
        if monitor is not None:
            hist = monitor(hist, k + m, rn)
        return (k + m, x, rn, hist)

    def cond(st):
        k, x, rn, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit)

    k, x, rnorm, hist = lax.while_loop(
        cond, cycle, (jnp.int32(0), x0, rnorm0, hist0))
    return (x, k, rnorm,
            _reason(rnorm, tol, atol, k, maxit, rnorm <= -1.0, dmax), hist)


def cgs_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
               dtol=None):
    """Conjugate Gradient Squared (KSPCGS), right-preconditioned.

    Solves ``(A·M) y = r0`` for the correction and applies ``x = x0 + M(y)``
    once at the end, so the residual monitored in-loop is the TRUE residual
    of the original system.
    """
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    op = lambda v: A(M(v))
    r = b - A(x0)
    rtilde = r
    rnorm = pnorm(r)
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)
    zero = jnp.zeros_like(b)
    dt = b.dtype

    def cond(st):
        return ((st["rn"] > tol) & (st["rn"] < dmax) & (st["k"] < maxit)
                & ~st["brk"])

    def body(st):
        k = st["k"]
        rho_new = pdot(rtilde, st["r"])
        brk = rho_new == 0
        rho_old = jnp.where(st["rho"] == 0, 1.0, st["rho"])
        beta = jnp.where(brk, 0.0, rho_new / rho_old)
        u = st["r"] + beta * st["q"]
        p = u + beta * (st["q"] + beta * st["p"])
        v = op(p)
        sigma = pdot(rtilde, v)
        brk = brk | (sigma == 0)
        alpha = jnp.where(brk, 0.0, rho_new / jnp.where(sigma == 0, 1.0, sigma))
        q = u - alpha * v
        uq = u + q
        y = st["y"] + alpha * uq
        r = st["r"] - alpha * op(uq)
        rn = pnorm(r)
        hist = st["hist"]
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return dict(k=k + 1, y=y, r=r, p=p, q=q, rho=rho_new, rn=rn,
                    brk=brk, hist=hist)

    st0 = dict(k=jnp.int32(0), y=zero, r=r, p=zero, q=zero,
               rho=jnp.asarray(1.0, dt), rn=rnorm, brk=rnorm <= -1.0,
               hist=hist)
    st = lax.while_loop(cond, body, st0)
    x = x0 + M(st["y"])
    # converged-reason from the recurrence residual the loop monitored
    # (PETSc semantics); the reported norm is the true residual, which may
    # drift above it in reduced precision (CGS squares the residual poly).
    rn_true = pnorm(b - A(x))
    return (x, st["k"], rn_true,
            _reason(st["rn"], tol, atol, st["k"], maxit, st["brk"], dmax),
            st["hist"])


def tfqmr_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
                 dtol=None):
    """Transpose-Free QMR (Freund 1993; KSPTFQMR), right-preconditioned.

    Runs on the correction system ``(A·M) y = r0``; the loop monitors the
    quasi-residual bound ``tau * sqrt(2k+1)`` (PETSc's dp), and the exact
    residual is evaluated once after the loop for the reported norm/reason.
    Two operator applications per (double) iteration, like BiCGStab.
    """
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    op = lambda v: A(M(v))
    r0 = b - A(x0)
    rstar = r0
    tau0 = pnorm(r0)
    dmax = _dmax(tau0, dtol)
    hist = _mon0(monitor, tau0, b.dtype)
    zero = jnp.zeros_like(b)
    dt = b.dtype
    u1_0 = op(r0)

    def half(st, yj, uj, alpha):
        """One half-step of the inner j=1,2 update."""
        w = st["w"] - alpha * uj
        safe_a = jnp.where(alpha == 0, 1.0, alpha)
        d = yj + (st["theta"] ** 2 * st["eta"] / safe_a) * st["d"]
        tau_old = jnp.where(st["tau"] == 0, 1.0, st["tau"])
        theta = pnorm(w) / tau_old
        c2 = 1.0 / (1.0 + theta * theta)
        tau = st["tau"] * theta * jnp.sqrt(c2)
        eta = c2 * alpha
        y = st["y"] + eta * d
        return dict(st, w=w, d=d, theta=theta, tau=tau, eta=eta, y=y)

    def cond(st):
        return ((st["dp"] > tol) & (st["dp"] < dmax) & (st["k"] < maxit)
                & ~st["brk"])

    def body(st):
        k = st["k"]
        sigma = pdot(rstar, st["v"])
        brk = sigma == 0
        alpha = jnp.where(brk, 0.0,
                          st["rho"] / jnp.where(sigma == 0, 1.0, sigma))
        y2 = st["y1"] - alpha * st["v"]
        u2 = op(y2)
        st1 = half(st, st["y1"], st["u1"], alpha)
        st2 = half(st1, y2, u2, alpha)
        rho_new = pdot(rstar, st2["w"])
        brk = brk | (st["rho"] == 0)
        beta = rho_new / jnp.where(st["rho"] == 0, 1.0, st["rho"])
        y1 = st2["w"] + beta * y2
        u1 = op(y1)
        v = u1 + beta * (u2 + beta * st["v"])
        # quasi-residual bound on the true residual after 2(k+1) half-steps
        dp = st2["tau"] * jnp.sqrt(2.0 * (k + 1) + 1.0)
        hist = st["hist"]
        if monitor is not None:
            hist = monitor(hist, k + 1, dp)
        return dict(st2, k=k + 1, y1=y1, u1=u1, v=v, rho=rho_new,
                    dp=dp, brk=brk, hist=hist)

    # mixed-dtype carry for complex builds: theta/tau/dp are norms (real),
    # eta/rho are Krylov coefficients (operator scalar)
    rdt = jnp.real(jnp.zeros((), dt)).dtype
    st0 = dict(k=jnp.int32(0), y=zero, w=r0, y1=r0, u1=u1_0, v=u1_0,
               d=zero, theta=jnp.asarray(0.0, rdt), eta=jnp.asarray(0.0, dt),
               tau=tau0, rho=pdot(rstar, r0), dp=tau0, brk=tau0 <= -1.0,
               hist=hist)
    st = lax.while_loop(cond, body, st0)
    x = x0 + M(st["y"])
    rn_true = pnorm(b - A(x))
    return (x, st["k"], rn_true,
            _reason(st["dp"], tol, atol, st["k"], maxit, st["brk"], dmax),
            st["hist"])


def cr_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
              dtol=None, natural=False):
    """Preconditioned Conjugate Residuals (KSPCR) for symmetric systems.

    Minimizes the preconditioned residual M(b - Ax) in the A-norm sense;
    requires symmetric A and SPD M (as PETSc documents for KSPCR). One SpMV
    + one PC apply + two psums per iteration. ``natural`` monitors
    sqrt <r, A r> of the preconditioned residual (the rho scalar the
    recurrence already carries), relative to its initial value.
    """
    r = M(b - A(x0))
    p = r
    w = A(r)        # A r
    q = w           # A p
    rho = pdot(r, w)
    if natural:
        rnorm = _nat(rho)
        tol = jnp.maximum(rtol * rnorm, atol)
        brk0 = jnp.real(rho) < 0     # indefinite A: natural norm undefined
    else:
        pb = M(b)
        bnorm = pnorm(pb)
        tol = jnp.maximum(rtol * bnorm, atol)
        rnorm = pnorm(r)
        brk0 = rnorm <= -1.0
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)

    def cond(st):
        k, x, r, p, w, q, rho, rn, brk, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit) & ~brk

    def body(st):
        k, x, r, p, w, q, rho, rn, brk, hist = st
        Mq = M(q)
        qMq = pdot(q, Mq)
        brk = qMq == 0
        alpha = jnp.where(brk, 0.0, rho / jnp.where(brk, 1.0, qMq))
        x = x + alpha * p
        r = r - alpha * Mq
        w = A(r)
        rho_new = pdot(r, w)
        if natural:
            brk = brk | (jnp.real(rho_new) < 0)
        beta = jnp.where(rho == 0, 0.0, rho_new / jnp.where(rho == 0, 1.0, rho))
        p = r + beta * p
        q = w + beta * q
        rn = _nat(rho_new) if natural else pnorm(r)
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, x, r, p, w, q, rho_new, rn, brk, hist)

    st0 = (jnp.int32(0), x0, r, p, w, q, rho, rnorm, brk0, hist)
    k, x, r, p, w, q, rho, rnorm, brk, hist = lax.while_loop(cond, body,
                                                             st0)
    return (x, k, rnorm, _reason(rnorm, tol, atol, k, maxit, brk, dmax),
            hist)


def lsqr_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
                At=None, monitor=None, dtol=None):
    """LSQR (Paige & Saunders 1982; KSPLSQR) via Golub-Kahan bidiagonalization.

    Solves ``min ||b - Ax||`` — usable on unsymmetric and inconsistent
    systems. Needs the transpose product ``At`` (operators provide
    ``local_spmv_t``; the preconditioner is ignored, matching PETSc's
    default unpreconditioned KSPLSQR).
    """
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    dt = b.dtype

    def normalize(v):
        nv = pnorm(v)
        return v / jnp.where(nv == 0, 1.0, nv), nv

    u, beta = normalize(b - A(x0))
    v, alfa = normalize(At(u))
    w = v
    dmax = _dmax(beta, dtol)
    hist = _mon0(monitor, beta, b.dtype)

    def cond(st):
        return ((st["phibar"] > tol) & (st["phibar"] < dmax)
                & (st["k"] < maxit) & ~st["brk"])

    def body(st):
        k = st["k"]
        u, beta = normalize(A(st["v"]) - st["alfa"] * st["u"])
        v, alfa = normalize(At(u) - beta * st["v"])
        rho = jnp.sqrt(st["rhobar"] ** 2 + beta ** 2)
        brk = rho == 0
        safe_rho = jnp.where(brk, 1.0, rho)
        c = st["rhobar"] / safe_rho
        s = beta / safe_rho
        theta = s * alfa
        rhobar = -c * alfa
        phi = c * st["phibar"]
        phibar = s * st["phibar"]
        x = st["x"] + (phi / safe_rho) * st["w"]
        w = v - (theta / safe_rho) * st["w"]
        hist = st["hist"]
        if monitor is not None:
            hist = monitor(hist, k + 1, phibar)
        return dict(k=k + 1, x=x, u=u, v=v, w=w, alfa=alfa,
                    rhobar=rhobar, phibar=phibar, brk=brk, hist=hist)

    st0 = dict(k=jnp.int32(0), x=x0, u=u, v=v, w=w, alfa=alfa,
               rhobar=alfa, phibar=beta, brk=beta <= -1.0, hist=hist)
    st = lax.while_loop(cond, body, st0)
    rn_true = pnorm(b - A(st["x"]))
    return (st["x"], st["k"], rn_true,
            _reason(st["phibar"], tol, atol, st["k"], maxit, st["brk"],
                    dmax), st["hist"])


def bicg_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
                At=None, Mt=None, dtol=None):
    """Biconjugate gradients (KSPBICG): dual recurrences on A and A^T.

    The shadow system preconditions with ``Mt`` — the PCApplyTranspose
    closure (falls back to ``M`` for symmetric applies).

    Complex builds use PETSc's Hermitian variant: the shadow sequence runs
    on ``A^H``/``M^H`` (the caller wires ``At``/``Mt`` as adjoints) and its
    coefficient updates carry the CONJUGATED alpha/beta — with the
    Hermitian inner product this preserves the biorthogonality relations
    ``(r̃_i, z_j) = 0``. ``conj`` is the identity on real scalars, so one
    kernel serves both builds.
    """
    if Mt is None:
        Mt = M
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    r = b - A(x0)
    rt = r
    z = M(r)
    zt = Mt(rt)
    p = z
    pt = zt
    rho = pdot(rt, z)
    rnorm = pnorm(r)
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)

    def cond(st):
        k, x, r, rt, p, pt, rho, rn, brk, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit) & ~brk

    def body(st):
        k, x, r, rt, p, pt, rho, rn, brk, hist = st
        q = A(p)
        qt = At(pt)
        pq = pdot(pt, q)
        brk = (pq == 0) | (rho == 0)
        alpha = jnp.where(brk, 0.0, rho / jnp.where(pq == 0, 1.0, pq))
        x = x + alpha * p
        r = r - alpha * q
        rt = rt - jnp.conj(alpha) * qt
        z = M(r)
        zt = Mt(rt)
        rho_new = pdot(rt, z)
        beta = jnp.where(rho == 0, 0.0,
                         rho_new / jnp.where(rho == 0, 1.0, rho))
        p = z + beta * p
        pt = zt + jnp.conj(beta) * pt
        rn = pnorm(r)
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, x, r, rt, p, pt, rho_new, rn, brk, hist)

    st0 = (jnp.int32(0), x0, r, rt, p, pt, rho, rnorm, rnorm <= -1.0, hist)
    k, x, r, rt, p, pt, rho, rnorm, brk, hist = lax.while_loop(cond, body,
                                                               st0)
    return (x, k, rnorm, _reason(rnorm, tol, atol, k, maxit, brk, dmax),
            hist)


def gcr_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
               restart=30, pmatdot=None, dtol=None):
    """Restarted GCR (KSPGCR): flexible — the preconditioner may change
    between iterations (like fgmres), with explicitly stored (v, z) pairs.

    The stored search directions live in fixed (restart, n_local) buffers;
    orthogonalization against them is one fused ``psum`` matvec (empty slots
    are zero rows, so no masking is needed).
    """
    m = restart
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    r = b - A(x0)
    rnorm = pnorm(r)
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)
    V = jnp.zeros((m,) + b.shape, b.dtype)
    Z = jnp.zeros_like(V)

    def cond(st):
        k, slot, x, r, V, Z, rn, brk, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit) & ~brk

    def body(st):
        k, slot, x, r, V, Z, rn, brk, hist = st
        wiped = (slot != 0).astype(b.dtype)
        V = V * wiped            # restart boundary: clear the direction set
        Z = Z * wiped
        z = M(r)
        v = A(z)
        bcoef = pmatdot(V, v)
        v = v - bcoef @ V
        z = z - bcoef @ Z
        nv = pnorm(v)
        brk = nv == 0
        nv_safe = jnp.where(brk, 1.0, nv)
        v = v / nv_safe
        z = z / nv_safe
        # the projection of r onto the normalized direction is <v, r> —
        # conjugate on v (pdot conjugates its first argument); real dtypes
        # are unaffected, complex ones stagnate with the order flipped
        alpha = pdot(v, r)
        x = x + alpha * z
        r = r - alpha * v
        V = V.at[slot].set(v)
        Z = Z.at[slot].set(z)
        rn = pnorm(r)
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, (slot + 1) % m, x, r, V, Z, rn, brk, hist)

    st0 = (jnp.int32(0), jnp.int32(0), x0, r, V, Z, rnorm, rnorm <= -1.0,
           hist)
    k, slot, x, r, V, Z, rnorm, brk, hist = lax.while_loop(cond, body, st0)
    return (x, k, rnorm, _reason(rnorm, tol, atol, k, maxit, brk, dmax),
            hist)


def cgne_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
                At=None, dtol=None):
    """CG on the normal equations A^T A x = A^T b (KSPCGNE).

    Squares the condition number but handles unsymmetric/rank-deficient
    square systems with only A and A^T products; the PC applies to the
    normal-equations residual. Convergence is tested on ||b - Ax|| like the
    other kernels.
    """
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    r = b - A(x0)
    s = At(r)
    z = M(s)
    p = z
    gamma = pdot(s, z)
    rnorm = pnorm(r)
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)

    def cond(st):
        k, x, r, p, gamma, rn, brk, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit) & ~brk

    def body(st):
        k, x, r, p, gamma, rn, brk, hist = st
        q = A(p)
        qq = pdot(q, q)
        brk = qq == 0
        alpha = jnp.where(brk, 0.0, gamma / jnp.where(brk, 1.0, qq))
        x = x + alpha * p
        r = r - alpha * q
        s = At(r)
        z = M(s)
        gamma_new = pdot(s, z)
        beta = jnp.where(gamma == 0, 0.0,
                         gamma_new / jnp.where(gamma == 0, 1.0, gamma))
        p = z + beta * p
        rn = pnorm(r)
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, x, r, p, gamma_new, rn, brk, hist)

    st0 = (jnp.int32(0), x0, r, p, gamma, rnorm, rnorm <= -1.0, hist)
    k, x, r, p, gamma, rnorm, brk, hist = lax.while_loop(cond, body, st0)
    return (x, k, rnorm, _reason(rnorm, tol, atol, k, maxit, brk, dmax),
            hist)


def symmlq_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, monitor=None,
                  dtol=None):
    """SYMMLQ (Paige & Saunders 1975; KSPSYMMLQ) for symmetric systems.

    The LQ companion of MINRES: iterates in the Krylov space with an LQ
    factorization of the tridiagonal, keeping the error (not the residual)
    monotone — the classical choice for symmetric *indefinite* systems where
    CG's recurrences break. Preconditioned Lanczos as in MINRES (M must be
    SPD). The loop monitors the CG-point residual estimate and transfers to
    the CG point on exit; the reported norm is the exact final residual.
    """
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    # Hermitian A + SPD M: the Lanczos/LQ scalars are real in exact
    # arithmetic — carry them real-typed (complex vectors, real scalars)
    dt = jnp.real(jnp.zeros((), b.dtype)).dtype
    r0 = b - A(x0)
    rnorm0 = pnorm(r0)
    dmax = _dmax(rnorm0, dtol)
    hist = _mon0(monitor, rnorm0, b.dtype)

    y = M(r0)
    beta1sq = jnp.real(pdot(r0, y))
    beta1 = jnp.sqrt(jnp.maximum(beta1sq, 0.0))
    safe_b1 = jnp.where(beta1 == 0, 1.0, beta1)
    v = y / safe_b1
    y2 = A(v)
    alfa = jnp.real(pdot(v, y2))
    y2 = y2 - (alfa / safe_b1) * r0
    r2 = y2
    y3 = M(r2)
    betasq = jnp.real(pdot(r2, y3))
    beta = jnp.sqrt(jnp.maximum(betasq, 0.0))
    # recurrence norms live in the M-weighted space; rescale estimates so
    # the tolerance test runs on the unpreconditioned residual norm
    scale = rnorm0 / safe_b1

    def cond(st):
        return ((st["rn"] > tol) & (st["rn"] < dmax) & (st["k"] < maxit)
                & ~st["brk"])

    def body(st):
        k = st["k"]
        beta_c = st["beta"]
        safe_beta = jnp.where(beta_c == 0, 1.0, beta_c)
        v = st["y"] / safe_beta
        yv = A(v)
        oldb_safe = jnp.where(st["oldb"] == 0, 1.0, st["oldb"])
        yv = yv - (beta_c / oldb_safe) * st["r1"]
        alfa = jnp.real(pdot(v, yv))
        yv = yv - (alfa / safe_beta) * st["r2"]
        r1 = st["r2"]
        r2 = yv
        y_new = M(r2)
        oldb = beta_c
        betasq = jnp.real(pdot(r2, y_new))
        brk = st["brk"] | (betasq < 0)
        beta_new = jnp.sqrt(jnp.maximum(betasq, 0.0))
        # plane rotation (LQ factorization of the tridiagonal)
        gamma = jnp.sqrt(st["gbar"] ** 2 + oldb ** 2)
        gamma = jnp.where(gamma == 0, jnp.asarray(1e-30, dt), gamma)
        cs = st["gbar"] / gamma
        sn = oldb / gamma
        delta = cs * st["dbar"] + sn * alfa
        gbar = sn * st["dbar"] - cs * alfa
        epsln = sn * beta_new
        dbar = -cs * beta_new
        # update the LQ point
        z = st["rhs1"] / gamma
        x = st["x"] + (z * cs) * st["w"] + (z * sn) * v
        w = sn * st["w"] - cs * v
        bstep = st["snprod"] * cs * z + st["bstep"]
        snprod = st["snprod"] * sn
        rhs1 = st["rhs2"] - delta * z
        rhs2 = -epsln * z
        # CG-point residual estimate for the convergence test
        qrnorm = snprod * beta1
        gbar_safe = jnp.where(gbar == 0, jnp.asarray(1e-30, dt), gbar)
        cgnorm = qrnorm * beta_new / jnp.abs(gbar_safe)
        rn = cgnorm * scale
        hist = st["hist"]
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return dict(k=k + 1, x=x, w=w, r1=r1, r2=r2, y=y_new,
                    oldb=oldb, beta=beta_new, gbar=gbar, dbar=dbar,
                    rhs1=rhs1, rhs2=rhs2, snprod=snprod, bstep=bstep,
                    rn=rn, brk=brk, hist=hist)

    zero = jnp.zeros_like(b)
    st0 = dict(k=jnp.int32(0), x=zero, w=zero, r1=r0, r2=r2, y=y3,
               oldb=beta1, beta=beta, gbar=alfa, dbar=beta,
               rhs1=beta1, rhs2=jnp.asarray(0.0, dt),
               snprod=jnp.asarray(1.0, dt), bstep=jnp.asarray(0.0, dt),
               rn=rnorm0, brk=(beta1sq < 0) | (betasq < 0), hist=hist)
    st = lax.while_loop(cond, body, st0)
    # transfer LQ point -> CG point, then add the component along v1 —
    # only if the loop actually iterated (the transfer IS one CG step; an
    # already-converged initial guess must come back untouched)
    gbar_safe = jnp.where(st["gbar"] == 0, 1.0, st["gbar"])
    zbar = st["rhs1"] / gbar_safe
    bstep = st["snprod"] * zbar + st["bstep"]
    xc = st["x"] + zbar * st["w"]
    xc = xc + (bstep / safe_b1) * y      # y = M(r0) from initialization
    x = x0 + jnp.where(st["k"] > 0, xc, jnp.zeros_like(b))
    rn_true = pnorm(b - A(x))
    return (x, st["k"], rn_true,
            _reason(rn_true, tol, atol, st["k"], maxit, st["brk"], dmax),
            st["hist"])


def fcg_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
               restart=30, pmatdot=None, monitor=None, dtol=None,
               natural=False):
    """Truncated flexible CG (Notay; KSPFCG).

    The preconditioner may change between iterations; new directions are
    A-orthogonalized against a sliding window of the last ``restart`` stored
    pairs ``(p_i, Ap_i)``. The whole-window projection is one fused ``psum``
    matvec per iteration (empty slots are zero rows — no masking needed).
    ``z = M r`` for the CURRENT residual is carried in the loop state (it is
    needed one iteration later anyway), so the ``natural`` norm
    sqrt <r, M r> costs one extra psum and no extra PC applies.
    """
    m = restart
    r = b - A(x0)
    if natural:
        z0 = M(r)
        rz0 = pdot(r, z0)
        rnorm = _nat(rz0)
        tol = jnp.maximum(rtol * rnorm, atol)
        brk0 = jnp.real(rz0) < 0     # indefinite M: natural norm undefined
    else:
        z0 = jnp.zeros_like(b)       # placeholder: body computes z at top
        bnorm, tol = _tol(pnorm, b, rtol, atol)
        rnorm = pnorm(r)
        brk0 = rnorm <= -1.0
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)
    Pbuf = jnp.zeros((m,) + b.shape, b.dtype)
    APbuf = jnp.zeros_like(Pbuf)
    eta = jnp.zeros(m, b.dtype)

    def cond(st):
        k, slot, x, r, z, Pb, APb, eta, rn, brk, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit) & ~brk

    def body(st):
        k, slot, x, r, z, Pb, APb, eta, rn, brk, hist = st
        if not natural:
            z = M(r)       # default mode applies M at the top; natural
                           # mode carries the end-of-body z (same count)
        c = pmatdot(APb, z)                 # z . Ap_i over the window
        coef = jnp.where(eta != 0, c / jnp.where(eta == 0, 1.0, eta), 0.0)
        p = z - coef @ Pb
        Ap = A(p)
        pAp = pdot(p, Ap)
        brk = pAp == 0
        alpha = jnp.where(brk, 0.0,
                          pdot(p, r) / jnp.where(brk, 1.0, pAp))
        x = x + alpha * p
        r = r - alpha * Ap
        Pb = Pb.at[slot].set(p)
        APb = APb.at[slot].set(Ap)
        eta = eta.at[slot].set(pAp)
        if natural:
            z = M(r)
            rz = pdot(r, z)
            brk = brk | (jnp.real(rz) < 0)
            rn = _nat(rz)
        else:
            rn = pnorm(r)
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, (slot + 1) % m, x, r, z, Pb, APb, eta, rn, brk,
                hist)

    st0 = (jnp.int32(0), jnp.int32(0), x0, r, z0, Pbuf, APbuf, eta,
           rnorm, brk0, hist)
    k, slot, x, r, z0, Pbuf, APbuf, eta, rnorm, brk, hist = \
        lax.while_loop(cond, body, st0)
    return (x, k, rnorm, _reason(rnorm, tol, atol, k, maxit, brk, dmax),
            hist)


def lgmres_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
                  restart=30, aug=2, pmatdot=None, monitor=None, dtol=None):
    """LGMRES (Baker, Jessup & Manteuffel 2005; KSPLGMRES).

    Restarted GMRES whose search space is augmented with the ``aug`` most
    recent *error approximations* (the correction vectors of previous
    cycles) — recovering much of the convergence lost to restarting on
    problems where plain GMRES(m) stalls. Until the augmentation slots fill,
    their zero rows contribute harmless zero columns to the small
    least-squares problem (the masked back-substitution returns 0 for them).
    """
    if aug <= 0:      # PETSc semantics: zero augmentation = plain GMRES(m)
        return gmres_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
                            restart=restart, pmatdot=pmatdot, monitor=monitor,
                            dtol=dtol)
    m = restart
    s = m + aug
    lsize = b.shape[0]
    pb = M(b)
    bnorm = pnorm(pb)
    tol = jnp.maximum(rtol * bnorm, atol)
    rnorm0 = pnorm(M(b - A(x0)))
    dmax = _dmax(rnorm0, dtol)
    hist0 = _mon0(monitor, rnorm0, b.dtype)
    Z0 = jnp.zeros((aug, lsize), b.dtype)

    def cycle(st):
        k, x, Z, rn, hist = st
        r = M(b - A(x))
        beta = pnorm(r)
        V = jnp.zeros((s + 1, lsize), b.dtype)
        V = V.at[0].set(r / jnp.where(beta == 0, 1.0, beta))
        W = jnp.zeros((s, lsize), b.dtype)
        H = jnp.zeros((s + 1, s), b.dtype)

        def arnoldi(j, VWH):
            V, W, H = VWH
            vj = lax.dynamic_index_in_dim(V, j, keepdims=False)
            zj = lax.dynamic_index_in_dim(
                Z, jnp.clip(j - m, 0, aug - 1), keepdims=False)
            wexp = jnp.where(j < m, vj, zj)
            W = W.at[j].set(wexp)
            u = M(A(wexp))
            h, hnorm, vnext = _cgs2_step(V, u, pmatdot, pnorm)
            H = H.at[:, j].set(h)
            H = H.at[j + 1, j].set(hnorm)
            V = V.at[j + 1].set(vnext)
            return (V, W, H)

        V, W, H = lax.fori_loop(0, s, arnoldi, (V, W, H))
        y, _ = _hessenberg_lstsq(H, beta)
        dx = y @ W
        x = x + dx
        ndx = pnorm(dx)
        znew = dx / jnp.where(ndx == 0, 1.0, ndx)
        Z = jnp.roll(Z, 1, axis=0).at[0].set(znew)
        rn = pnorm(M(b - A(x)))
        if monitor is not None:
            hist = monitor(hist, k + s, rn)
        return (k + s, x, Z, rn, hist)

    def cond(st):
        k, x, Z, rn, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit)

    k, x, Z, rnorm, hist = lax.while_loop(
        cond, cycle, (jnp.int32(0), x0, Z0, rnorm0, hist0))
    return (x, k, rnorm,
            _reason(rnorm, tol, atol, k, maxit, rnorm <= -1.0, dmax), hist)


def bcgsl_kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit,
                 ell=2, monitor=None, dtol=None):
    """BiCGStab(ℓ) (Sleijpen & Fokkema 1993; KSPBCGSL), right-preconditioned.

    Combines ℓ BiCG steps with an ℓ-th-degree minimum-residual polynomial
    update per outer iteration — more robust than BiCGStab (ℓ=1) on
    operators with complex spectra, where the degree-1 MR polynomial
    stagnates. ℓ is a static unroll (default 2, ``-ksp_bcgsl_ell``); runs on
    the correction system ``(A·M) y = r0`` with ``x = x0 + M(y)`` applied
    once at the end, so the in-loop residual is the true residual.
    """
    L = int(ell)
    if L < 1:
        raise ValueError(f"-ksp_bcgsl_ell must be >= 1, got {L}")
    bnorm, tol = _tol(pnorm, b, rtol, atol)
    op = lambda v: A(M(v))
    r0 = b - A(x0)
    rtilde = r0
    rnorm = pnorm(r0)
    dmax = _dmax(rnorm, dtol)
    hist0 = _mon0(monitor, rnorm, b.dtype)
    dt = b.dtype
    Rb = jnp.zeros((L + 1,) + b.shape, dt).at[0].set(r0)
    Ub = jnp.zeros_like(Rb)

    def safe(x):
        return jnp.where(x == 0, jnp.asarray(1.0, dt), x)

    def cond(st):
        return ((st["rn"] > tol) & (st["rn"] < dmax) & (st["k"] < maxit)
                & ~st["brk"])

    def body(st):
        k, y, R, U = st["k"], st["y"], st["R"], st["U"]
        rho0, alpha, omega, brk = (st["rho0"], st["alpha"], st["omega"],
                                   st["brk"])
        rho0 = -omega * rho0
        # ---- BiCG part (static unroll over j) ----
        for j in range(L):
            rho1 = pdot(R[j], rtilde)
            brk = brk | (rho0 == 0)
            beta = alpha * rho1 / safe(rho0)
            rho0 = rho1
            for i in range(j + 1):
                U = U.at[i].set(R[i] - beta * U[i])
            U = U.at[j + 1].set(op(U[j]))
            gam = pdot(U[j + 1], rtilde)
            brk = brk | (gam == 0)
            alpha = rho0 / safe(gam)
            for i in range(j + 1):
                R = R.at[i].set(R[i] - alpha * U[i + 1])
            R = R.at[j + 1].set(op(R[j]))
            y = y + alpha * U[0]
        # ---- MR part: min ||R[0] - [R1..RL] g|| via modified Gram-Schmidt
        tau = [[jnp.asarray(0.0, dt)] * (L + 1) for _ in range(L + 1)]
        sigma = [jnp.asarray(0.0, dt)] * (L + 1)
        gamma_p = [jnp.asarray(0.0, dt)] * (L + 1)
        for j in range(1, L + 1):
            for i in range(1, j):
                tau[i][j] = pdot(R[j], R[i]) / safe(sigma[i])
                R = R.at[j].set(R[j] - tau[i][j] * R[i])
            sigma[j] = pdot(R[j], R[j])
            brk = brk | (sigma[j] == 0)
            gamma_p[j] = pdot(R[0], R[j]) / safe(sigma[j])
        gamma = [jnp.asarray(0.0, dt)] * (L + 1)
        gamma_pp = [jnp.asarray(0.0, dt)] * (L + 1)
        gamma[L] = gamma_p[L]
        omega = gamma[L]
        brk = brk | (omega == 0)
        for j in range(L - 1, 0, -1):
            gamma[j] = gamma_p[j] - sum(
                (tau[j][i] * gamma[i] for i in range(j + 1, L + 1)),
                jnp.asarray(0.0, dt))
        for j in range(1, L):
            gamma_pp[j] = gamma[j + 1] + sum(
                (tau[j][i] * gamma[i + 1] for i in range(j + 1, L)),
                jnp.asarray(0.0, dt))
        # ---- update ----
        y = y + gamma[1] * R[0]
        R = R.at[0].set(R[0] - gamma_p[L] * R[L])
        U = U.at[0].set(U[0] - gamma[L] * U[L])
        for j in range(1, L):
            U = U.at[0].set(U[0] - gamma[j] * U[j])
            y = y + gamma_pp[j] * R[j]
            R = R.at[0].set(R[0] - gamma_p[j] * R[j])
        # freeze the iterate on breakdown (brk was False at loop entry; the
        # safe()-substituted updates after the flag are garbage) — siblings
        # do the same via alpha = where(brk, 0, ...)
        y = jnp.where(brk, st["y"], y)
        rn = jnp.where(brk, st["rn"], pnorm(R[0]))
        hist = st["hist"]
        if monitor is not None:
            hist = monitor(hist, k + L, rn)
        return dict(k=k + L, y=y, R=R, U=U, rho0=rho0, alpha=alpha,
                    omega=omega, rn=rn, brk=brk, hist=hist)

    st0 = dict(k=jnp.int32(0), y=jnp.zeros_like(b), R=Rb, U=Ub,
               rho0=jnp.asarray(1.0, dt), alpha=jnp.asarray(0.0, dt),
               omega=jnp.asarray(1.0, dt), rn=rnorm, brk=rnorm <= -1.0,
               hist=hist0)
    st = lax.while_loop(cond, body, st0)
    x = x0 + M(st["y"])
    rn_true = pnorm(b - A(x))
    return (x, st["k"], rn_true,
            _reason(st["rn"], tol, atol, st["k"], maxit, st["brk"], dmax),
            st["hist"])


KSP_KERNELS = {
    "cg": cg_kernel,
    "pipecg": pipecg_kernel,
    "sstep": sstep_kernel,
    "bcgs": bcgs_kernel,
    "gmres": gmres_kernel,
    "fgmres": fgmres_kernel,
    "cgs": cgs_kernel,
    "tfqmr": tfqmr_kernel,
    "cr": cr_kernel,
    "lsqr": lsqr_kernel,
    "minres": minres_kernel,
    "chebyshev": chebyshev_kernel,
    "preonly": preonly_kernel,
    "richardson": richardson_kernel,
    "bicg": bicg_kernel,
    "gcr": gcr_kernel,
    "cgne": cgne_kernel,
    "symmlq": symmlq_kernel,
    "fcg": fcg_kernel,
    "lgmres": lgmres_kernel,
    "bcgsl": bcgsl_kernel,
    # PETSc's fbcgs: the bcgs kernel here is already right-preconditioned
    # (flexible by construction), so it shares the kernel; fbcgsr is the
    # distinct merged-reduction recurrence
    "fbcgs": bcgs_kernel,
    "fbcgsr": fbcgsr_kernel,
}

# kernels needing the transpose product A^T v (operator.local_spmv_t)
_NEEDS_TRANSPOSE = ("lsqr", "bicg", "cgne")

# kernels accepting KSP_NORM_NATURAL — the single source both this module's
# dispatch and KSP.set_norm_type validation read (cg/fcg: sqrt <r, M r>;
# cr: sqrt <r̃, A r̃> of the preconditioned residual — the scalar its own
# recurrence carries)
NATURAL_TYPES = ("cg", "fcg", "cr")


# ---------------------------------------------------------------------------
# program factory: wrap a kernel body in shard_map + jit
# ---------------------------------------------------------------------------

_PROGRAM_CACHE: dict = {}


@_functools.lru_cache(maxsize=1)
def donation_supported() -> bool:
    """Whether the active backend actually ALIASES donated buffers.

    Solve programs donate the initial-iterate argument (the output x has
    identical shape/sharding, so XLA reuses the buffer in place — every
    repeat solve on a session then runs at ZERO extra HBM allocations,
    the serving hot-path requirement). Backends that cannot alias ignore
    the donation with a per-call UserWarning; this one tiny probe decides
    once per process so such backends never pay the warning spam and the
    cache key stays honest about what was compiled.
    """
    import warnings
    probe = jax.jit(lambda v: v + 1, donate_argnums=(0,))
    x = jnp.zeros((8,), jnp.float32)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        probe(x)
    return bool(getattr(x, "is_deleted", lambda: False)())


def _consumed_zeros(x0):
    """A zero initial iterate that still CONSUMES the ``x0`` argument.

    Donated zero-guess programs cannot use ``jnp.zeros_like``: the x0
    parameter would be dead in the jaxpr, jit would PRUNE it, and the
    donated buffer could never alias the output (the zero-allocation
    contract silently evaporates — measured: no warning is emitted).
    ``nan_to_num`` first makes ``v * 0 == 0`` exact for ANY buffer
    content — a donated buffer may carry a previous solve's NaN/Inf
    iterate, and ``NaN * 0`` is NaN. Two elementwise passes over one
    vector, once per solve."""
    return jnp.nan_to_num(x0, nan=0.0, posinf=0.0, neginf=0.0) * 0


def _local_dot(platform: str):
    """The shard-local inner product ``vdot(u, v)``. XLA:TPU lowers an
    fp64 ``dot_general`` by splitting each operand into float32 pieces
    (eight times the operand, a loop of passes over it); on TPU a real
    fp64 dot is an elementwise product and a sum instead, one fused pass."""
    if platform != "tpu":
        return jnp.vdot

    def dot(u, v):
        if u.dtype == jnp.float64 and v.dtype == jnp.float64:
            return jnp.sum(u * v)
        return jnp.vdot(u, v)
    return dot


# kernels supporting masked multi-step unrolling per while_loop iteration
_UNROLLABLE = ("cg",)

# Every KSP type is complex-capable (the PETSc complex-build contract):
# the conjugating pdot, conjugating basis projections, complex-capable
# Givens rotations, adjoint (A^H/M^H) transpose wiring for bicg/cgne/lsqr,
# real-typed norm carries in the fused-identity kernels
# (pipecg/fbcgsr/tfqmr), and real Lanczos scalars for the Hermitian
# three-term kernels (minres/symmlq).


def build_ksp_program(comm: DeviceComm, ksp_type: str, pc, operator,
                      restart: int = 30, monitored: bool = False,
                      zero_guess: bool = False, nullspace_dim: int = 0,
                      aug: int = 2, ell: int = 2, unroll: int = 1,
                      natural: bool = False, hist_cap: int = 0,
                      live: bool = False, true_res: bool = False,
                      abft: bool = False, abft_pc: bool = False,
                      rr: bool = False, donate: bool = False,
                      sstep_s: int = 4):
    """Build (or fetch cached) the jitted SPMD solve program.

    Signature of the returned callable::

        x, iters, rnorm, reason, hist = prog(op_arrays, pc_arrays, b, x0,
                                             rtol, atol, dtol, maxit)

    With ``true_res=True`` the program appends an epilogue after the
    solver loop computing the TRUE residual norm ``||b - A x||`` and
    ``||b||`` on device (one extra SpMV + two psum reductions, fused into
    the same XLA program) and returns them as two extra outputs::

        x, iters, rnorm, reason, hist, true_rnorm, bnorm = prog(...)

    This is what makes ``-ksp_true_residual_check``'s honest case FREE of
    extra dispatches: the gate reads the epilogue scalars from the same
    batched fetch instead of re-dispatching a mult + norm (each a
    program launch and a host round trip).

    ``hist`` is the in-program residual history: a (-1)-initialized
    (hist_cap,) buffer whose slot k holds the iteration-k monitored norm
    (zero-size when ``monitored=False``); -1 is the never-written sentinel
    because norms are nonnegative while NaN (a blown-up residual) must be
    recordable (see _HistMonitor). The caller fetches it once after the
    solve and replays the ``hist != -1`` entries to user monitors — no host
    callbacks exist in the program, so monitoring works on every mesh
    and costs no in-loop host round trips.

    With ``nullspace_dim > 0`` an extra leading argument carries the
    row-sharded (k, n_pad) orthonormal null-space basis::

        x, ... = prog(op_arrays, pc_arrays, ns_basis, b, x0, rtol, atol,
                      dtol, maxit)

    and the program removes the null-space component from the RHS, the
    initial guess, and every operator/preconditioner output (PETSc's
    MatNullSpace semantics for compatible singular systems) — one fused
    ``psum`` dot per basis vector, inside the same XLA program.

    ``operator`` is anything implementing the linear-operator protocol (see
    core.mat.Mat and models.stencil): ``shape``, ``dtype``,
    ``device_arrays()``, ``local_spmv(comm)``, ``op_specs(axis)`` and
    ``program_key()``.

    With the silent-corruption guard on (``abft``/``rr`` — CG only), the
    program grows extra leading checksum-vector arguments and trailing
    guard scalars, plus three extra outputs::

        x, iters, rnorm, reason, hist, det, rrc, xv [, true_rnorm, bnorm]
            = prog(op_arrays, pc_arrays, [cs,] [csM,] b, x0,
                   rtol, atol, dtol, maxit, abft_tol, rr_n)

    ``det`` is the first in-program detector that fired
    (:data:`SDC_DETECTOR_NAMES`; 0 = clean), ``rrc`` the residual
    replacements performed, ``xv`` the last VERIFIED iterate the caller
    rolls back to on detection. See :func:`cg_kernel_guarded`.

    ``donate=True`` donates the ``x0`` argument into the program
    (``jax.jit(..., donate_argnums=...)``): the output iterate aliases
    the input buffer, so a session issuing repeat solves (KSP.solve's
    hot path, the serving dispatch loop) performs ZERO extra device
    allocations per solve. The caller must treat its ``x0`` buffer as
    CONSUMED by the call (KSP.solve rebinds ``x.data`` to the program's
    output). Silently off on backends that cannot alias
    (:func:`donation_supported`).

    The program is served through the export cache (utils/aot.wrap,
    kind ``ksp``): a fresh process loads its StableHLO instead of
    tracing and lowering it, and the returned :class:`aot.Program` says
    which it did (``aot``). Not for a key that holds a fault plan's
    nonce, a live monitor or a shell callback's uid; those, and every
    program under ``TPU_SOLVE_AOT=0``, are the plain jit.
    """
    axis = comm.axis
    n = operator.shape[0]
    dtype = operator.dtype
    # the PRECISION PLAN: storage = the operator's dtype (what the
    # gathers/halos/AXPYs move), reduce = the accumulation channel
    # (utils.dtypes.reduce_dtype — fp32 under bf16 storage, identity
    # otherwise). Mixed plans are assembled by the CG loop-body builder
    # (cg_plans), so only the plan-built family (+ the loop-free
    # preonly/richardson bodies, whose carries stay dtype-consistent)
    # accepts sub-f32 storage.
    prec = _plans.precision_plan(dtype)
    mixed = prec.mixed
    if mixed and ksp_type not in ("cg", "pipecg", "sstep", "preonly",
                                  "richardson"):
        raise ValueError(
            f"sub-f32 storage ({np.dtype(dtype)}) solves are assembled by "
            f"the mixed-precision CG plans; KSP {ksp_type!r} has no "
            "precision-plan body — use cg/pipecg/sstep (typically under "
            "RefinedKSP fp64 refinement), or f32 storage")
    rdt = prec.reduce
    _up = prec.up       # the ONE lift-to-reduce-channel definition
    guard_k = bool(abft or rr)
    abft_k = bool(abft)
    abft_pc_k = bool(abft and abft_pc)
    if guard_k:
        if ksp_type not in GUARDED_TYPES:
            raise ValueError(
                f"the silent-corruption guard (-ksp_abft / "
                f"-ksp_residual_replacement) supports KSP "
                f"{sorted(GUARDED_TYPES)}; {ksp_type!r} has no guarded "
                "kernel — disable the guard or use cg")
        if nullspace_dim:
            raise ValueError(
                "the silent-corruption guard does not compose with a "
                "null-space projection (the projected operator's column "
                "checksum differs from the assembled one); disable "
                "-ksp_abft/-ksp_residual_replacement for singular solves")
        if natural:
            raise ValueError(
                "the silent-corruption guard monitors the unpreconditioned "
                "residual norm; it does not compose with "
                "-ksp_norm_type natural")
    # normalize knobs a solver type doesn't consume, so changing e.g.
    # bcgsl_ell never recompiles an unrelated CG program
    restart_k = restart if ksp_type in ("gmres", "fgmres", "gcr", "fcg",
                                        "lgmres") else 0
    aug_k = aug if ksp_type == "lgmres" else 0
    ell_k = ell if ksp_type == "bcgsl" else 0
    # s-step block size: part of the traced body (the basis build and the
    # coordinate recurrences unroll statically over s), so it keys the
    # program; normalized to 0 for every other type
    sstep_k = max(1, int(sstep_s)) if ksp_type == "sstep" else 0
    # unrolling trades wasted masked steps for fewer loop dispatches; with a
    # monitor attached every sub-step would re-fire the callback, so
    # monitored programs stay at 1
    unroll_k = (max(1, int(unroll))
                if ksp_type in _UNROLLABLE and not monitored
                and not guard_k else 1)
    natural_k = bool(natural) and ksp_type in NATURAL_TYPES
    cap_k = int(hist_cap) if monitored else 0
    live_k = bool(live) and monitored
    true_res_k = bool(true_res)
    # fault-injection isolation: _faults.trace_key() is None with no plan
    # armed (keys identical to a fault-free build, full reuse); with a plan
    # armed it is a fresh nonce, so a program traced under injection (e.g.
    # a corrupted comm.psum baked into the jaxpr) is never cached into —
    # or served from — the fault-free program set.
    donate_k = bool(donate) and donation_supported()
    trace_nonce = _faults.trace_key()
    aot_on = aot.aot_enabled()
    # fp64 BCGS on one TPU chip with bjacobi's ILU(0) kernel runs as
    # double-f32 Pallas sweeps (solvers/bcgs_dd.py): its group layout,
    # from the PC's stack shape, which the keys below do not otherwise
    # hold. The projection, the fault points and the live monitor's
    # callbacks live in the XLA loop. Imported here, where that PC has
    # loaded Pallas already: no other build pays for the import
    dd = None
    if (pc.kind == "bjacobi_ilu0" and not nullspace_dim and not live_k
            and trace_nonce is None):
        from . import bcgs_dd
        dd = bcgs_dd.layout(comm, ksp_type, pc, operator)
    key = (comm.mesh, axis, ksp_type, pc.program_key(), n, prec.key(),
           restart_k, monitored, zero_guess, operator.program_key(),
           nullspace_dim, aug_k, ell_k, unroll_k, natural_k, cap_k, live_k,
           true_res_k, abft_k, abft_pc_k, bool(rr), donate_k, sstep_k,
           trace_nonce, aot_on, dd)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    kernel = KSP_KERNELS[ksp_type]
    pc_apply_t = None
    if ksp_type == "bicg":
        # BiCG's shadow recurrence preconditions with Mᵀ — PETSc's
        # PCApplyTranspose slot (PC.local_apply_transpose here)
        pc_apply_t = pc.local_apply_transpose(comm, n)
        if pc_apply_t is None:
            raise ValueError(
                f"KSP 'bicg' needs a preconditioner with a transpose apply "
                f"(PCApplyTranspose); pc {pc.get_type()!r} provides none — "
                "supported: none/jacobi, the block kinds (bjacobi/sor/ssor/"
                "ilu/icc), lu/cholesky (dense mode; the large-n tridiagonal "
                "cyclic-reduction mode has no transpose), composite-additive "
                "of those, and shell with set_shell_apply_transpose; or use "
                "bcgs/gmres/gcr for general preconditioning")
    # CG fast path: matrix-free stencil operators with a uniform diagonal
    # and PC none/jacobi get the fused matvec+dot kernel and the scalar
    # Jacobi identities; PC mg composes the slab V-cycle 3D-natively
    # (see cg_stencil_kernel). Dispatch is part of the cache key via
    # pc.program_key() + operator.program_key().
    stencil_cg = (ksp_type == "cg" and nullspace_dim == 0
                  and unroll_k == 1 and not natural_k
                  # the fused Pallas partial sums u*y without a conjugate and
                  # carries a real-typed rr — real operators only
                  and not is_complex(dtype)
                  and pc.get_type() in ("none", "jacobi", "mg")
                  # the guarded stencil kernel keeps the scalar-Jacobi
                  # identities only; guard+mg routes through the general
                  # kernel (pc.local_apply serves the V-cycle there)
                  and not (guard_k and pc.get_type() == "mg")
                  and hasattr(operator, "local_matvec_dot")
                  and hasattr(operator, "grid3d")
                  and getattr(operator, "uniform_diagonal", None) is not None
                  # a jacobi PC built from a SEPARATE preconditioning matrix
                  # (set_operators(A, P)) must not collapse to A's diagonal
                  and (pc.get_type() == "none"
                       or getattr(pc, "_mat", None) is operator))
    matvec_dot = operator.local_matvec_dot(comm) if stencil_cg else None
    pc_apply3 = (pc.local_apply_grid3d(comm)
                 if stencil_cg and pc.get_type() == "mg" else None)
    # pipelined-CG stencil fast path: grid-shaped carries + the 3D-native
    # apply (zero in-loop reshapes) with the scalar-Jacobi PC identity;
    # guard/complex/nullspace configurations route through the general
    # flat kernel (pipecg_kernel). Dispatch is part of the cache key via
    # pc.program_key() + operator.program_key().
    stencil_pipe = (ksp_type == "pipecg" and nullspace_dim == 0
                    and not guard_k and not is_complex(dtype)
                    and pc.get_type() in ("none", "jacobi")
                    and hasattr(operator, "local_apply_grid3")
                    and hasattr(operator, "grid3d")
                    and getattr(operator, "uniform_diagonal", None)
                    is not None
                    and (pc.get_type() == "none"
                         or getattr(pc, "_mat", None) is operator))
    apply3 = operator.local_apply_grid3(comm) if stencil_pipe else None

    pc_apply = pc.local_apply(comm, n)
    spmv_local = operator.local_spmv(comm)
    ldot = _local_dot(comm.platform)
    spmv_t_local = None
    if ksp_type in _NEEDS_TRANSPOSE:
        if not hasattr(operator, "local_spmv_t"):
            raise ValueError(
                f"KSP {ksp_type!r} needs the transpose product; operator "
                f"{type(operator).__name__} provides no local_spmv_t")
        spmv_t_local = operator.local_spmv_t(comm)
    op_specs = operator.op_specs(axis)

    # functional in-program recorder (no host callbacks — see _HistMonitor);
    # callback-capable backends get the live-streaming variant
    mon_cls = _LiveMonitor if live_k else _HistMonitor
    # the history buffer records REDUCE-channel norms (bf16 slots would
    # quantize the monitored convergence curve to 8 mantissa bits)
    monitor = (mon_cls(rdt if mixed else dtype,
                       cap_k or hist_capacity(10000, restart))
               if monitored else None)

    def make_body(project):
        def body(op_arrays, pc_arrays, b, x0, rtol, atol, dtol, maxit,
                 guard_args=None):
            if zero_guess:
                x0 = _consumed_zeros(x0) if donate_k else jnp.zeros_like(b)
            b, x0 = project(b), project(x0)
            # the spmv.result / pc.apply SILENT fault points apply at
            # trace time (resilience/abft.py): the solver-loop operator
            # and PC applies are injectable, the true-residual epilogue
            # (_true_res_tail) and the guard's replacement verifier stay
            # on the raw closures/plain psums — a corrupted verifier
            # would lie about recovery
            A = lambda v: project(_abft.apply_silent_fault(
                "spmv.result", spmv_local(op_arrays, v)))
            M = lambda r: project(_abft.apply_silent_fault(
                "pc.apply", pc_apply(pc_arrays, r)))
            # vdot conjugates its first argument — the complex-correct inner
            # product; norms take the real part (vdot(u,u) carries a ~0
            # imaginary component for complex dtypes) so every kernel's
            # convergence scalar stays real-typed. Under a mixed plan the
            # operands are lifted into the REDUCE dtype first (_up is the
            # identity otherwise), so bf16 storage never accumulates a
            # dot product in bf16.
            pdot = lambda u, v: _psum(ldot(_up(u), _up(v)), axis)
            pnorm = lambda u: jnp.sqrt(jnp.real(_psum(ldot(_up(u), _up(u)),
                                                      axis)))
            kw = {"monitor": monitor} if monitor is not None else {}
            kw["dtol"] = dtol
            if natural_k:
                kw["natural"] = True
            if mixed and ksp_type in ("cg", "pipecg", "sstep"):
                # only the plan-built family takes the plan object; the
                # loop-free preonly/richardson bodies need no casts
                kw["prec"] = prec
            # the dtype every stacked-psum phase accumulates in — the
            # plan's reduce channel (== the operator scalar for uniform
            # plans, so existing programs are unchanged)
            stack_dt = rdt

            def _stack_psum(parts):
                # ONE fused (possibly faulted) psum for a whole phase's
                # scalars — the pipecg/fbcgsr discipline the ABFT
                # partials ride on (zero extra collectives)
                return _psum(jnp.stack([jnp.asarray(q, stack_dt)
                                        for q in parts]), axis)

            eps = _abft.checksum_tolerance_dtype(dtype)

            if dd is not None:
                return bcgs_dd.solve(
                    *dd, op_arrays, pc_arrays, b, x0, rtol, atol, maxit,
                    offsets=operator.dia_offsets, zero_guess=zero_guess,
                    interpret=comm.platform != "tpu", **kw)

            if stencil_cg:
                idt = rdt if mixed else b.dtype
                inv_diag = (jnp.asarray(1.0, idt) if pc.get_type() == "none"
                            else jnp.asarray(1.0 / operator.uniform_diagonal,
                                             idt))
                # 3D-carry variant: the stencil path is real-dtype, so the
                # reductions are plain sums (see cg_stencil_kernel docstring
                # for why the grid shape is kept through the loop); _up
                # lifts bf16 operands into the f32 reduce channel
                pdot3 = lambda u, v: _psum(jnp.sum(_up(u) * _up(v)), axis)
                pnorm3 = lambda u: jnp.sqrt(_psum(jnp.sum(_up(u) * _up(u)),
                                                  axis))

                def Adot(v):
                    y, d = matvec_dot(op_arrays, v)
                    return _abft.apply_silent_fault("spmv.result", y), d

                if guard_args is not None:
                    cs_l, _csM_l, abft_tol, rr_n = guard_args
                    cs3 = (cs_l.reshape(operator.grid3d)
                           if cs_l is not None else None)
                    thr = lambda scale: abft_tol * eps * scale

                    if cs3 is not None:
                        def init3(b3, r3, x3):
                            b3u, r3u = _up(b3), _up(r3)
                            cx = _up(cs3) * _up(x3)
                            s = _stack_psum([
                                jnp.sum(b3u * b3u), jnp.sum(r3u * r3u),
                                jnp.sum(r3u), jnp.sum(b3u), jnp.sum(cx),
                                jnp.sum(jnp.abs(r3u)),
                                jnp.sum(jnp.abs(b3u)),
                                jnp.sum(jnp.abs(cx))])
                            bad = (jnp.abs(s[2] - s[3] + s[4])
                                   > thr(s[5] + s[6] + s[7]))
                            return (jnp.sqrt(jnp.maximum(s[0], 0.0)),
                                    jnp.sqrt(jnp.maximum(s[1], 0.0)), bad)

                        def p2_stencil(r3, p3, Ap3):
                            r3u, Apu = _up(r3), _up(Ap3)
                            cp = _up(cs3) * _up(p3)
                            s = _stack_psum([
                                jnp.sum(r3u * r3u), jnp.sum(Apu),
                                jnp.sum(cp), jnp.sum(jnp.abs(Apu)),
                                jnp.sum(jnp.abs(cp))])
                            bad = jnp.abs(s[1] - s[2]) > thr(s[3] + s[4])
                            return jnp.maximum(s[0], 0.0), bad
                    else:
                        def init3(b3, r3, x3):
                            return pnorm3(b3), pnorm3(r3), False

                        def p2_stencil(r3, p3, Ap3):
                            return jnp.maximum(pdot3(r3, r3), 0.0), False

                    g3 = _types.SimpleNamespace(
                        init=init3, p2_stencil=p2_stencil,
                        vnorm2=lambda rt: lax.psum(
                            jnp.sum(_up(rt) * _up(rt)), axis),
                        rr_n=rr_n, eps=eps)
                    return cg_stencil_kernel_guarded(
                        Adot, inv_diag, pdot3, pnorm3, b, x0, rtol, atol,
                        maxit, g3, grid3d=operator.grid3d, **kw)

                if pc_apply3 is not None:
                    kw["M3"] = lambda r: _abft.apply_silent_fault(
                        "pc.apply", pc_apply3(pc_arrays, r))
                return cg_stencil_kernel(
                    Adot, inv_diag,
                    pdot3, pnorm3, b, x0, rtol, atol, maxit,
                    grid3d=operator.grid3d, **kw)

            if stencil_pipe:
                idt = rdt if mixed else b.dtype
                inv_diag = (jnp.asarray(1.0, idt)
                            if pc.get_type() == "none"
                            else jnp.asarray(1.0 / operator.uniform_diagonal,
                                             idt))
                A3 = lambda u: _abft.apply_silent_fault(
                    "spmv.result", apply3(op_arrays, u))
                pnorm3 = lambda v: jnp.sqrt(_psum(jnp.sum(_up(v) * _up(v)),
                                                  axis))

                def fused3(r_, u_, w_):
                    ru, uu, wu = _up(r_), _up(u_), _up(w_)
                    s = _plans.fuse_psum(
                        [jnp.sum(ru * uu), jnp.sum(wu * uu),
                         jnp.sum(ru * ru)], _psum, axis, stack_dt)
                    return s[0], s[1], s[2]

                return pipecg_stencil_kernel(
                    A3, inv_diag, pnorm3, fused3, b, x0, rtol, atol,
                    maxit, grid3d=operator.grid3d, **kw)

            if guard_args is not None:
                cs_l, csM_l, abft_tol, rr_n = guard_args[:4]
                # the guard's partial sums run in the REDUCE channel (_up
                # lifts bf16 operands); the detection threshold stays
                # scaled to the STORAGE epsilon (eps_dtype)
                flavor = dict(dot=lambda u, v: jnp.vdot(_up(u), _up(v)),
                              tsum=lambda u: jnp.sum(_up(u)),
                              tasum=lambda u: jnp.sum(jnp.abs(_up(u))),
                              cmul=lambda c, v: _up(c) * _up(v),
                              no_bad=lambda v: False,
                              pdot=pdot, pnorm=pnorm,
                              eps_dtype=dtype if mixed else None)
                if ksp_type == "pipecg":
                    gp = _make_pipe_guard(stack_dt, axis, cs_l, csM_l,
                                          abft_tol, rr_n, **flavor)
                    return pipecg_kernel_guarded(A, M, pdot, pnorm, b, x0,
                                                 rtol, atol, maxit, gp,
                                                 **kw)
                if ksp_type == "sstep":
                    gs = _make_sstep_guard(stack_dt, axis, cs_l, csM_l,
                                           abft_tol, rr_n, **flavor)
                    return sstep_kernel_guarded(
                        A, M, pdot, pnorm, b, x0, rtol, atol, maxit, gs,
                        s=sstep_k,
                        greduce=lambda parts: _plans.fuse_gram_psum(
                            parts, _psum, axis, stack_dt),
                        max_repl=guard_args[4], **kw)
                g = _make_guard(stack_dt, axis, cs_l, csM_l, abft_tol, rr_n,
                                **flavor)
                return cg_kernel_guarded(A, M, pdot, pnorm, b, x0, rtol,
                                         atol, maxit, g, **kw)
            if unroll_k > 1:
                kw["unroll"] = unroll_k
            if ksp_type in ("gmres", "fgmres", "gcr", "fcg", "lgmres"):
                kw["restart"] = restart
                # conj for complex-correct basis projections (identity on
                # real dtypes, where XLA elides it)
                kw["pmatdot"] = lambda Vb, w: _psum(jnp.conj(Vb) @ w,
                                                    axis)
                if ksp_type == "lgmres":
                    kw["aug"] = aug
            elif ksp_type == "bcgsl":
                kw["ell"] = ell
            elif ksp_type == "preonly":
                # refinement is for direct factorizations only (PETSc's
                # KSPPREONLY is literally one PC apply); pc.program_key()
                # is in the cache key, so this bool can't go stale
                kw["refine"] = pc.kind in ("lu", "crtri", "crband")
            elif ksp_type in ("pipecg", "fbcgsr"):
                # the whole point: all per-iteration dots in ONE fused
                # psum — routed through the cg_plans.fuse_psum seam so
                # the 1-reduce-site gate's injected-regression test can
                # split it and prove the assert has teeth
                kw["preduce"] = lambda *parts: _plans.fuse_psum(
                    list(parts), _psum, axis, stack_dt)
            elif ksp_type == "sstep":
                # the s-block's ONE collective: Gram matrix + guard
                # partials through the cg_plans.fuse_gram_psum seam (the
                # 1-site-per-s-block gate's injected-regression splits it)
                kw["s"] = sstep_k
                kw["greduce"] = lambda parts: _plans.fuse_gram_psum(
                    parts, _psum, axis, stack_dt)
            elif ksp_type in _NEEDS_TRANSPOSE:
                # the adjoint of the projected operator v -> P(Av) is
                # w -> A^T(Pw): project BEFORE the transpose product (P is
                # the null(A) projector; projecting after would be wrong for
                # unsymmetric A). project is the identity without a nullspace.
                if is_complex(dtype):
                    # complex scalars need the ADJOINT A^H, not A^T:
                    # cgne/lsqr's normal equations are A^H A (the plain-
                    # transpose product is not even Hermitian), and bicg's
                    # Hermitian-variant shadow sequence runs on A^H.
                    # A^H v = conj(A^T conj(v)).
                    kw["At"] = lambda v: jnp.conj(
                        spmv_t_local(op_arrays, jnp.conj(project(v))))
                else:
                    kw["At"] = lambda v: spmv_t_local(op_arrays, project(v))
                if ksp_type == "bicg":
                    # same adjoint rule for the preconditioner:
                    # (P M)^T = M^T P, and complex M^H = conj(M^T(conj ·))
                    if is_complex(dtype):
                        kw["Mt"] = lambda r: jnp.conj(
                            pc_apply_t(pc_arrays, jnp.conj(project(r))))
                    else:
                        kw["Mt"] = lambda r: pc_apply_t(pc_arrays,
                                                        project(r))
            return kernel(A, M, pdot, pnorm, b, x0, rtol, atol, maxit, **kw)
        return body

    def _true_res_tail(op_arrays, b, x):
        # epilogue: TRUE residual of the returned iterate against the RAW
        # rhs (matching the host-side oracle at reference test.py:148-149),
        # fused into the solve program — see the true_res docstring note;
        # the norms accumulate in the reduce channel (_up); named
        # ``true_residual`` in the HLO's op_name metadata
        with jax.named_scope("true_residual"):
            r = _up(b - spmv_local(op_arrays, x))
            bu = _up(b)
            return (jnp.sqrt(jnp.real(lax.psum(ldot(r, r), axis))),
                    jnp.sqrt(jnp.real(lax.psum(ldot(bu, bu), axis))))

    if nullspace_dim:
        def local_fn(op_arrays, pc_arrays, ns_q, b, x0, rtol, atol, dtol,
                     maxit):
            def project(v):
                # one psum either way; a mixed plan projects in the
                # reduce channel and stores back (identity casts elide)
                nq, vu = _up(ns_q), _up(v)
                out = vu - lax.psum(nq @ vu, axis) @ nq
                return out.astype(v.dtype) if mixed else out
            out = make_body(project)(op_arrays, pc_arrays, b, x0,
                                     rtol, atol, dtol, maxit)
            if true_res_k:
                out = out + _true_res_tail(op_arrays, b, out[0])
            return out

        in_specs = (op_specs, pc.in_specs(axis), P(None, axis),
                    P(axis), P(axis), P(), P(), P(), P())
        x0_idx = 4
    elif guard_k:
        # guard signature: leading checksum vectors (present per flag),
        # trailing runtime guard scalars (tolerance factor + replacement
        # interval — runtime, so tuning them never recompiles; sstep
        # appends its basis-restart budget -ksp_sstep_max_replacements)
        def local_fn(op_arrays, pc_arrays, *args):
            i = 0
            cs = csM = None
            if abft_k:
                cs = args[i]
                i += 1
            if abft_pc_k:
                csM = args[i]
                i += 1
            if ksp_type == "sstep":
                (b, x0, rtol, atol, dtol, maxit, abft_tol, rr_n,
                 max_repl) = args[i:]
                ga = (cs, csM, abft_tol, rr_n, max_repl)
            else:
                b, x0, rtol, atol, dtol, maxit, abft_tol, rr_n = args[i:]
                ga = (cs, csM, abft_tol, rr_n)
            out = make_body(lambda v: v)(
                op_arrays, pc_arrays, b, x0, rtol, atol, dtol, maxit,
                guard_args=ga)
            if true_res_k:
                out = out + _true_res_tail(op_arrays, b, out[0])
            return out

        in_specs = (op_specs, pc.in_specs(axis)) \
            + tuple(P(axis) for _ in range(abft_k + abft_pc_k)) \
            + (P(axis), P(axis), P(), P(), P(), P(), P(), P()) \
            + ((P(),) if ksp_type == "sstep" else ())
        x0_idx = 3 + abft_k + abft_pc_k
    else:
        def local_fn(op_arrays, pc_arrays, b, x0, rtol, atol, dtol, maxit):
            out = make_body(lambda v: v)(op_arrays, pc_arrays, b, x0,
                                         rtol, atol, dtol, maxit)
            if true_res_k:
                out = out + _true_res_tail(op_arrays, b, out[0])
            return out

        in_specs = (op_specs, pc.in_specs(axis),
                    P(axis), P(axis), P(), P(), P(), P())
        x0_idx = 3
    # the history buffer rides as a 5th (replicated) output — every device
    # writes identical psum'd norms into it; with true_res the epilogue's
    # two scalars follow as replicated trailing outputs; the guard appends
    # (det, rrc, xv) before them
    out_specs = (P(axis), P(), P(), P(), P())
    if guard_k:
        out_specs = out_specs + (P(), P(), P(axis))
    if true_res_k:
        out_specs = out_specs + (P(), P())
    dn = (x0_idx,) if donate_k else ()
    prog = jax.jit(comm.shard_map(local_fn, in_specs, out_specs),
                   donate_argnums=dn)
    # the export cache (utils/aot): a later process loads this program's
    # StableHLO instead of tracing and lowering it, unless the key holds
    # something valid in this process only: a fault plan's nonce, a live
    # monitor's host callbacks (a shell callback's uid: aot.wrap)
    if aot_on and trace_nonce is None and not live_k:
        prog = aot.wrap("ksp", comm,
                        key[1:] + (aot.operand_shapes(
                            operator.device_arrays(), pc.device_arrays()),),
                        prog, donate_argnums=dn)
    # what runs the iteration, for the ksp.setup span
    prog.loop = "xla" if dd is None else "dd_pallas"
    _PROGRAM_CACHE[key] = prog
    return prog


# ---------------------------------------------------------------------------
# batched multi-RHS solves: k independent CG recurrences in ONE program
# ---------------------------------------------------------------------------

class _HistMonitorMany(_HistMonitor):
    """Per-column residual recorder for the batched kernels: a
    ``(cap, nrhs)`` buffer where slot ``(i, j)`` holds column j's
    iteration-i monitored norm. Frozen columns re-write their last slot
    with an unchanged value — harmless, and the replay (KSP.solve_many)
    walks each column independently."""

    def __init__(self, dtype, cap, nrhs):
        super().__init__(dtype, cap)
        self.nrhs = int(nrhs)

    def init(self):
        return jnp.full((self.cap, self.nrhs), -1.0, self.dtype)

    def __call__(self, hist, k, rn):
        return hist.at[k, jnp.arange(self.nrhs)].set(
            rn.astype(self.dtype), mode="drop")


def cg_kernel_many(A, M, pdotc, pnormc, pduo, B, X0, rtol, atol, maxit,
                   monitor=None, dtol=None, prec=None):
    """Batched preconditioned CG: ``nrhs`` INDEPENDENT recurrences in
    lockstep over an ``(lsize, nrhs)`` RHS block (KSPMatSolve's hot-loop
    analog).

    Per column the arithmetic is exactly :func:`cg_kernel` at unroll=1 —
    per-RHS results, iteration counts, and breakdown behavior match
    sequential solves — but one batched operator apply (ONE all_gather
    for the whole block) and one fused per-phase reduction serve all k
    columns: ``pdotc``/``pnormc`` reduce (nrhs,) vectors in a single
    psum, and ``pduo(R, Z) -> (<R,Z>, <R,R>)`` stacks both end-of-step
    dots into ONE collective, so the per-iteration collective COUNT is
    independent of k (2 reduction phases; bytes scale with k).

    Per-RHS masked convergence: a column whose residual meets its own
    ``max(rtol*||b_j||, atol)`` (or that breaks down / diverges) freezes
    — its state is carried unchanged via masked selects — while the loop
    runs until the last active column exits. Returns per-column
    ``(X, iters, rnorm, reason, hist)`` with shapes (nrhs,)-batched.
    """
    return _plans.classic_cg_loop(
        b=B, x0=X0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        A=A, M=M, pdot=pdotc, pnorm=pnormc, pduo=pduo,
        bp=_plans.ManyBatch("cols"), monitor=monitor, prec=prec)


def cg_stencil_kernel_many(Adot, inv_diag, pdotc3, B, X0, rtol, atol,
                           maxit, monitor=None, dtol=None, grid3d=None,
                           prec=None):
    """Batched twin of :func:`cg_stencil_kernel` for uniform-diagonal
    stencil operators: state lives in ``(nrhs,) + grid3d`` slabs, the
    SpMV + per-column ``<p_j, A p_j>`` partials run in one fused pass
    (``Adot`` — the multi-RHS Pallas kernel on TPU), the Jacobi apply
    collapses to the scalar ``inv_diag`` multiply, and
    ``rz_j = inv_diag * ||r_j||^2`` reuses the residual-norm reduction.
    Per-column masked convergence as in :func:`cg_kernel_many`; per-column
    arithmetic identical to the single-RHS fast path.
    """
    nrhs = B.shape[1]
    flat = B.shape
    B3 = B.T.reshape((nrhs,) + grid3d)
    X3 = X0.T.reshape((nrhs,) + grid3d)
    out = _plans.classic_cg_loop(
        b=B3, x0=X3, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        Adot=Adot, inv_diag=inv_diag, pdot=pdotc3,
        pnorm=lambda U: jnp.sqrt(pdotc3(U, U)),
        bp=_plans.ManyBatch("slabs"), monitor=monitor, prec=prec)
    X = out[0].reshape(nrhs, -1).T.reshape(flat)
    return (X,) + out[1:]


def cg_kernel_many_guarded(A, M, pdotc, pnormc, B, X0, rtol, atol, maxit,
                           g, monitor=None, dtol=None, prec=None):
    """Batched guarded CG: :func:`cg_kernel_many`'s masked lockstep
    recurrences with PER-COLUMN silent-corruption detection.

    Mask-aware guard semantics: the ABFT checksums, the NaN/monotonicity
    sentinels, and the drift gate all evaluate per column — a detected
    column freezes (its ``det`` code set, state preserved) while clean
    columns keep iterating; the periodic replacement recomputes the whole
    residual BLOCK in one batched apply and replaces/verifies only the
    still-active columns. All guard partials fold into the two existing
    stacked per-phase psums, so the per-iteration collective count stays
    independent of both nrhs and the guard.

    Returns ``(X, iters, rnorm, reason, hist, det, rrc, Xv)`` with
    ``det``/``rrc`` per-column ``(nrhs,)`` vectors and ``Xv`` the
    per-column last-verified iterate block.
    """
    return _plans.classic_cg_loop(
        b=B, x0=X0, rtol=rtol, atol=atol, maxit=maxit, dtol=dtol,
        A=A, M=M, pdot=pdotc, pnorm=pnormc, guard=g,
        bp=_plans.ManyBatch("cols"), monitor=monitor, prec=prec)


_PROGRAM_CACHE_MANY: dict = {}


def batched_pc_supported(pc) -> bool:
    """Whether this PC kind has a batched (trailing-RHS-axis) apply —
    the KSP.solve_many routing test (unsupported kinds fall back to
    per-column sequential solves)."""
    return pc.kind in ("none", "jacobi", "bjacobi", "bjacobi_ilu0", "lu")


def build_ksp_program_many(comm: DeviceComm, ksp_type: str, pc, operator,
                           nrhs: int, monitored: bool = False,
                           zero_guess: bool = False, hist_cap: int = 0,
                           abft: bool = False, abft_pc: bool = False,
                           rr: bool = False, true_res: bool = False,
                           donate: bool = False, sstep_s: int = 4):
    """Build (or fetch cached) the batched multi-RHS solve program.

    Signature of the returned callable::

        X, iters, rnorm, reason, hist = prog(op_arrays, pc_arrays, B, X0,
                                             rtol, atol, dtol, maxit)

    with ``B``/``X0``/``X`` row-sharded ``(n_pad, nrhs)`` blocks and
    ``iters``/``rnorm``/``reason`` per-column ``(nrhs,)`` vectors
    (``hist`` is ``(hist_cap, nrhs)`` when monitored, zero-size
    otherwise). Only CG is batched (the block-Krylov workhorse); other
    KSP types route through the sequential fallback in KSP.solve_many.

    ``true_res=True`` appends the batched true-residual epilogue — two
    extra per-column outputs ``(true_rnorm, bnorm)``, each ``(nrhs,)`` —
    the zero-extra-dispatch data the per-column ``-ksp_true_residual_check``
    gate reads. With the silent-corruption guard on (``abft``/``rr``) the
    program grows the checksum arguments/guard scalars and the
    ``(det, rrc, Xv)`` per-column outputs exactly like the single-RHS
    program (:func:`build_ksp_program`), with mask-aware per-column
    detection (:func:`cg_kernel_many_guarded`); the stencil fast path
    routes through the general batched kernel under the guard or the
    epilogue (both need the flat-block spmv).

    The jitted program is additionally AOT-export-cached
    (utils/aot.wrap) with ``nrhs`` in the key — a fresh process loads
    the StableHLO for its exact batch width instead of re-tracing —
    except while a fault plan with live trace-time faults is armed
    (a program traced under injection must never be persisted).
    """
    if ksp_type not in ("cg", "pipecg", "sstep"):
        raise ValueError(
            f"batched multi-RHS programs support KSP 'cg'/'pipecg'/"
            f"'sstep' (the block-CG plans); {ksp_type!r} solves route "
            "through the sequential fallback (KSP.solve_many)")
    axis = comm.axis
    n = operator.shape[0]
    dtype = operator.dtype
    sstep_k = max(1, int(sstep_s)) if ksp_type == "sstep" else 0
    # precision plan (see build_ksp_program): batched storage channel in
    # the operator dtype, reductions lifted into the reduce channel
    prec = _plans.precision_plan(dtype)
    mixed = prec.mixed
    rdt = prec.reduce
    _up = prec.up       # the ONE lift-to-reduce-channel definition
    stack_dt = rdt      # == dtype for uniform plans
    cap_k = int(hist_cap) if monitored else 0
    guard_k = bool(abft or rr)
    abft_k = bool(abft)
    abft_pc_k = bool(abft and abft_pc)
    true_res_k = bool(true_res)
    trace_nonce = _faults.trace_key()
    aot_on = aot.aot_enabled() and trace_nonce is None
    donate_k = bool(donate) and donation_supported()
    key = (comm.mesh, axis, ksp_type, pc.program_key(), n, prec.key(),
           int(nrhs), monitored, zero_guess, operator.program_key(),
           cap_k, abft_k, abft_pc_k, bool(rr), true_res_k, donate_k,
           sstep_k, trace_nonce, aot_on)
    cached = _PROGRAM_CACHE_MANY.get(key)
    if cached is not None:
        return cached

    pc_apply = pc.local_apply_many(comm, n)
    if pc_apply is None:
        raise ValueError(
            f"pc {pc.get_type()!r} has no batched apply "
            "(krylov.batched_pc_supported); KSP.solve_many falls back to "
            "sequential per-column solves for it")
    stencil_cg = (ksp_type == "cg"
                  and not is_complex(dtype)
                  and not guard_k and not true_res_k
                  and pc.get_type() in ("none", "jacobi")
                  and hasattr(operator, "local_matvec_dot_many")
                  and hasattr(operator, "grid3d")
                  and getattr(operator, "uniform_diagonal", None) is not None
                  and (pc.get_type() == "none"
                       or getattr(pc, "_mat", None) is operator))
    matvec_dot = operator.local_matvec_dot_many(comm) if stencil_cg else None
    spmv_many = None if stencil_cg else operator.local_spmv_many(comm)
    op_specs = operator.op_specs(axis)
    monitor = (_HistMonitorMany(rdt if mixed else dtype,
                                cap_k or hist_capacity(10000, 0),
                                nrhs) if monitored else None)

    def _tail_many(op_arrays, B, X):
        # batched true-residual epilogue (raw spmv + plain psum — the
        # verifier channel, exactly like the single-RHS _true_res_tail;
        # both per-column norm rows ride ONE stacked psum)
        with jax.named_scope("true_residual"):
            R = _up(B - spmv_many(op_arrays, X))
            Bu = _up(B)
            s = lax.psum(jnp.stack(
                [jnp.real(jnp.sum(jnp.conj(R) * R, axis=0)),
                 jnp.real(jnp.sum(jnp.conj(Bu) * Bu, axis=0))]), axis)
            return jnp.sqrt(s[0]), jnp.sqrt(s[1])

    def body(op_arrays, pc_arrays, B, X0, rtol, atol, dtol, maxit,
             guard_args=None):
        if zero_guess:
            X0 = _consumed_zeros(X0) if donate_k else jnp.zeros_like(B)
        cdot = lambda U, V: jnp.sum(jnp.conj(_up(U)) * _up(V), axis=0)
        pdotc = lambda U, V: _psum(cdot(U, V), axis)
        pnormc = lambda U: jnp.sqrt(jnp.real(_psum(cdot(U, U), axis)))

        def pduo(R, Z):
            # BOTH end-of-step dots of every column in ONE stacked psum —
            # the pipecg/fbcgsr fused-reduction discipline, batched
            s = _psum(jnp.stack([cdot(R, Z), cdot(R, R)]), axis)
            return s[0], s[1]

        kw = {"monitor": monitor} if monitor is not None else {}
        kw["dtol"] = dtol
        if mixed:
            kw["prec"] = prec
        if stencil_cg:
            idt = rdt if mixed else B.dtype
            inv_diag = (jnp.asarray(1.0, idt) if pc.get_type() == "none"
                        else jnp.asarray(1.0 / operator.uniform_diagonal,
                                         idt))
            pdotc3 = lambda U, V: _psum(jnp.sum(_up(U) * _up(V),
                                                axis=(1, 2, 3)),
                                        axis)

            def Adot3(U):
                Y, d = matvec_dot(op_arrays, U)
                return _abft.apply_silent_fault("spmv.result", Y), d

            return cg_stencil_kernel_many(
                Adot3, inv_diag, pdotc3,
                B, X0, rtol, atol, maxit, grid3d=operator.grid3d, **kw)
        A = lambda V: _abft.apply_silent_fault(
            "spmv.result", spmv_many(op_arrays, V))
        M = lambda R: _abft.apply_silent_fault(
            "pc.apply", pc_apply(pc_arrays, R))
        if guard_args is not None:
            cs_l, csM_l, abft_tol, rr_n = guard_args[:4]
            flavor = dict(
                dot=cdot, tsum=lambda U: jnp.sum(_up(U), axis=0),
                tasum=lambda U: jnp.sum(jnp.abs(_up(U)), axis=0),
                cmul=lambda c, V: _up(c)[:, None] * _up(V),
                no_bad=lambda V: jnp.zeros(V.shape[1], bool),
                pdot=pdotc, pnorm=pnormc,
                eps_dtype=dtype if mixed else None)
            if ksp_type == "pipecg":
                gp = _make_pipe_guard(stack_dt, axis, cs_l, csM_l,
                                      abft_tol, rr_n, **flavor)
                return pipecg_kernel_many_guarded(A, M, pdotc, pnormc, B,
                                                  X0, rtol, atol, maxit,
                                                  gp, **kw)
            if ksp_type == "sstep":
                gs = _make_sstep_guard(stack_dt, axis, cs_l, csM_l,
                                       abft_tol, rr_n, **flavor)
                return sstep_kernel_many_guarded(
                    A, M, pdotc, pnormc, B, X0, rtol, atol, maxit, gs,
                    s=sstep_k,
                    greduce=lambda parts: _plans.fuse_gram_psum(
                        parts, _psum, axis, stack_dt, batched=True),
                    max_repl=guard_args[4], **kw)
            g = _make_guard(stack_dt, axis, cs_l, csM_l, abft_tol, rr_n,
                            **flavor)
            return cg_kernel_many_guarded(A, M, pdotc, pnormc, B, X0,
                                          rtol, atol, maxit, g, **kw)
        if ksp_type == "pipecg":
            def fusedc(Rb, U, W):
                s = _plans.fuse_psum([cdot(Rb, U), cdot(W, U),
                                      cdot(Rb, Rb)], _psum, axis,
                                     stack_dt)
                return s[0], s[1], s[2]
            return pipecg_kernel_many(A, M, pdotc, pnormc, fusedc, B, X0,
                                      rtol, atol, maxit, **kw)
        if ksp_type == "sstep":
            return sstep_kernel_many(
                A, M, pdotc, pnormc, B, X0, rtol, atol, maxit,
                s=sstep_k,
                greduce=lambda parts: _plans.fuse_gram_psum(
                    parts, _psum, axis, stack_dt, batched=True), **kw)
        return cg_kernel_many(A, M, pdotc, pnormc, pduo, B, X0, rtol,
                              atol, maxit, **kw)

    if guard_k:
        def local_fn(op_arrays, pc_arrays, *args):
            i = 0
            cs = csM = None
            if abft_k:
                cs = args[i]
                i += 1
            if abft_pc_k:
                csM = args[i]
                i += 1
            if ksp_type == "sstep":
                (B, X0, rtol, atol, dtol, maxit, abft_tol, rr_n,
                 max_repl) = args[i:]
                ga = (cs, csM, abft_tol, rr_n, max_repl)
            else:
                B, X0, rtol, atol, dtol, maxit, abft_tol, rr_n = args[i:]
                ga = (cs, csM, abft_tol, rr_n)
            out = body(op_arrays, pc_arrays, B, X0, rtol, atol, dtol,
                       maxit, guard_args=ga)
            if true_res_k:
                out = out + _tail_many(op_arrays, B, out[0])
            return out

        in_specs = (op_specs, pc.in_specs(axis)) \
            + tuple(P(axis) for _ in range(abft_k + abft_pc_k)) \
            + (P(axis, None), P(axis, None), P(), P(), P(), P(), P(),
               P()) \
            + ((P(),) if ksp_type == "sstep" else ())
        x0_idx = 3 + abft_k + abft_pc_k
    else:
        def local_fn(op_arrays, pc_arrays, B, X0, rtol, atol, dtol, maxit):
            out = body(op_arrays, pc_arrays, B, X0, rtol, atol, dtol,
                       maxit)
            if true_res_k:
                out = out + _tail_many(op_arrays, B, out[0])
            return out

        in_specs = (op_specs, pc.in_specs(axis), P(axis, None),
                    P(axis, None), P(), P(), P(), P())
        x0_idx = 3
    out_specs = (P(axis, None), P(), P(), P(), P())
    if guard_k:
        out_specs = out_specs + (P(), P(), P(axis, None))
    if true_res_k:
        out_specs = out_specs + (P(), P())
    # the X0 block is donated on aliasing-capable backends: the program's
    # output X reuses the input buffer, so the serving dispatch loop's
    # repeat launches allocate nothing (KSP.solve_many always passes a
    # freshly placed X0 it never reads back)
    dn = (x0_idx,) if donate_k else ()
    prog = jax.jit(comm.shard_map(local_fn, in_specs, out_specs),
                   donate_argnums=dn)
    if aot_on:
        # key_parts: the full program identity minus the mesh (the wrap
        # appends its own mesh/jax-version/x64 fingerprint) — nrhs is in
        # there, so each batch width gets its own shape-specialized blob
        prog = aot.wrap("ksp_many", comm, key[1:], prog,
                        donate_argnums=dn)
    _PROGRAM_CACHE_MANY[key] = prog
    return prog
