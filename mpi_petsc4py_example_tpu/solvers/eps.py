"""EPS — eigensolver, TPU-native equivalent of SLEPc EPS (SURVEY.md N6).

Reference usage (``petsc_funcs.py:13-20``, ``test2.py:88-96``): ``EPS().create``,
``setOperators``, ``setProblemType(HEP)``, ``setFromOptions``, ``solve``,
``getConverged``, ``getEigenpair(i, vr, vi)``. SLEPc's default configuration —
**Krylov-Schur**, nev=1, largest magnitude [external] — is the semantic target,
and Krylov-Schur (thick-restart Arnoldi/Lanczos) is the default type here too.

Solver types (``set_type`` / ``-eps_type``):

* ``krylovschur`` — thick-restart Arnoldi (Krylov-Schur). The ncv-step
  factorization *continuation* is one jit-compiled ``shard_map`` program
  (SpMV + ``lax.psum`` CGS2 dots over the mesh); each restart compresses the
  basis to the k wanted Ritz/Schur vectors **on device** (one sharded matmul)
  and re-enters the same compiled program at step k. The small (ncv x ncv)
  projected eigenproblem is solved on host each restart — mirroring SLEPc's
  own dense-subproblem split.
* ``arnoldi``  — explicitly-restarted Arnoldi (restart vector = combination
  of wanted Ritz vectors).
* ``lanczos``  — Hermitian alias of the thick-restart path (full CGS2
  reorthogonalization makes the factorization a numerically-reliable Lanczos
  process).
* ``power``    — power iteration, chunked into a jitted program.
* ``subspace`` — subspace iteration; Hermitian problems run the WHOLE solve
  as one compiled program (device eigh Rayleigh-Ritz each iteration, O(1)
  sync points — _build_subspace_loop_program), mirrors of the fused
  Krylov-Schur loop; non-Hermitian keeps the host-projection loop.
* ``lobpcg``   — same fusion: the 3m×3m projected pencil is whitened and
  solved on device inside one while_loop program
  (_build_lobpcg_loop_program), host fetch only at extraction.
* ``lapack``   — SLEPc's EPSLAPACK: the FULL dense problem solved on host
  (eigh/eig/generalized eigh), every pair exact; the small-n oracle as a
  first-class type (round 5).

Spectral transformations (``ST``; ``-st_type sinvert -st_shift s``) and
generalized Hermitian problems ``A x = lambda B x`` are supported: the solver
runs on the transformed operator (solvers/st.py) and — for GHEP — performs all
orthogonalization in the B-inner product, then back-transforms the Ritz values.

Unlike the reference driver — which calls the collective ``getEigenpair``
under ``if rank == 0:`` (a latent deadlock, SURVEY.md §3.2) — eigenpair
extraction here is single-controller and host-replicated, so it is trivially
collective-safe.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from ..core.mat import Mat
from ..core.vec import Vec
from ..parallel.mesh import DeviceComm, as_comm
from ..resilience import faults as _faults
from ..telemetry import spans as _telemetry
from ..utils import aot as _aot
from ..utils.convergence import SolveResult
from ..utils.errors import wrap_device_errors
from ..utils.options import global_options
from ..utils.dtypes import host_dtype, is_complex
from ..utils.profiling import record_sync
from .st import ST

DEFAULT_TOL = 1e-8        # SLEPc's EPS default
DEFAULT_MAX_RESTARTS = 100

EPS_TYPES = ("lapack", "krylovschur", "arnoldi", "lanczos", "power", "subspace",
             "lobpcg", "gd")


class EPSProblemType:
    HEP = "hep"       # Hermitian
    NHEP = "nhep"     # non-Hermitian
    GHEP = "ghep"     # generalized Hermitian, B SPD


class EPSWhich:
    LARGEST_MAGNITUDE = "largest_magnitude"
    SMALLEST_MAGNITUDE = "smallest_magnitude"
    LARGEST_REAL = "largest_real"
    SMALLEST_REAL = "smallest_real"
    TARGET_MAGNITUDE = "target_magnitude"
    TARGET_REAL = "target_real"


class EPSType:
    KRYLOVSCHUR = "krylovschur"
    ARNOLDI = "arnoldi"
    LANCZOS = "lanczos"
    POWER = "power"
    SUBSPACE = "subspace"
    LOBPCG = "lobpcg"
    LAPACK = "lapack"
    GD = "gd"


_PROGRAM_CACHE: dict = {}


def _op_key(op):
    return (op.shape[0], str(op.dtype), op.program_key())


def _operand_shapes(op, inner=None):
    """The operand geometry part of an AOT blob key (utils/aot.
    operand_shapes): ``_op_key`` pins the logical operator but not, e.g.,
    the ELL width K an exported program is specialized to."""
    return _aot.operand_shapes(
        op.device_arrays(),
        inner.device_arrays() if inner is not None else ())


def _facto_steps(spmv, b_apply, axis, ncv):
    """The shared CGS2 Arnoldi/Lanczos continuation body: run steps
    ``k..ncv-1`` on (V, H). Used by every fused program variant."""
    def run(op_arrays, b_arrays, V, H, k):
        def A(v):
            return spmv(op_arrays, v)

        def Bip(v):
            return b_apply(b_arrays, v) if b_apply is not None else v

        def pdot_vec(Vb, wB):
            return lax.psum(jnp.conj(Vb) @ wB, axis)

        def pnorm(u):
            return jnp.sqrt(jnp.real(lax.psum(jnp.vdot(u, Bip(u)), axis)))

        vk = V[k]
        nrm = pnorm(vk)
        V = V.at[k].set(vk / jnp.where(nrm == 0, 1.0, nrm))

        def step(j, VH):
            V, H = VH
            w = A(V[j])
            h1 = pdot_vec(V, Bip(w))
            w = w - h1 @ V
            h2 = pdot_vec(V, Bip(w))
            w = w - h2 @ V
            h = h1 + h2
            b = pnorm(w)
            V = V.at[j + 1].set(w / jnp.where(b == 0, 1.0, b))
            H = H.at[:, j].set(h)
            H = H.at[j + 1, j].set(b)
            return (V, H)

        return lax.fori_loop(k, ncv, step, (V, H))
    return run


def _build_seed_facto_program(comm: DeviceComm, op, ncv: int, inner=None):
    """Seed + full factorization fused: ``prog(op_arrays, b_arrays, v0) ->
    (V, H)`` — builds the (ncv+1, n_pad) basis on device from the flat
    start vector and runs all ncv steps in the same program (one
    compile-cache entry + one dispatch instead of two; the remote-runtime
    round trip is ~100 ms each).

    AOT-cached (utils/aot): this and the restart-facto program are the two
    fixed-shape programs a fresh cfg2-style driver process pays tracing +
    lowering for — a prior process's export loads in their place."""
    axis = comm.axis
    key = ("seedfacto", comm.mesh, axis, ncv, _op_key(op),
           _op_key(inner) if inner is not None else None)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    spmv = _operator_precision(op.local_spmv(comm))
    op_specs = op.op_specs(axis)
    if inner is not None:
        b_apply = _operator_precision(inner.local_spmv(comm))
        b_specs = inner.op_specs(axis)
    else:
        b_apply = None
        b_specs = ()
    run = _facto_steps(spmv, b_apply, axis, ncv)

    def local_fn(op_arrays, b_arrays, v0):
        V = jnp.zeros((ncv + 1, v0.shape[0]), v0.dtype).at[0].set(v0)
        H = jnp.zeros((ncv + 1, ncv), v0.dtype)
        return run(op_arrays, b_arrays, V, H, 0)

    prog = jax.jit(comm.shard_map(
        local_fn,
        in_specs=(op_specs, b_specs, P(axis)),
        out_specs=(P(None, axis), P())))
    prog = _aot.wrap("seedfacto", comm,
                     key[3:] + (_operand_shapes(op, inner),), prog)
    _PROGRAM_CACHE[key] = prog
    return prog


def _build_restart_facto_program(comm: DeviceComm, op, ncv: int, inner=None):
    """Thick-restart compression + factorization continuation fused:
    ``prog(op_arrays, b_arrays, V, H_prefill, S, k) -> (V, H)`` — the basis
    compression (one sharded matmul) and the steps ``k..ncv-1`` run as ONE
    program, so each restart costs one dispatch + one small H fetch."""
    axis = comm.axis
    key = ("restartfacto", comm.mesh, axis, ncv, _op_key(op),
           _op_key(inner) if inner is not None else None)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    spmv = _operator_precision(op.local_spmv(comm))
    op_specs = op.op_specs(axis)
    if inner is not None:
        b_apply = _operator_precision(inner.local_spmv(comm))
        b_specs = inner.op_specs(axis)
    else:
        b_apply = None
        b_specs = ()
    run = _facto_steps(spmv, b_apply, axis, ncv)

    def local_fn(op_arrays, b_arrays, V, H, S, k):
        Vr = S.T @ V[:ncv]
        row = jnp.arange(ncv)[:, None]
        Vnew = jnp.zeros_like(V)
        Vnew = Vnew.at[:ncv].set(jnp.where(row < k, Vr, 0))
        Vnew = Vnew.at[k].set(V[ncv])
        return run(op_arrays, b_arrays, Vnew, H, k)

    prog = jax.jit(comm.shard_map(
        local_fn,
        in_specs=(op_specs, b_specs, P(None, axis), P(), P(), P()),
        out_specs=(P(None, axis), P())))
    prog = _aot.wrap("restartfacto", comm,
                     key[3:] + (_operand_shapes(op, inner),), prog)
    _PROGRAM_CACHE[key] = prog
    return prog


def _build_arnoldi_restart_facto_program(comm: DeviceComm, op, ncv: int,
                                         inner=None):
    """Explicit (arnoldi) restart + factorization fused:
    ``prog(op_arrays, b_arrays, V, w) -> (V, H)`` — the new start vector
    ``w @ V[:ncv]`` and the fresh ncv-step factorization in one program."""
    axis = comm.axis
    key = ("arnoldifacto", comm.mesh, axis, ncv, _op_key(op),
           _op_key(inner) if inner is not None else None)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    spmv = _operator_precision(op.local_spmv(comm))
    op_specs = op.op_specs(axis)
    if inner is not None:
        b_apply = _operator_precision(inner.local_spmv(comm))
        b_specs = inner.op_specs(axis)
    else:
        b_apply = None
        b_specs = ()
    run = _facto_steps(spmv, b_apply, axis, ncv)

    def local_fn(op_arrays, b_arrays, V, w):
        v0 = w @ V[:ncv]
        Vn = jnp.zeros_like(V).at[0].set(v0)
        H = jnp.zeros((ncv + 1, ncv), V.dtype)
        return run(op_arrays, b_arrays, Vn, H, 0)

    prog = jax.jit(comm.shard_map(
        local_fn,
        in_specs=(op_specs, b_specs, P(None, axis), P()),
        out_specs=(P(None, axis), P())))
    _PROGRAM_CACHE[key] = prog
    return prog


def _highest_precision(fn):
    """Trace ``fn`` under HIGHEST matmul precision: TPU's default f32
    matmul is bf16 (measured 1.4e-4 relative Gram error at n=5000 vs
    8.6e-8 at highest) — enough to stall every Gram/projection-based
    fused loop; 'highest' restores true working precision at ~3x matmul
    cost on the tiny projected dimensions involved."""
    import functools

    @functools.wraps(fn)
    def wrapped(*args):
        with jax.default_matmul_precision("highest"):
            return fn(*args)
    return wrapped


def _operator_precision(apply_fn):
    """Re-enter DEFAULT matmul precision around an operator application:
    _highest_precision protects the small Gram/projection matmuls, but the
    O(n²)-scale operator applies inside the same program (e.g. sinvert's
    dense inverse matvec) must not pay the ~3x multi-pass cost — their
    accuracy is governed by the operator itself, not the subspace algebra."""
    import functools

    @functools.wraps(apply_fn)
    def wrapped(*args):
        with jax.default_matmul_precision("default"):
            return apply_fn(*args)
    return wrapped


def _bt_dev(lam, sigma, st_type: str):
    """In-program spectral-transform back-transform (static ST branch,
    runtime sigma) — shared by every fused EPS loop program."""
    if st_type == "sinvert":
        safe = jnp.where(lam == 0, 1.0, lam)
        return jnp.where(lam == 0, jnp.inf, sigma + 1.0 / safe)
    if st_type != "shift":
        # cayley (two runtime parameters) runs the HOST loops — a fused
        # path reaching here is a gating bug; fail at trace time instead
        # of silently applying the wrong transform
        raise ValueError(f"_bt_dev: unhandled ST type {st_type!r}")
    return lam + sigma                     # 'shift' (identity at 0)


def _metric_dev(lam_bt, tau, which: str):
    """In-program selection metric — mirrors EPS._metric for real (HEP)
    spectra; shared by every fused EPS loop program."""
    if which == EPSWhich.LARGEST_MAGNITUDE:
        return jnp.abs(lam_bt)
    if which == EPSWhich.SMALLEST_MAGNITUDE:
        return -jnp.abs(lam_bt)
    if which == EPSWhich.LARGEST_REAL:
        return lam_bt
    if which == EPSWhich.SMALLEST_REAL:
        return -lam_bt
    if which == EPSWhich.TARGET_MAGNITUDE:
        return -jnp.abs(lam_bt - tau)
    if which == EPSWhich.TARGET_REAL:
        return -jnp.abs(lam_bt - tau)
    raise ValueError(f"unsupported which {which!r} for a fused EPS loop")


def _sym_orth(Y, axis, passes: int = 2):
    """Symmetric (eigh-based) row orthonormalization inside shard_map.

    ``B = diag(w^{-1/2}) Vᴴ Y`` from the Gram eigendecomposition
    ``psum(Y Yᴴ) = V diag(w) Vᴴ`` — near-null directions are MASKED to
    zero rows instead of dropped (the host loops' rank-revealing QR drops
    rows, which is a dynamic shape jit cannot express).

    Rows are normalized FIRST: Gram eigenvalues are squared norms, so
    without this a residual direction at 1e-6 of the iterates' scale falls
    below the mask threshold and LOBPCG hits a 1e-6 fixed point (measured);
    normalized, the trial blocks are mutually near-orthogonal and the Gram
    stays well-conditioned. A second pass (the CholeskyQR2 move) then
    restores machine-precision orthogonality. Returns ``(B, good, K)``
    with ``good`` the kept-direction mask and ``K`` the (rows×rows)
    transform such that ``B = K @ Y_input`` — LOBPCG's coefficient-split
    search directions need it to express new iterates over the ORIGINAL
    [X; W; P] rows.
    """
    rn = jnp.sqrt(jnp.real(lax.psum(jnp.sum(Y.conj() * Y, axis=1), axis)))
    # dtype-aware tiny: a 1e-300 literal underflows to 0 in f32, turning
    # zero rows (LOBPCG's first-iteration P block) into 0*inf = NaN
    tiny = jnp.finfo(rn.dtype).tiny
    inv0 = 1.0 / jnp.maximum(rn, tiny)
    Y = Y * inv0[:, None].astype(Y.dtype)
    K = jnp.diag(inv0).astype(Y.dtype)
    good = None
    for _ in range(max(1, passes)):
        G = lax.psum(Y @ Y.conj().T, axis)
        w, V = jnp.linalg.eigh(G)              # w real ascending
        scale = jnp.maximum(w[-1], tiny)
        g = w > scale * 1e-12
        inv = jnp.where(g, 1.0 / jnp.sqrt(jnp.where(g, w, 1.0)), 0.0)
        M = inv[:, None].astype(Y.dtype) * V.conj().T
        Y = M @ Y
        K = M @ K
        good = g if good is None else good
    return Y, good, K


def _build_hep_loop_program(comm: DeviceComm, op, ncv: int, k_keep: int,
                            nev: int, inner=None, which: str = "",
                            st_type: str = "shift"):
    """The ENTIRE Hermitian Krylov-Schur solve as ONE compiled program.

    ``prog(op_arrays, b_arrays, v0, tol, sigma, tau, max_restarts) ->
    (V, H, restarts, nconv)`` — a ``lax.while_loop`` over thick restarts:
    each iteration solves the ncv×ncv projected problem with
    ``jnp.linalg.eigh`` ON DEVICE, selects/orders by the ``which`` metric of
    the back-transformed Ritz values (static ST-type branch, runtime
    ``sigma``/``tau``), compresses the basis, and continues the
    factorization — no host round trips until the final (V, H) fetch, so a
    converged HEP/GHEP solve costs O(1) sync points instead of one per
    restart (where a fetch costs more than the ncv SpMVs, it dominates
    each cycle).

    Used only where the device ``eigh`` carries full working precision
    (see ``_device_eigh_trustworthy``): the CPU backend at any dtype and
    the TPU at f32/f64 (measured 2e-13 f64 eigh accuracy under x64 mode,
    which the package enables; complex eigh is CPU-only on this runtime —
    a lower-precision eigh would inject backward error into every thick
    restart, so the gate matters).
    """
    axis = comm.axis
    key = ("heploop", comm.mesh, axis, ncv, k_keep, nev, _op_key(op),
           _op_key(inner) if inner is not None else None, which, st_type)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    spmv = _operator_precision(op.local_spmv(comm))
    op_specs = op.op_specs(axis)
    if inner is not None:
        b_apply = _operator_precision(inner.local_spmv(comm))
        b_specs = inner.op_specs(axis)
    else:
        b_apply = None
        b_specs = ()
    run = _facto_steps(spmv, b_apply, axis, ncv)

    def back_transform(lam, sigma):
        return _bt_dev(lam, sigma, st_type)

    def metric(lam_bt, tau):
        return _metric_dev(lam_bt, tau, which)

    def local_fn(op_arrays, b_arrays, v0, tol, sigma, tau, max_restarts):
        dt = v0.dtype
        V0 = jnp.zeros((ncv + 1, v0.shape[0]), dt).at[0].set(v0)
        H0 = jnp.zeros((ncv + 1, ncv), dt)
        V, H = run(op_arrays, b_arrays, V0, H0, 0)

        def rr(H):
            Hm = H[:ncv, :ncv]
            Hm = (Hm + Hm.conj().T) / 2.0
            lam, S = jnp.linalg.eigh(Hm)       # lam real, ascending
            beta = jnp.real(H[ncv, ncv - 1])
            m = jnp.where(jnp.isfinite(lam),
                          metric(back_transform(lam, sigma), tau), -jnp.inf)
            order = jnp.argsort(-m)
            res = jnp.abs(beta) * jnp.abs(S[ncv - 1, order])
            rel = res / jnp.maximum(jnp.abs(lam[order]), 1e-300)
            lead = jnp.cumprod((rel[:nev] <= tol).astype(jnp.int32))
            return lam, S, order, jnp.sum(lead), beta

        def nconv_of(H):
            return rr(H)[3]

        def cond(st):
            V, H, restarts, nconv = st
            return (nconv < nev) & (restarts < max_restarts)

        def body(st):
            V, H, restarts, _ = st
            lam, S, order, _, beta = rr(H)
            take = order[:k_keep]
            S_keep = S[:, take]                    # (ncv, k)
            # thick restart: exact for device-precision eigenvectors
            H_new = jnp.zeros_like(H)
            H_new = H_new.at[jnp.arange(k_keep),
                             jnp.arange(k_keep)].set(lam[take].astype(dt))
            H_new = H_new.at[k_keep, :k_keep].set(
                (beta * S[ncv - 1, take]).astype(dt))
            Vr = S_keep.T @ V[:ncv]                # (k, lsize)
            V_new = jnp.zeros_like(V).at[:k_keep].set(Vr)
            V_new = V_new.at[k_keep].set(V[ncv])
            V2, H2 = run(op_arrays, b_arrays, V_new, H_new, k_keep)
            return (V2, H2, restarts + 1, nconv_of(H2))

        st = lax.while_loop(cond, body,
                            (V, H, jnp.int32(1), nconv_of(H)))
        V, H, restarts, nconv = st
        return V, H, restarts, nconv

    prog = jax.jit(comm.shard_map(
        _highest_precision(local_fn),
        in_specs=(op_specs, b_specs, P(axis), P(), P(), P(), P()),
        out_specs=(P(None, axis), P(), P(), P())))
    _PROGRAM_CACHE[key] = prog
    return prog


def _want_fused(comm: DeviceComm, n: int) -> bool:
    """Whether a whole-solve fused loop program should be used.

    The big fused program costs more to compile and load than the small
    host-loop programs, so tiny problems — where the per-iteration fetch
    it eliminates is cheap — default to the host loop (override:
    TPU_SOLVE_EPS_FUSED=0/1)."""
    fused_env = os.environ.get("TPU_SOLVE_EPS_FUSED", "")
    if fused_env in ("0", "false"):
        return False
    if fused_env in ("1", "true"):
        return True
    return comm.devices[0].platform == "cpu" or n >= 4096


def _device_matmul_trustworthy(comm: DeviceComm, dtype) -> bool:
    """True when device matmuls carry the full working precision of
    ``dtype``. XLA:TPU computes f64 matmuls with ~f32 accumulation (one
    v5e chip, chip_smoke.py's gate line, PR 21: 2.0e-8 relative Gram error
    of a 5000x64 V at ``lax.Precision.HIGHEST``), which floors Gram-based
    orthonormalization near f32 orthogonality — fused loops whose
    CONVERGENCE depends on working-precision projections (subspace/lobpcg)
    must keep the host loop for f64 there. CPU BLAS is exact-precision;
    TPU f32 matmul is native working precision for f32 operators."""
    if comm.devices[0].platform == "cpu":
        return True
    return np.dtype(str(dtype)) == np.dtype(np.float32)


def _device_eigh_trustworthy(comm: DeviceComm, dtype) -> bool:
    """True when ``jnp.linalg.eigh`` on this mesh carries the full working
    precision of ``dtype``: the CPU backend (LAPACK) always does, and the
    TPU's eigh is full-precision for f32/f64 under x64 mode (the package
    enables x64 at import). Complex64 eigh runs on the chip at working
    precision (one v5e, chip_smoke.py's gate line, PR 21: 5.0e-7 relative
    eigenvalue error at 64x64); complex128 was not probed there and stays
    CPU-only."""
    platform = comm.devices[0].platform
    if platform == "cpu":
        return True
    return np.dtype(str(dtype)) != np.dtype(np.complex128)


def _build_power_program(comm: DeviceComm, op, steps: int):
    """``steps`` normalized power steps + Rayleigh quotient/residual, jitted."""
    axis = comm.axis
    key = ("power", comm.mesh, axis, steps, _op_key(op))
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    spmv = op.local_spmv(comm)
    op_specs = op.op_specs(axis)

    def local_fn(op_arrays, v):
        def A(u):
            return spmv(op_arrays, u)

        def pnorm(u):
            # real-typed also for complex vectors (vdot(u,u) has ~0 imag)
            return jnp.sqrt(jnp.real(lax.psum(jnp.vdot(u, u), axis)))

        def step(_, u):
            w = A(u)
            return w / pnorm(w)

        v = v / pnorm(v)
        v = lax.fori_loop(0, steps, step, v)
        w = A(v)
        theta = lax.psum(jnp.vdot(v, w), axis)
        res = pnorm(w - theta * v)
        return v, theta, res

    prog = jax.jit(comm.shard_map(
        local_fn,
        in_specs=(op_specs, P(axis)),
        out_specs=(P(axis), P(), P())))
    _PROGRAM_CACHE[key] = prog
    return prog


def _build_block_mult_program(comm: DeviceComm, op, m: int):
    """Apply the operator to each of ``m`` basis rows (statically unrolled)."""
    axis = comm.axis
    key = ("blockmult", comm.mesh, axis, m, _op_key(op))
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    spmv = op.local_spmv(comm)
    op_specs = op.op_specs(axis)

    def local_fn(op_arrays, Y):
        rows = [spmv(op_arrays, Y[j]) for j in range(m)]
        return jnp.stack(rows)

    prog = jax.jit(comm.shard_map(
        local_fn,
        in_specs=(op_specs, P(None, axis)),
        out_specs=P(None, axis)))
    _PROGRAM_CACHE[key] = prog
    return prog


def _build_subspace_loop_program(comm: DeviceComm, op, ncv: int, nev: int,
                                 which: str, st_type: str):
    """The ENTIRE Hermitian subspace iteration as ONE compiled program.

    ``prog(op_arrays, Y0, tol, sigma, tau, max_it) ->
    (X, lam_t, rel, iters, nconv)`` — a ``lax.while_loop`` whose body
    orthonormalizes the block (symmetric eigh orthonormalization — the
    MXU-friendly, fixed-shape stand-in for the host loop's QR), applies the
    operator (ncv unrolled SpMVs), solves the ncv×ncv projected problem
    with ``jnp.linalg.eigh`` ON DEVICE, forms Ritz rows + residuals
    in-program, and power-steps. O(1) host sync points per solve instead of
    one fetch per iteration (the round-3 VERDICT's lobpcg/subspace demand);
    same gating as the fused Krylov-Schur loop (_device_eigh_trustworthy).
    """
    axis = comm.axis
    key = ("subspaceloop", comm.mesh, axis, ncv, nev, _op_key(op), which,
           st_type)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    spmv = _operator_precision(op.local_spmv(comm))
    op_specs = op.op_specs(axis)

    def local_fn(op_arrays, Y0, tol, sigma, tau, max_it):
        rdt = jnp.real(jnp.zeros((), Y0.dtype)).dtype

        def blockA(Q):
            return jnp.stack([spmv(op_arrays, Q[j]) for j in range(ncv)])

        def reseed_masked(Q, good, it):
            # a _sym_orth-masked row is a ZERO row and the power step of a
            # zero row stays zero — a numerically rank-deficient block
            # would stall at max_it (the host loop's Householder QR
            # re-injects orthogonal-complement directions instead; ADVICE
            # r4). Re-fill masked rows with a counter-based pseudo-random
            # direction (fold_in on iteration + shard index: deterministic
            # and trace-safe) orthogonalized against the kept rows, then
            # re-orthonormalize the block once.
            def fill(Q):
                key = jax.random.fold_in(jax.random.fold_in(
                    jax.random.PRNGKey(7), it), lax.axis_index(axis))
                Z = jax.random.normal(key, Q.shape, rdt).astype(Q.dtype)
                G = lax.psum(Z @ Q.conj().T, axis)
                Z = Z - G @ Q
                zn = jnp.sqrt(jnp.real(lax.psum(
                    jnp.sum(Z.conj() * Z, axis=1), axis)))
                Z = Z * (1.0 / jnp.maximum(zn, jnp.finfo(rdt).tiny)
                         )[:, None].astype(Q.dtype)
                Q2 = jnp.where(good[:, None], Q, Z)
                return _sym_orth(Q2, axis, passes=1)[0]
            return lax.cond(jnp.any(~good), fill, lambda q: q, Q)

        def rr(Y, it):
            Q, good, _ = _sym_orth(Y, axis)
            Q = reseed_masked(Q, good, it)
            W = blockA(Q)
            Hm = lax.psum(Q.conj() @ W.T, axis)
            Hm = (Hm + Hm.conj().T) / 2.0
            lam, S = jnp.linalg.eigh(Hm)       # real, ascending
            m = jnp.where(jnp.isfinite(lam),
                          _metric_dev(_bt_dev(lam, sigma, st_type), tau,
                                      which), -jnp.inf)
            order = jnp.argsort(-m)
            X = S[:, order].T @ Q              # Ritz rows (ncv, lsize)
            AX = S[:, order].T @ W
            lam_o = lam[order]
            R = AX - lam_o[:, None].astype(AX.dtype) * X
            rn = jnp.sqrt(jnp.real(lax.psum(
                jnp.sum(R.conj() * R, axis=1), axis)))
            rel = rn / jnp.maximum(jnp.abs(lam_o), jnp.finfo(rn.dtype).tiny)
            lead = jnp.cumprod((rel[:nev] <= tol).astype(jnp.int32))
            return Q, W, X, lam_o.astype(rdt), rel.astype(rdt), \
                jnp.sum(lead).astype(jnp.int32)

        def cond(st):
            Y, X, lam_o, rel, it, nconv = st
            return (nconv < nev) & (it < max_it)

        def body(st):
            Y, _, _, _, it, _ = st
            Q, W, X, lam_o, rel, nconv = rr(Y, it)
            # power step — the host loop's Y <- A Q (the real-dtype
            # imaginary-part drop there is a no-op on these real carries)
            return (W, X, lam_o, rel, it + 1, nconv)

        z = jnp.zeros_like(Y0)
        st0 = (Y0, z, jnp.zeros((ncv,), rdt), jnp.full((ncv,), jnp.inf,
                                                       rdt),
               jnp.int32(0), jnp.int32(0))
        Y, X, lam_o, rel, it, nconv = lax.while_loop(cond, body, st0)
        return X, lam_o, rel, it, nconv

    prog = jax.jit(comm.shard_map(
        _highest_precision(local_fn),
        in_specs=(op_specs, P(None, axis), P(), P(), P(), P()),
        out_specs=(P(None, axis), P(), P(), P(), P())))
    _PROGRAM_CACHE[key] = prog
    return prog


def _lobpcg_seed(op, n: int, m: int, dtype):
    """Deterministic LOBPCG start block (orthonormal rows, fixed seed) and
    Jacobi-diagonal inverse — the ONE definition both the fused and host
    paths use, so their solves start identically."""
    hdt = host_dtype(dtype)
    rng = np.random.default_rng(20240901)
    X0 = rng.standard_normal((m, n)).astype(hdt)
    if is_complex(dtype):
        X0 = X0 + 1j * rng.standard_normal((m, n))
    X0 = np.linalg.qr(X0.T)[0].T
    try:
        diag = np.asarray(op.diagonal(), dtype=hdt)
        dinv = np.where(np.abs(diag) > 0,
                        1.0 / np.where(diag == 0, 1.0, diag),
                        1.0).astype(hdt)
    except (ValueError, AttributeError):
        dinv = np.ones(n, dtype=hdt)
    return X0, dinv


def _build_lobpcg_loop_program(comm: DeviceComm, op, bop, m: int, nev: int,
                               largest: bool):
    """The ENTIRE LOBPCG solve as ONE compiled program.

    ``prog(op_arrays, b_arrays, dinv, X0, tol, max_it) ->
    (X, theta, rel, iters, nconv)`` — a ``lax.while_loop`` over block
    iterations: the 3m-row trial space span[X, T·R, P] is orthonormalized
    with the masked symmetric-eigh orthonormalization (_sym_orth — the
    fixed-shape analog of the host loop's rank-revealing QR; dropped
    directions become zero rows whose projected diagonal is pushed to
    +LARGE so selection ignores them), the 3m×3m pencil is whitened by the
    Bg eigendecomposition and solved with ``jnp.linalg.eigh`` ON DEVICE,
    and new B-orthonormal Ritz rows + search directions are formed
    in-program. O(1) host sync points per solve (round-3 VERDICT item 7).
    ``dinv`` is the Jacobi preconditioner diagonal (ones = identity).
    """
    axis = comm.axis
    key = ("lobpcgloop", comm.mesh, axis, m, nev, _op_key(op),
           _op_key(bop) if bop is not None else None, largest)
    cached = _PROGRAM_CACHE.get(key)
    if cached is not None:
        return cached

    spmv = _operator_precision(op.local_spmv(comm))
    op_specs = op.op_specs(axis)
    if bop is not None:
        b_apply = _operator_precision(bop.local_spmv(comm))
        b_specs = bop.op_specs(axis)
    else:
        b_apply = None
        b_specs = ()
    sign = -1.0 if largest else 1.0
    nev_m = min(nev, m)

    def local_fn(op_arrays, b_arrays, dinv, X0, tol, max_it):
        rdt = jnp.real(jnp.zeros((), X0.dtype)).dtype
        # masked-direction push-out value: must dominate any Ritz value yet
        # survive squaring inside eigh (1e30 overflows f32 there)
        BIG = 1e30 if jnp.finfo(rdt).bits >= 64 else 1e12

        def blockA(M):
            return jnp.stack([spmv(op_arrays, M[j])
                              for j in range(M.shape[0])])

        def blockB(M):
            if b_apply is None:
                return M
            return jnp.stack([b_apply(b_arrays, M[j])
                              for j in range(M.shape[0])])

        def evaluate(X, AX, BX):
            num = jnp.real(lax.psum(jnp.sum(X.conj() * AX, axis=1), axis))
            den = jnp.real(lax.psum(jnp.sum(X.conj() * BX, axis=1), axis))
            theta = num / jnp.where(den == 0, 1.0, den)
            R = AX - theta[:, None].astype(AX.dtype) * BX
            rn = jnp.sqrt(jnp.real(lax.psum(
                jnp.sum(R.conj() * R, axis=1), axis)))
            rel = rn / jnp.maximum(jnp.abs(theta),
                                   jnp.finfo(rn.dtype).tiny)
            ordm = jnp.argsort(sign * theta)
            lead = jnp.cumprod((rel[ordm][:nev_m] <= tol).astype(jnp.int32))
            return (theta.astype(rdt), R, rel.astype(rdt),
                    jnp.sum(lead).astype(jnp.int32))

        def cond(st):
            X, Pd, AX, BX, Xr, theta, rel, it, nconv = st
            return (nconv < nev_m) & (it < max_it)

        def body(st):
            X, Pd, AX, BX, _, _, _, it, _ = st
            theta, R, rel, nconv = evaluate(X, AX, BX)
            W = R * dinv[None, :]
            S0 = jnp.concatenate([X, W, Pd], axis=0)       # (3m, lsize)
            B, _, K = _sym_orth(S0, axis)
            AS = blockA(B)
            BS = blockB(B)
            Ag = lax.psum(B.conj() @ AS.T, axis)
            Bg = lax.psum(B.conj() @ BS.T, axis)
            Ag = (Ag + Ag.conj().T) / 2.0
            Bg = (Bg + Bg.conj().T) / 2.0
            # whiten by Bg (masked zero rows of B give null Bg directions;
            # they get +BIG diagonals below so selection never takes them)
            wb, Vb = jnp.linalg.eigh(Bg)
            goodb = wb > jnp.maximum(wb[-1], jnp.finfo(wb.dtype).tiny) * 1e-12
            ib = jnp.where(goodb, 1.0 / jnp.sqrt(jnp.where(goodb, wb, 1.0)),
                           0.0)
            T = Vb * ib[None, :]
            Ag2 = T.conj().T @ (sign * Ag) @ T
            Ag2 = (Ag2 + Ag2.conj().T) / 2.0
            Ag2 = Ag2 + jnp.diag(jnp.where(goodb, 0.0, BIG).astype(
                Ag2.dtype))
            lam2, C2 = jnp.linalg.eigh(Ag2)                # ascending
            C = T @ C2[:, :m]                              # Bg-orthonormal
            Xn = C.T @ B
            AXn = C.T @ AS
            BXn = C.T @ BS
            # new search directions: Knyazev's COEFFICIENT SPLIT — the part
            # of Xn built from the W and P rows only. Xn = Cᵀ B = CᵀK S0,
            # so D = Kᵀ C expresses Xn over the original [X; W; P] rows and
            # the W/P slice of D is the new P. (Measured on the complex-GHEP
            # oracle: 125 its; "P = Xn − X" 999+; a span(X) projection
            # stalls at ~1e-7.)
            D = K.T @ C
            Pn = D[m:].T @ S0[m:]
            # the RESULT slots carry the block just EVALUATED (X, not Xn):
            # when cond exits on nconv, the reported pairs are exactly the
            # ones whose residuals passed the test
            return (Xn, Pn, AXn, BXn, X, theta, rel, it + 1, nconv)

        AX0 = blockA(X0)
        BX0 = blockB(X0)
        P0 = jnp.zeros_like(X0)
        th0, _, rel0, nc0 = evaluate(X0, AX0, BX0)
        st = lax.while_loop(
            cond, body,
            (X0, P0, AX0, BX0, X0, th0, rel0, jnp.int32(0), nc0))
        _, _, _, _, Xr, theta, rel, it, nconv = st
        return Xr, theta, rel, it, nconv

    prog = jax.jit(comm.shard_map(
        _highest_precision(local_fn),
        in_specs=(op_specs, b_specs, P(axis), P(None, axis), P(), P()),
        out_specs=(P(None, axis), P(), P(), P(), P())))
    _PROGRAM_CACHE[key] = prog
    return prog


def _apply_blocked(S, apply_m, m):
    """Apply an m-row block program to a ``(k, n)`` host block, k arbitrary.

    Chunks the rows into m-row blocks (zero-padding the tail) so one compiled
    block-mult program serves every basis size LOBPCG produces.
    """
    k = S.shape[0]
    out = np.zeros_like(S)
    for s in range(0, k, m):
        blk = S[s:s + m]
        if blk.shape[0] < m:
            pad = np.zeros((m, S.shape[1]), dtype=S.dtype)
            pad[:blk.shape[0]] = blk
            out[s:s + m] = apply_m(pad)[:blk.shape[0]]
        else:
            out[s:s + m] = apply_m(blk)
    return out


class EPS:
    """Eigensolver context, slepc4py-``EPS``-shaped."""

    ProblemType = EPSProblemType
    Which = EPSWhich
    Type = EPSType

    def __init__(self, comm=None):
        self.comm = None
        self._mat: Mat | None = None
        self._bmat: Mat | None = None
        self._type = "krylovschur"     # SLEPc default
        self._problem_type = EPSProblemType.NHEP
        self._which = EPSWhich.LARGEST_MAGNITUDE
        self._target: float | None = None
        self.st = ST()
        self.nev = 1                  # SLEPc default
        self.ncv: int | None = None   # auto: max(2*nev, nev+15), capped at n
        self.tol = DEFAULT_TOL
        self.max_it = DEFAULT_MAX_RESTARTS
        self.gd_blocksize = 0     # -eps_gd_blocksize (0 = auto: nev)
        self._monitors: list = []      # EPSMonitorSet callbacks
        self._monitor_flag = False     # -eps_monitor default printer
        self.result = SolveResult()
        self._eigenvalues = np.zeros(0)
        self._eigenvectors = np.zeros((0, 0))
        self._residuals = np.zeros(0)
        self._nconv = 0
        if comm is not None:
            self.create(comm)

    # ---- lifecycle / configuration -----------------------------------------
    def create(self, comm=None):
        self.comm = as_comm(comm)
        return self

    def destroy(self):
        return self

    def set_type(self, eps_type: str):
        eps_type = str(eps_type).lower()
        if eps_type not in EPS_TYPES:
            raise ValueError(f"unknown EPS type {eps_type!r}; "
                             f"available: {EPS_TYPES}")
        self._type = eps_type
        return self

    setType = set_type

    def get_type(self) -> str:
        return self._type

    getType = get_type

    def set_operators(self, A: Mat, B: Mat | None = None):
        self._mat = A
        self._bmat = B
        if B is not None and self._problem_type not in (EPSProblemType.GHEP,):
            self._problem_type = EPSProblemType.GHEP
        if self.comm is None:
            self.create(A.comm)
        return self

    setOperators = set_operators

    def set_problem_type(self, ptype):
        ptype = str(ptype).lower()
        if ptype not in (EPSProblemType.HEP, EPSProblemType.NHEP,
                         EPSProblemType.GHEP):
            raise ValueError(f"unsupported problem type {ptype!r}")
        self._problem_type = ptype
        return self

    setProblemType = set_problem_type

    def set_which_eigenpairs(self, which: str):
        self._which = str(which).lower()
        return self

    setWhichEigenpairs = set_which_eigenpairs

    def set_target(self, target: float):
        """Target value for ``target_*`` selections; with ST ``sinvert`` the
        target doubles as the default shift (SLEPc's convention)."""
        self._target = float(target)
        return self

    setTarget = set_target

    def get_st(self) -> ST:
        return self.st

    getST = get_st

    def set_dimensions(self, nev: int | None = None, ncv: int | None = None):
        if nev is not None:
            self.nev = int(nev)
        if ncv is not None:
            self.ncv = int(ncv)
        return self

    setDimensions = set_dimensions

    def set_tolerances(self, tol=None, max_it=None):
        if tol is not None:
            self.tol = float(tol)
        if max_it is not None:
            self.max_it = int(max_it)
        return self

    setTolerances = set_tolerances

    def set_from_options(self):
        """Apply ``-eps_type``, ``-eps_nev``, ``-eps_ncv``, ``-eps_tol``,
        ``-eps_max_it``, ``-eps_hermitian``, ``-eps_which``, ``-eps_target``
        plus the ST options (``-st_type``, ``-st_shift``) from the options DB
        (the reference's ``E.setFromOptions()``, ``petsc_funcs.py:17``)."""
        opt = global_options()
        eps_type = opt.get_string("eps_type")
        if eps_type:
            self.set_type(eps_type)
        self.nev = opt.get_int("eps_nev", self.nev)
        ncv = opt.get_int("eps_ncv", None)
        if ncv is not None:
            self.ncv = ncv
        self.tol = opt.get_real("eps_tol", self.tol)
        self.max_it = opt.get_int("eps_max_it", self.max_it)
        if opt.get_bool("eps_hermitian", False):
            self._problem_type = EPSProblemType.HEP
        which = opt.get_string("eps_which")
        if which:
            self._which = which
        target = opt.get_real("eps_target", None)
        if target is not None:
            self.set_target(target)
        self.gd_blocksize = opt.get_int("eps_gd_blocksize",
                                        self.gd_blocksize)
        self._monitor_flag = opt.get_bool("eps_monitor",
                                          self._monitor_flag)
        self.st.set_from_options()
        return self

    # ---- monitors (EPSMonitorSet / -eps_monitor) -----------------------------
    def set_monitor(self, fn):
        """Register ``fn(eps, its, nconv, eig, errest)`` — slepc4py's
        ``EPS.setMonitor`` signature: back-transformed eigenvalue
        approximations and relative error estimates, most-wanted-first,
        once per outer iteration/restart. Monitored solves run the
        host-orchestrated loops (a fused whole-solve program has no
        per-restart host point to report from — same philosophy as KSP's
        monitored-programs-stay-unrolled rule)."""
        if fn is not None:          # setMonitor(None) is a no-op (slepc4py)
            self._monitors.append(fn)
        return self

    setMonitor = set_monitor

    def cancel_monitor(self):
        """EPSMonitorCancel: removes ALL monitors — including the
        ``-eps_monitor`` printer — and un-pins the fused solve paths."""
        self._monitors = []
        self._monitor_flag = False
        return self

    cancelMonitor = cancel_monitor

    def _monitored(self) -> bool:
        return bool(self._monitors) or self._monitor_flag

    def _emit_monitor(self, its, nconv, lam, errest):
        """One monitoring event. ``lam``/``errest`` ordered
        most-wanted-first; prints SLEPc's ``-eps_monitor`` line when the
        flag is set, then runs user callbacks."""
        if not self._monitored():
            return
        lam = np.atleast_1d(np.asarray(lam))
        errest = np.atleast_1d(np.asarray(errest))
        if self._monitor_flag:
            if int(nconv) < len(lam):
                j = int(nconv)
                err = float(errest[j]) if j < len(errest) else 0.0
                print(f"{int(its):3d} EPS nconv={int(nconv)} first "
                      f"unconverged value (error) {lam[j]} ({err:.8e})")
            else:   # every reported pair converged — no mislabeled value
                print(f"{int(its):3d} EPS nconv={int(nconv)} "
                      "(all requested pairs converged)")
        for fn in self._monitors:
            fn(self, int(its), int(nconv), lam, errest)

    setFromOptions = set_from_options

    # ---- selection ----------------------------------------------------------
    def _effective_ncv(self, n: int) -> int:
        if self.ncv is not None:
            return min(self.ncv, n)
        return min(n, max(2 * self.nev, self.nev + 15))

    def _metric(self, lam: np.ndarray) -> np.ndarray:
        """Bigger = more wanted (used for both sorting and Schur selection)."""
        w = self._which
        if w == EPSWhich.LARGEST_MAGNITUDE:
            return np.abs(lam)
        if w == EPSWhich.SMALLEST_MAGNITUDE:
            return -np.abs(lam)
        if w == EPSWhich.LARGEST_REAL:
            return np.real(lam)
        if w == EPSWhich.SMALLEST_REAL:
            return -np.real(lam)
        if w == EPSWhich.TARGET_MAGNITUDE:
            tau = 0.0 if self._target is None else self._target
            return -np.abs(lam - tau)
        if w == EPSWhich.TARGET_REAL:
            tau = 0.0 if self._target is None else self._target
            return -np.abs(np.real(lam) - tau)
        raise ValueError(f"unknown which {self._which!r}")

    def _select(self, lam: np.ndarray) -> np.ndarray:
        finite = np.where(np.isfinite(lam), self._metric(lam), -np.inf)
        return np.argsort(-finite, kind="stable")

    # ---- solve --------------------------------------------------------------
    @wrap_device_errors("EPSSolve")
    def solve(self):
        mat = self._mat
        if mat is None:
            raise RuntimeError("EPS.solve: no operators set")
        _faults.check("eps.solve")    # injectable pre-solve device failure
        if self._bmat is not None and \
                self._problem_type != EPSProblemType.GHEP:
            raise ValueError("two operators were set; problem type must be "
                             "'ghep' (B must be SPD)")
        if self._problem_type == EPSProblemType.GHEP and self._bmat is None:
            raise ValueError("problem type 'ghep' needs operators (A, B)")
        # SLEPc convention: a target with sinvert/cayley supplies the shift.
        if (self._target is not None
                and self.st.get_type() in ("sinvert", "cayley")
                and self.st.sigma == 0.0):
            self.st.set_shift(self._target)
        t0 = time.perf_counter()
        with _telemetry.span("eps.solve", eps_type=self._type,
                             problem=str(self._problem_type),
                             nev=int(self.nev),
                             n=int(mat.shape[0]),
                             devices=int(getattr(mat.comm, "size", 0)
                                         or 0)) as sp:
            if self._type == "lapack":
                self._solve_lapack()
            elif self._type == "power":
                self._solve_power()
            elif self._type == "subspace":
                self._solve_subspace()
            elif self._type == "lobpcg":
                self._solve_lobpcg()
            elif self._type == "gd":
                self._solve_gd()
            elif self._type == "arnoldi":
                self._solve_arnoldi_explicit()
            else:  # krylovschur / lanczos
                if self._type == "lanczos" and self._problem_type not in (
                        EPSProblemType.HEP, EPSProblemType.GHEP):
                    raise ValueError("EPS 'lanczos' needs a Hermitian "
                                     "problem type (hep/ghep)")
                self._solve_krylovschur()
            wall = time.perf_counter() - t0
            self.result = SolveResult(
                self._its, float(self._residuals[0])
                if len(self._residuals) else 0.0,
                # nev > n cannot "diverge": min(nev, n) pairs exist at all
                2 if self._nconv >= min(self.nev, mat.shape[0]) else -3,
                wall)
            sp.set_attrs(iterations=int(self._its),
                         nconv=int(self._nconv),
                         reason=self.result.reason)
        from ..utils.profiling import record_event
        record_event(
            f"EPSSolve({self._type},{self._problem_type},nev={self.nev})",
            mat.shape[0], self._its, wall, self.result.reason)
        return self

    # ---- lapack (dense host solve — SLEPc's EPSLAPACK) ----------------------
    _LAPACK_CAP = 16384   # O(n^2) dense storage + O(n^3) host factorization

    def _solve_lapack(self):
        """SLEPc's ``EPSLAPACK`` equivalent: solve the FULL dense problem
        on host (LAPACK eigh/eig; [external] behind ``-eps_type lapack``
        through the reference's ``setFromOptions``, petsc_funcs.py:17) and
        select ``nev`` pairs by ``which``/``target``. Every reported pair
        is exact to machine precision — the small-n oracle the iterative
        types are tested against, now a first-class type. Host O(n^3);
        capped like the dense direct paths."""
        import scipy.linalg as sla
        mat = self._mat
        n = mat.shape[0]
        if n > self._LAPACK_CAP:
            raise ValueError(
                f"EPS 'lapack' solves the full dense problem on host "
                f"(O(n^3)); n={n} exceeds the {self._LAPACK_CAP} cap — "
                "use krylovschur/lobpcg")
        if not hasattr(mat, "to_scipy") or (
                self._problem_type == EPSProblemType.GHEP
                and not hasattr(self._bmat, "to_scipy")):
            raise ValueError("EPS 'lapack' needs assembled matrices (Mat)")
        A = mat.to_scipy().toarray()
        hermitian = self._problem_type in (EPSProblemType.HEP,
                                           EPSProblemType.GHEP)
        if self._problem_type == EPSProblemType.GHEP:
            B = self._bmat.to_scipy().toarray()
            lam, V = sla.eigh(A, B)
        elif hermitian:
            lam, V = np.linalg.eigh((A + A.conj().T) / 2.0)
        else:
            lam, V = np.linalg.eig(A)
        if self.st.get_type() == "sinvert":
            # the iterative types' sinvert Krylov space contains the pairs
            # CLOSEST TO sigma (largest |theta| = |1/(lam-sigma)|); the
            # dense solve has every pair, so reproduce that selection
            # explicitly — otherwise '-eps_type lapack -st_type sinvert'
            # would silently return globally-extremal pairs instead
            order = np.argsort(np.abs(lam - self.st.sigma), kind="stable")
        elif self.st.get_type() == "cayley":
            # cayley's magnification is |theta| = |lam+nu|/|lam-sigma| —
            # NOT plain distance to sigma (a pair at lam = -nu has theta=0:
            # the LEAST magnified of the whole spectrum); order by the
            # actual transformed magnitude, descending
            nu = self.st.get_antishift()
            dist = np.abs(lam - self.st.sigma)
            theta_mag = np.where(dist == 0, np.inf,
                                 np.abs(lam + nu) / np.where(dist == 0, 1.0,
                                                             dist))
            order = np.argsort(-theta_mag, kind="stable")
        else:
            order = self._select(lam)
        count = min(self.nev, n)
        take = order[:count]
        vecs = V[:, take].T
        nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
        nrm[nrm == 0] = 1.0
        vecs = vecs / nrm
        # exact dense residuals (machine-precision by construction)
        if self._problem_type == EPSProblemType.GHEP:
            R = A @ vecs.T - B @ vecs.T * lam[take][None, :]
        else:
            R = A @ vecs.T - vecs.T * lam[take][None, :]
        rel = (np.linalg.norm(R, axis=0)
               / np.maximum(np.abs(lam[take]), np.finfo(float).tiny))
        self._store(lam[take], vecs, rel, count, 1)

    # ---- shared pieces ------------------------------------------------------
    def _setup_operator(self):
        comm = self._mat.comm
        hermitian = self._problem_type in (EPSProblemType.HEP,
                                           EPSProblemType.GHEP)
        # Cache the built ST operator: sinvert/GHEP factorize a dense inverse
        # on host (O(n^3)) — rebuilding it per solve() with unchanged
        # (A, B, st) would repeat that and re-ship the replicated inverse.
        key = (self._mat, getattr(self._mat, "_state", 0), self._bmat,
               getattr(self._bmat, "_state", 0), self.st.get_type(),
               self.st.sigma, self.st.get_antishift()
               if self.st.get_type() == "cayley" else None)
        cached = getattr(self, "_op_cache", None)
        if cached is not None and cached[0] == key:
            return comm, cached[1], cached[2], hermitian
        op, inner = self.st.build_operator(self._mat, self._bmat)
        self._op_cache = (key, op, inner)
        return comm, op, inner, hermitian

    def _dominant_only(self, solver: str):
        """power/subspace converge to the *dominant* (transformed) subspace —
        any other selection, or a transform under which dominance no longer
        means "wanted" (a nonzero shift), silently returns wrong pairs
        (SLEPc's EPSPOWER errors the same way)."""
        ok = (self._which == EPSWhich.LARGEST_MAGNITUDE
              and self.st.is_identity()) or (
            self._which == EPSWhich.TARGET_MAGNITUDE
            and self.st.get_type() == "sinvert")
        if not ok:
            raise ValueError(
                f"EPS {solver!r} computes dominant eigenpairs only — use "
                f"which='largest_magnitude' with no spectral transform, or "
                f"'target_magnitude' with ST 'sinvert' (got "
                f"which={self._which!r}, st={self.st.get_type()!r} "
                f"shift={self.st.sigma}); krylovschur supports all "
                "selections")

    def _rayleigh_ritz(self, Hh: np.ndarray, ncv: int, nev: int,
                       hermitian: bool):
        """Shared projected-eigenproblem + selection + convergence step.

        Returns ``(beta, lam_t, S, order, rel, nconv)``: the subdiagonal
        residual norm, transformed Ritz values, projected eigenvectors, the
        which-ordering, relative residual estimates (ordered), and the count
        of leading converged wanted pairs. The Ritz residual
        ``|beta| |e_m^T y|`` is valid for the arrow+Hessenberg projected
        matrix too (the Krylov-Schur relation ``T V = V H + beta v e_m^T``
        holds after every thick restart).
        """
        Hm = Hh[:ncv, :ncv]
        # the subdiagonal entry is a norm — real by construction
        beta = float(np.real(Hh[ncv, ncv - 1]))
        if hermitian:
            Hm = (Hm + Hm.conj().T) / 2.0
            lam_t, S = np.linalg.eigh(Hm)
        else:
            lam_t, S = np.linalg.eig(Hm)
        order = self._select(self.st.back_transform(lam_t))
        res = np.abs(beta) * np.abs(S[ncv - 1, order])
        denom = np.maximum(np.abs(lam_t[order]), 1e-300)
        rel = res / denom
        nconv = 0
        while nconv < min(nev, len(rel)) and rel[nconv] <= self.tol:
            nconv += 1
        return beta, lam_t, S, order, rel, nconv

    def _start_vector(self, comm, n, dtype):
        rng = np.random.default_rng(20240901)
        npad = comm.padded_size(n)
        v0 = rng.standard_normal(npad)
        v0[n:] = 0.0        # padding never enters the Krylov space
        return v0.astype(dtype)

    def _store(self, lam, vecs, rel, nconv, its):
        self._eigenvalues = np.asarray(lam)
        self._eigenvectors = np.asarray(vecs)
        self._residuals = np.asarray(rel, dtype=float)
        self._nconv = int(nconv)
        self._its = int(its)

    def _extract(self, Vh, S, lam_t, order, n, count):
        """Ritz vectors ``(count, n)`` from host basis + projected vectors,
        back-transformed eigenvalues, normalized."""
        take = order[:count]
        vecs = (S[:, take].T @ Vh)[:, :n]
        nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
        nrm[nrm == 0] = 1.0
        vecs = vecs / nrm
        lam = self.st.back_transform(lam_t[take])
        return lam, vecs

    # ---- krylovschur (thick restart) ----------------------------------------
    def _solve_krylovschur(self):
        comm, op, inner, hermitian = self._setup_operator()
        n = op.shape[0]
        ncv = self._effective_ncv(n)
        nev = min(self.nev, ncv)
        dtype = np.dtype(str(op.dtype))
        op_arrays = op.device_arrays()
        b_arrays = inner.device_arrays() if inner is not None else ()
        v0 = comm.put_rows(self._start_vector(comm, n, dtype))
        k_keep = int(min(max(nev, ncv // 2), ncv - 1))

        # ---- fused whole-solve path: every restart's projected eigh runs
        # ON DEVICE inside one while_loop program — O(1) sync points/solve.
        # Requires a Hermitian problem (real projected spectrum, no Schur
        # ordering) and a device eigh at full working precision. The big
        # fused program costs more to compile and load than the two small
        # host-loop programs, so tiny problems — where the per-restart H
        # fetch it eliminates is cheap — default to the host loop
        # (override: TPU_SOLVE_EPS_FUSED).
        # cayley back-transforms with TWO runtime parameters (sigma, nu);
        # the fused program's static _bt_dev carries only sigma, so cayley
        # runs the host loop (generic st.back_transform). Monitored solves
        # also run it — the fused program has no per-restart host point.
        want_fused = (_want_fused(comm, n)
                      and self.st.get_type() != "cayley"
                      and not self._monitored())
        if (want_fused and hermitian and ncv < n and k_keep >= 1
                and self._which in (
                    EPSWhich.LARGEST_MAGNITUDE, EPSWhich.SMALLEST_MAGNITUDE,
                    EPSWhich.LARGEST_REAL, EPSWhich.SMALLEST_REAL,
                    EPSWhich.TARGET_MAGNITUDE, EPSWhich.TARGET_REAL)
                and _device_eigh_trustworthy(comm, dtype)):
            prog = _build_hep_loop_program(
                comm, op, ncv, k_keep, nev, inner,
                which=self._which, st_type=self.st.get_type())
            tau = 0.0 if self._target is None else float(self._target)
            V, H, restarts_a, _ = prog(
                op_arrays, b_arrays, v0,
                np.float64(self.tol), np.float64(self.st.sigma),
                np.float64(tau), np.int32(self.max_it))
            # the ONE blocking D2H point: H for the final (host, full-f64)
            # Rayleigh-Ritz used for extraction/reporting
            Hh = np.asarray(H, dtype=host_dtype(dtype))
            record_sync("EPS H fetch/solve")
            restarts = int(restarts_a)
            beta, lam_t, S, order, rel, nconv = self._rayleigh_ritz(
                Hh, ncv, nev, hermitian)
            Vh = comm.host_fetch(V)[:ncv]
            record_sync("EPS basis fetch/solve")
            count = max(nev, 1)
            lam, vecs = self._extract(Vh, S, lam_t, order, n, count)
            self._store(lam, vecs, rel[:count], nconv, restarts)
            return

        # ---- host-eigh loop (NHEP Schur ordering, complex-on-TPU,
        # degenerate sizes, and small-n remote solves where the big fused
        # program's compile-cache load outweighs the fetches it saves):
        # seed+factorization and compression+factorization each run as ONE
        # fused program, so a restart costs one dispatch + one small H
        # fetch.
        seed_prog = _build_seed_facto_program(comm, op, ncv, inner)
        restart_prog = _build_restart_facto_program(comm, op, ncv, inner)
        V = None
        H_prefill = np.zeros((ncv + 1, ncv), dtype=dtype)
        S_pad = np.zeros((ncv, ncv), dtype=dtype)
        k = 0

        for restarts in range(1, self.max_it + 1):
            if V is None:
                V, H = seed_prog(op_arrays, b_arrays, v0)
            else:
                V, H = restart_prog(op_arrays, b_arrays, V, H_prefill,
                                    S_pad, np.asarray(k, dtype=np.int32))
            # the ONE blocking D2H point per restart: the small replicated
            # projected matrix (the basis V stays on device; the restart
            # compression runs inside the same program). Counted because
            # this fetch, not the ncv SpMVs, can dominate a restart.
            Hh = np.asarray(H, dtype=host_dtype(dtype))
            record_sync("EPS H fetch/restart")
            beta, lam_t, S, order, rel, nconv = self._rayleigh_ritz(
                Hh, ncv, nev, hermitian)
            if self._monitored():   # guard: args cost O(ncv) per restart
                self._emit_monitor(restarts, nconv,
                                   self.st.back_transform(lam_t[order]),
                                   rel)
            if nconv >= nev or ncv >= n or restarts == self.max_it:
                break

            # ---- thick restart: keep k wanted Ritz/Schur directions --------
            k = k_keep
            if hermitian:
                take = order[:k]
                T_new = np.diag(lam_t[take])
                b_new = beta * S[ncv - 1, take]
                S_keep = S[:, take]
            else:
                Hm = Hh[:ncv, :ncv]
                thresh = np.sort(self._metric(
                    self.st.back_transform(lam_t)))[::-1][k - 1]

                def want(re, im):
                    lam = self.st.back_transform(
                        np.asarray(re + 1j * im))
                    return bool(self._metric(lam) >= thresh - 1e-12)

                T, Z, sdim = _ordered_schur(Hm, want)
                k = int(min(max(sdim, 1), ncv - 1))
                # never cut through a 2x2 (complex-pair) block: T[k, k-1] != 0
                # means rows k-1,k are coupled — truncating there would break
                # the Krylov-Schur relation and poison later residuals
                if 0 < k < ncv and T[k, k - 1] != 0.0:
                    k = k - 1 if k > 1 else min(k + 1, ncv - 1)
                k = int(min(max(k, 1), ncv - 1))
                T_new = T[:k, :k]
                b_new = beta * Z[ncv - 1, :k]
                S_keep = Z[:, :k]

            H_prefill = np.zeros((ncv + 1, ncv), dtype=dtype)
            H_prefill[:k, :k] = T_new
            H_prefill[k, :k] = b_new
            S_pad = np.zeros((ncv, ncv), dtype=dtype)
            S_pad[:, :k] = S_keep

        Vh = comm.host_fetch(V)[:ncv]
        record_sync("EPS basis fetch/solve")
        count = max(nev, 1)
        lam, vecs = self._extract(Vh, S, lam_t, order, n, count)
        self._store(lam, vecs, rel[:count], nconv, restarts)

    # ---- explicitly-restarted arnoldi ---------------------------------------
    def _solve_arnoldi_explicit(self):
        comm, op, inner, hermitian = self._setup_operator()
        n = op.shape[0]
        ncv = self._effective_ncv(n)
        nev = min(self.nev, ncv)
        seed_prog = _build_seed_facto_program(comm, op, ncv, inner)
        restart_prog = _build_arnoldi_restart_facto_program(comm, op, ncv,
                                                           inner)
        op_arrays = op.device_arrays()
        b_arrays = inner.device_arrays() if inner is not None else ()

        dtype = np.dtype(str(op.dtype))
        V = None
        wanted = None

        for restarts in range(1, self.max_it + 1):
            if V is None:
                V, H = seed_prog(op_arrays, b_arrays, comm.put_rows(
                    self._start_vector(comm, n, dtype)))
            else:
                V, H = restart_prog(op_arrays, b_arrays, V, wanted)
            Hh = np.asarray(H, dtype=host_dtype(dtype))
            record_sync("EPS H fetch/restart")
            beta, lam_t, S, order, rel, nconv = self._rayleigh_ritz(
                Hh, ncv, nev, hermitian)
            if self._monitored():   # guard: args cost O(ncv) per restart
                self._emit_monitor(restarts, nconv,
                                   self.st.back_transform(lam_t[order]),
                                   rel)
            if nconv >= nev or ncv >= n or restarts == self.max_it:
                break
            # restart vector: combination of wanted, not-yet-converged Ritz
            # directions, formed on device (the basis stays in HBM).
            # Real dtype needs a real vector (complex-pair Ritz columns
            # collapse to their real part); complex dtype keeps the full
            # combination.
            comb = S[:, order[:nev]].sum(axis=1)
            wanted = (comb if is_complex(dtype) else comb.real).astype(dtype)

        Vh = comm.host_fetch(V)[:ncv]
        record_sync("EPS basis fetch/solve")
        count = max(nev, 1)
        lam, vecs = self._extract(Vh, S, lam_t, order, n, count)
        self._store(lam, vecs, rel[:count], nconv, restarts)

    # ---- power iteration ----------------------------------------------------
    def _solve_power(self):
        self._dominant_only("power")
        comm, op, inner, hermitian = self._setup_operator()
        if inner is not None:
            raise ValueError("EPS 'power' supports standard problems only "
                             "(use krylovschur for GHEP)")
        n = op.shape[0]
        steps = 8
        prog = _build_power_program(comm, op, steps)
        op_arrays = op.device_arrays()
        dtype = np.dtype(str(op.dtype))
        v = comm.put_rows(self._start_vector(comm, n, dtype))

        theta = 0.0
        rel = np.inf
        its = 0
        for chunk in range(1, self.max_it + 1):
            v, theta_a, res_a = prog(op_arrays, v)
            theta = (complex(theta_a) if is_complex(dtype)
                     else float(theta_a))
            res = float(res_a)
            record_sync("EPS power fetch/chunk", 2)
            rel = res / max(abs(theta), 1e-300)
            its = chunk * steps
            if self._monitored():
                self._emit_monitor(
                    its, 1 if rel <= self.tol else 0,
                    self.st.back_transform(np.asarray([theta])), [rel])
            if rel <= self.tol:
                break

        lam = self.st.back_transform(np.asarray([theta]))
        vec = comm.host_fetch(v)[:n]
        record_sync("EPS basis fetch/solve")
        nrm = np.linalg.norm(vec)
        vec = vec / (nrm if nrm else 1.0)
        self._store(lam, vec[None, :], [rel], 1 if rel <= self.tol else 0,
                    its)

    # ---- subspace iteration --------------------------------------------------
    def _solve_subspace(self):
        self._dominant_only("subspace")
        comm, op, inner, hermitian = self._setup_operator()
        if inner is not None:
            raise ValueError("EPS 'subspace' supports standard problems only "
                             "(use krylovschur for GHEP)")
        n = op.shape[0]
        _SUBSPACE_NCV_CAP = 32   # the block spmvs are statically unrolled
        if (self.ncv is not None and self.ncv > _SUBSPACE_NCV_CAP) or \
                self.nev > _SUBSPACE_NCV_CAP:
            raise ValueError(
                f"EPS 'subspace' caps ncv at {_SUBSPACE_NCV_CAP} (the block "
                "operator applications are unrolled into one program) — "
                "use krylovschur for larger subspaces")
        ncv = min(self._effective_ncv(n), _SUBSPACE_NCV_CAP)
        nev = min(self.nev, ncv)
        op_arrays = op.device_arrays()
        dtype = np.dtype(str(op.dtype))
        npad = comm.padded_size(n)
        rng = np.random.default_rng(20240901)
        Y = rng.standard_normal((ncv, npad)).astype(dtype)
        Y[:, n:] = 0.0

        # ---- fused whole-solve path: every iteration's orthonormalization
        # and ncv×ncv projected eigh run ON DEVICE inside one while_loop
        # program — O(1) sync points/solve (same gating as krylovschur)
        if (hermitian and _want_fused(comm, n)
                and not self._monitored()
                and _device_eigh_trustworthy(comm, dtype)
                and _device_matmul_trustworthy(comm, dtype)):
            sprog = _build_subspace_loop_program(
                comm, op, ncv, nev, which=self._which,
                st_type=self.st.get_type())
            tau = 0.0 if self._target is None else float(self._target)
            X, lam_t, rel, it_a, nconv_a = sprog(
                op_arrays, comm.put_spec(Y, P(None, comm.axis)),
                np.float64(self.tol), np.float64(self.st.sigma),
                np.float64(tau), np.int32(self.max_it))
            Xh = comm.host_fetch(X)[:, :n]
            lam_t, rel, it, nconv = (np.asarray(lam_t), np.asarray(rel),
                                     int(it_a), int(nconv_a))
            record_sync("EPS subspace fused fetch/solve")
            count = max(nev, 1)
            lam = self.st.back_transform(lam_t[:count])
            vecs = Xh[:count]
            nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
            nrm[nrm == 0] = 1.0
            self._store(lam, vecs / nrm, rel[:count], nconv, it)
            return

        prog = _build_block_mult_program(comm, op, ncv)
        for it in range(1, self.max_it + 1):
            Q = np.linalg.qr(Y[:, :n].T)[0].T        # (ncv, n) orthonormal rows
            Qp = np.zeros((ncv, npad), dtype=dtype)
            Qp[:, :n] = Q
            W = comm.host_fetch(prog(op_arrays, comm.put_spec(Qp, P(None, comm.axis))))
            record_sync("EPS subspace fetch/iter")
            # Hm[i,j] = <q_i, A q_j> (conjugate on the projector row)
            Hm = Q.conj() @ W[:, :n].T
            if hermitian:
                Hm = (Hm + Hm.conj().T) / 2.0
                lam_t, S = np.linalg.eigh(Hm)
            else:
                lam_t, S = np.linalg.eig(Hm)
            order = self._select(self.st.back_transform(lam_t))
            X = (S[:, order].T @ Q)                   # Ritz rows (ncv, n)
            AX = (S[:, order].T @ W[:, :n])
            R = AX - lam_t[order][:, None] * X
            rel = (np.linalg.norm(R, axis=1)
                   / np.maximum(np.abs(lam_t[order]), 1e-300))
            nconv = 0
            while nconv < nev and rel[nconv] <= self.tol:
                nconv += 1
            if self._monitored():
                self._emit_monitor(it, nconv,
                                   self.st.back_transform(lam_t[order]),
                                   rel)
            if nconv >= nev or it == self.max_it:
                break
            Y = np.zeros((ncv, npad), dtype=dtype)
            # power step: Y <- A Q (real dtypes drop the spurious imaginary
            # parts complex-pair arithmetic can introduce; complex keep all)
            Y[:, :n] = (W[:, :n] if is_complex(dtype)
                        else np.real(W[:, :n]))

        count = max(nev, 1)
        lam = self.st.back_transform(lam_t[order[:count]])
        vecs = X[:count]
        nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
        nrm[nrm == 0] = 1.0
        self._store(lam, vecs / nrm, rel[:count], nconv, it)

    # ---- LOBPCG --------------------------------------------------------------
    def _solve_lobpcg(self):
        """Locally Optimal Block Preconditioned CG (Knyazev 2001; EPSLOBPCG).

        Extreme eigenpairs of a Hermitian (or generalized Hermitian) pencil:
        each iteration Rayleigh-Ritzes over the 3m-dimensional trial space
        span[X, T·R, P] (iterates, preconditioned residuals, previous search
        directions). The m-row block operator applications run on the mesh
        (one compiled program, same block-mult kernel as EPS 'subspace'); the
        3m×3m projected problem is host LAPACK. The preconditioner T is
        inverse-diagonal (Jacobi) when the operator exposes a diagonal,
        identity otherwise — the analog of SLEPc's default STPRECOND.

        Restricted to ``which`` in {smallest_real, largest_real}: LOBPCG
        converges to extreme ends of the spectrum only (SLEPc's EPSLOBPCG has
        the same restriction).
        """
        import scipy.linalg
        if self._problem_type not in (EPSProblemType.HEP,
                                      EPSProblemType.GHEP):
            raise ValueError("EPS 'lobpcg' needs a Hermitian problem type "
                             "(hep/ghep)")
        if self._which not in (EPSWhich.SMALLEST_REAL, EPSWhich.LARGEST_REAL):
            raise ValueError(
                "EPS 'lobpcg' computes extreme eigenvalues — set "
                "which='smallest_real' or 'largest_real' (got "
                f"{self._which!r}); krylovschur supports all selections")
        if not self.st.is_identity():
            raise ValueError("EPS 'lobpcg' supports no spectral transform — "
                             "use krylovschur with ST 'sinvert'")
        comm = self._mat.comm
        op = self._mat
        bop = self._bmat
        n = op.shape[0]
        _LOBPCG_BS_CAP = 16   # block spmvs are statically unrolled
        m = min(max(self.nev, 1), _LOBPCG_BS_CAP, n)
        if self.nev > _LOBPCG_BS_CAP:
            raise ValueError(
                f"EPS 'lobpcg' caps the block size at {_LOBPCG_BS_CAP} — "
                "use krylovschur for more pairs")
        dtype_ = np.dtype(str(op.dtype))

        # ---- fused whole-solve path: the 3m-row trial-space
        # orthonormalization and the 3m×3m projected pencil (whitened,
        # eigh) run ON DEVICE inside one while_loop program — O(1) sync
        # points/solve (same gating as the other fused loops)
        if (_want_fused(comm, n) and not self._monitored()
                and _device_eigh_trustworthy(comm, dtype_)
                and _device_matmul_trustworthy(comm, dtype_)):
            npad_ = comm.padded_size(n)
            X0, dinv = _lobpcg_seed(op, n, m, dtype_)
            X0p = np.zeros((m, npad_), dtype=dtype_)
            X0p[:, :n] = X0
            lprog = _build_lobpcg_loop_program(
                comm, op, bop, m, self.nev,
                largest=(self._which == EPSWhich.LARGEST_REAL))
            b_arrays_ = bop.device_arrays() if bop is not None else ()
            X, theta, rel, it_a, nconv_a = lprog(
                op.device_arrays(), b_arrays_,
                comm.put_rows(dinv.astype(dtype_)),
                comm.put_spec(X0p, P(None, comm.axis)),
                np.float64(self.tol), np.int32(self.max_it))
            Xh = comm.host_fetch(X)[:, :n]
            theta, rel = np.asarray(theta), np.asarray(rel)
            it, nconv = int(it_a), int(nconv_a)
            record_sync("EPS lobpcg fused fetch/solve")
            sign_ = -1.0 if self._which == EPSWhich.LARGEST_REAL else 1.0
            order = np.argsort(sign_ * theta, kind="stable")
            count = max(min(self.nev, m), 1)
            take = order[:count]
            vecs = Xh[take]
            nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
            nrm[nrm == 0] = 1.0
            self._store(theta[take], vecs / nrm, rel[take], nconv, it)
            return

        prog = _build_block_mult_program(comm, op, m)
        bprog = (_build_block_mult_program(comm, bop, m)
                 if bop is not None else None)
        op_arrays = op.device_arrays()
        dtype = np.dtype(str(op.dtype))
        npad = comm.padded_size(n)

        hdt = host_dtype(dtype)

        def block_apply(which_prog, arrays, M_host):
            """Host (m, n) block -> device block program -> host (m, n)."""
            Mp = np.zeros((m, npad), dtype=dtype)
            Mp[:, :n] = M_host
            out = comm.host_fetch(
                which_prog(arrays, comm.put_spec(Mp, P(None, comm.axis))))
            record_sync("EPS lobpcg fetch/block-mult")
            return out[:, :n].astype(hdt)

        A_apply = lambda Mh: block_apply(prog, op_arrays, Mh)
        if bop is not None:
            b_arrays = bop.device_arrays()
            B_apply = lambda Mh: block_apply(bprog, b_arrays, Mh)
        else:
            B_apply = lambda Mh: Mh

        X, dinv_h = _lobpcg_seed(op, n, m, dtype)
        T_apply = lambda Rh: Rh * dinv_h[None, :]

        sign = -1.0 if self._which == EPSWhich.LARGEST_REAL else 1.0
        Pdir = np.zeros((0, n), dtype=hdt)
        theta = np.zeros(m)
        rel = np.full(m, np.inf)
        nconv = 0

        def rr_basis(S):
            """Drop near-dependent rows (rank-revealing QR), orthonormalize."""
            Q, R, _ = scipy.linalg.qr(S.T, mode="economic", pivoting=True)
            d = np.abs(np.diag(R))
            keep = d > max(d[0], 1e-300) * 1e-12
            return Q[:, keep].T

        it = 0
        AX = BX = None
        for it in range(1, self.max_it + 1):
            if AX is None:        # later iterations reuse Cᵀ(AS)/Cᵀ(BS)
                AX = A_apply(X)
                BX = B_apply(X)
            # current Ritz values of the block (Rayleigh quotients <x,Ax>/
            # <x,Bx> with the Hermitian inner product — real for HEP/GHEP)
            theta = np.real(np.sum(X.conj() * AX, axis=1)
                            / np.sum(X.conj() * BX, axis=1))
            R = AX - theta[:, None] * BX
            rel = (np.linalg.norm(R, axis=1)
                   / np.maximum(np.abs(theta), 1e-300))
            order0 = np.argsort(sign * theta, kind="stable")
            nconv = 0
            while nconv < min(self.nev, m) and rel[order0[nconv]] <= self.tol:
                nconv += 1
            if self._monitored():
                # guarded like the krylovschur/arnoldi/subspace sites: the
                # fancy-indexed args are O(m) work per iteration that an
                # unmonitored solve must not pay (ADVICE r5)
                self._emit_monitor(it, nconv, theta[order0], rel[order0])
            if nconv >= min(self.nev, m) or it == self.max_it:
                break
            W = T_apply(R)
            S = rr_basis(np.vstack([X, W, Pdir]) if len(Pdir)
                         else np.vstack([X, W]))
            AS = _apply_blocked(S, A_apply, m)
            BS = _apply_blocked(S, B_apply, m) if bop is not None else S
            # projected pencil in the Hermitian inner product (conj on the
            # projector rows; plain .T would not even be Hermitian for
            # complex operators)
            Ag = S.conj() @ AS.T
            Bg = S.conj() @ BS.T
            Ag = (Ag + Ag.conj().T) / 2.0
            Bg = (Bg + Bg.conj().T) / 2.0
            lam_g, C = scipy.linalg.eigh(sign * Ag, Bg)
            C = C[:, :m]                      # m best in the wanted direction
            Xn = C.T @ S
            # new search directions: the part of Xn outside span(X)
            Pdir = Xn - (Xn @ X.conj().T) @ X
            nrm = np.linalg.norm(Pdir, axis=1)
            Pdir = Pdir[nrm > 1e-12]
            # Xn's rows are the Ritz vectors (B-orthonormal: Cᵀ Bg C = I) —
            # re-orthonormalizing with plain QR would MIX them and stall
            # generalized problems. A(Xn)/B(Xn) come free from the projected
            # basis images — two device block-mults saved per iteration.
            X = Xn
            AX = C.T @ AS
            BX = (C.T @ BS) if bop is not None else Xn

        order = np.argsort(sign * theta, kind="stable")
        count = max(min(self.nev, m), 1)
        take = order[:count]
        vecs = X[take]
        nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
        nrm[nrm == 0] = 1.0
        self._store(theta[take], vecs / nrm, rel[take], nconv, it)

    # ---- gd (block generalized Davidson — SLEPc's EPSGD) ---------------------
    def _solve_gd(self):
        """Block generalized Davidson (EPSGD analog), Hermitian problems.

        Outer iteration: Rayleigh-Ritz over the growing subspace V, then
        expand V with the Jacobi-preconditioned residuals of the ``m``
        current Ritz pairs (SLEPc's default STPRECOND diagonal
        preconditioner [external, behind ``-eps_type gd`` through
        petsc_funcs.py:17]), restarting to the best Ritz vectors when the
        basis reaches ``ncv``. Block operator applications run on the mesh
        (the 'subspace'/'lobpcg' block-mult program — one device call per
        outer iteration); the k×k projected problem is host LAPACK.
        Rank-deficient expansion rows are reseeded (the round-4 ADVICE
        discipline) so a degenerated block cannot stall.

        Extreme ``which`` only, like EPSLOBPCG; no spectral transform
        (use krylovschur + ST 'sinvert' for interior pairs).
        """
        import scipy.linalg
        if self._problem_type != EPSProblemType.HEP:
            raise ValueError("EPS 'gd' supports problem type 'hep' — use "
                             "lobpcg for GHEP, krylovschur for NHEP")
        if self._which not in (EPSWhich.SMALLEST_REAL, EPSWhich.LARGEST_REAL):
            raise ValueError(
                "EPS 'gd' computes extreme eigenvalues — set "
                "which='smallest_real' or 'largest_real' (got "
                f"{self._which!r}); krylovschur supports all selections")
        if not self.st.is_identity():
            raise ValueError("EPS 'gd' supports no spectral transform — "
                             "use krylovschur with ST 'sinvert'")
        comm = self._mat.comm
        op = self._mat
        n = op.shape[0]
        _GD_BS_CAP = 16
        if self.nev > _GD_BS_CAP:
            raise ValueError(
                f"EPS 'gd' caps the block size at {_GD_BS_CAP} — use "
                "krylovschur for more pairs")
        if self.gd_blocksize > _GD_BS_CAP:
            # same limit, same signal as nev — never a silent clamp
            raise ValueError(
                f"-eps_gd_blocksize {self.gd_blocksize} exceeds the "
                f"{_GD_BS_CAP} cap (block spmvs are statically unrolled)")
        # -eps_gd_blocksize widens the expansion block past nev (never
        # below it: the first nev Ritz pairs are the convergence targets)
        m = min(max(self.gd_blocksize, self.nev, 1), n)
        dtype = np.dtype(str(op.dtype))
        hdt = host_dtype(dtype)
        npad = comm.padded_size(n)
        # the restart bound honors a user ncv exactly (docstring contract):
        # an explicit ncv that leaves no room for even one new direction
        # past the block is an ERROR, not a silent raise to m+1 — the
        # _GD_BS_CAP discipline (ADVICE r5)
        if self.ncv is not None and min(self.ncv, n) <= m < n:
            raise ValueError(
                f"EPS 'gd': ncv ({self.ncv}) must exceed the expansion "
                f"block size ({m}) — raise -eps_ncv or shrink "
                "-eps_gd_blocksize/nev")
        mmax = min(n, max(self._effective_ncv(n), m + 1))
        sign = -1.0 if self._which == EPSWhich.LARGEST_REAL else 1.0

        prog = _build_block_mult_program(comm, op, m)
        op_arrays = op.device_arrays()

        def A_apply(Mh):
            """(t, n) host block, t <= m -> A @ rows; the device program is
            built for m rows, so short blocks pad with zero rows."""
            t = Mh.shape[0]
            Mp = np.zeros((m, npad), dtype=dtype)
            Mp[:t, :n] = Mh
            out = comm.host_fetch(
                prog(op_arrays, comm.put_spec(Mp, P(None, comm.axis))))
            record_sync("EPS gd fetch/block-mult")
            return out[:t, :n].astype(hdt)

        rng = np.random.default_rng(20240901)
        X0, _ = _lobpcg_seed(op, n, m, dtype)
        try:
            diag = np.asarray(op.diagonal(), dtype=hdt)
        except (ValueError, AttributeError):
            diag = np.zeros(n, dtype=hdt)
        V = X0.astype(hdt)                 # (k, n) orthonormal rows
        W = A_apply(V)                     # A V, maintained incrementally
        theta = np.zeros(m)
        rel = np.full(m, np.inf)
        X = V[:m]
        nconv, it = 0, 0
        for it in range(1, self.max_it + 1):
            H = np.conj(V) @ W.T           # V^H A V (rows are vectors)
            H = (H + H.conj().T) / 2.0
            mu, S = scipy.linalg.eigh(sign * H)
            # first m of eigh(sign·H) ascending = the m most-wanted pairs
            # in the wanted direction for either sign
            theta = np.real(sign * mu[:m])
            S = S[:, :m]
            X = S.T @ V                    # Ritz vectors (m, n)
            AX = S.T @ W
            R = AX - theta[:, None] * X
            rnorm = np.linalg.norm(R, axis=1)
            # relative residual with the siblings' tiny-eigenvalue floor
            # (max(|theta|, 1) would quietly turn it absolute for
            # |lambda| < 1)
            rel = rnorm / np.maximum(np.abs(theta), 1e-300)
            # contiguous count: slepc4py semantics — the FIRST nconv
            # stored pairs are the converged ones
            nconv = 0
            while nconv < min(self.nev, m) and rel[nconv] <= self.tol:
                nconv += 1
            if self._monitored():          # same guard as the sibling sites
                self._emit_monitor(it, nconv, theta, rel)
            if nconv >= min(self.nev, m) or it == self.max_it:
                break                      # no discarded final expansion
            if V.shape[0] + 1 > mmax:
                # thick restart: keep the current Ritz block (already
                # orthonormal — S has orthonormal columns)
                V, W = X.copy(), AX.copy()
            # expansion: up to m preconditioned residuals, bounded by the
            # ncv window AND the space dimension (a basis cannot exceed n
            # orthonormal rows)
            t_rows = min(m, mmax - V.shape[0], n - V.shape[0])
            if t_rows <= 0:
                break                      # basis spans the whole space
            # Davidson's diagonal correction t_i = (D − θ_i I)⁻¹ r_i —
            # dramatically better than plain D⁻¹ for extreme pairs (the
            # correction SLEPc's GD applies through its shifted STPRECOND
            # [external]); near-zero denominators clamp to a floor so a
            # Ritz value sitting ON a diagonal entry cannot blow up
            denom = diag[None, :] - theta[:, None]
            floor = 1e-3 * np.maximum(np.abs(theta[:, None]), 1.0)
            denom = np.where(np.abs(denom) < floor,
                             np.where(denom >= 0, floor, -floor), denom)
            T = (R / denom)[:t_rows]
            for _ in range(2):             # two-pass MGS vs V's rows
                T = T - (T @ V.conj().T) @ V
            good = np.linalg.norm(T, axis=1) > 1e-10
            if not np.all(good):
                # reseed degenerated rows instead of letting them vanish
                reseed = rng.standard_normal((int(np.sum(~good)), n))
                if is_complex(dtype):
                    reseed = reseed + 1j * rng.standard_normal(reseed.shape)
                T[~good] = reseed
                for _ in range(2):
                    T = T - (T @ V.conj().T) @ V
            T = np.linalg.qr(T.T)[0].T.astype(hdt)
            V = np.vstack([V, T])
            W = np.vstack([W, A_apply(T)])
        count = max(min(self.nev, m), 1)
        # theta is already most-wanted-first by construction (mu ascending
        # from eigh, sign applied) — no reorder needed
        vecs = X[:count]
        nrm = np.linalg.norm(vecs, axis=1, keepdims=True)
        nrm[nrm == 0] = 1.0
        self._store(theta[:count], vecs / nrm, rel[:count], nconv, it)

    # ---- results (slepc4py-shaped, collective-safe) --------------------------
    def get_converged(self) -> int:
        return self._nconv

    getConverged = get_converged

    def get_iteration_number(self) -> int:
        return self.result.iterations

    getIterationNumber = get_iteration_number

    def get_dimensions(self):
        """(nev, ncv) — slepc4py's getDimensions, ncv resolved from the
        auto rule when unset (never None, like slepc4py)."""
        if self._mat is not None:     # the size the solver actually uses
            return (self.nev, self._effective_ncv(self._mat.shape[0]))
        if self.ncv is not None:
            return (self.nev, self.ncv)
        return (self.nev, max(2 * self.nev, self.nev + 15))

    getDimensions = get_dimensions

    def get_tolerances(self):
        """(tol, max_it) — slepc4py's getTolerances."""
        return (self.tol, self.max_it)

    getTolerances = get_tolerances

    def get_eigenvalue(self, i: int):
        lam = self._eigenvalues[i]
        return complex(lam)

    getEigenvalue = get_eigenvalue

    def get_eigenpair(self, i: int, vr: Vec | None = None,
                      vi: Vec | None = None):
        """Fill ``vr``/``vi`` with the i-th eigenvector and return lambda.

        Host-replicated — safe to call from any control context (the
        reference calls SLEPc's collective version rank-0-only, test2.py:94-96,
        which is a latent deadlock this design removes).
        """
        lam = complex(self._eigenvalues[i])
        vec = self._eigenvectors[i]
        if vr is not None and is_complex(vr.dtype):
            # complex-build semantics (slepc4py): vr carries the full
            # complex eigenvector, vi is unused (zeroed here)
            vr.set_global(vec)
            if vi is not None:
                vi.set_global(np.zeros_like(vec))
            return lam
        if vr is not None:
            vr.set_global(np.real(vec))
        if vi is not None:
            vi.set_global(np.imag(vec))
        return lam

    getEigenpair = get_eigenpair

    def get_error_estimate(self, i: int) -> float:
        return float(self._residuals[i])

    getErrorEstimate = get_error_estimate

    def compute_error(self, i: int, error_type: str = "relative") -> float:
        """EPSComputeError: the TRUE residual of the i-th eigenpair.

        Recomputes ``||A v - λ v||`` (or ``||A v - λ B v||`` for
        generalized problems) with the stored operator — independent of the
        solver's internal estimate (:meth:`get_error_estimate`).
        ``error_type``: ``'absolute'`` or ``'relative'`` (divide by |λ|,
        SLEPc's default).
        """
        lam = complex(self._eigenvalues[i])
        vec = np.asarray(self._eigenvectors[i])
        A = self._mat
        if A is None:
            raise RuntimeError("compute_error: no operators set")

        def apply(op, v):
            vv = Vec.from_global(self.comm, v, dtype=op.dtype)
            return np.asarray(op.mult(vv).to_numpy(),
                              dtype=host_dtype(op.dtype))

        if is_complex(A.dtype):
            # complex operator: apply to the complex vector directly
            Av = apply(A, vec)
            Bv = apply(self._bmat, vec) if self._bmat is not None else vec
            r = Av - lam * Bv
        else:
            vr, vi = np.real(vec), np.imag(vec)
            # apply to the real and imaginary parts separately (real
            # operators; complex pairs only arise for NHEP)
            Avr = apply(A, vr)
            Avi = apply(A, vi) if np.any(vi) else np.zeros_like(Avr)
            if self._bmat is not None:
                Bvr = apply(self._bmat, vr)
                Bvi = (apply(self._bmat, vi) if np.any(vi)
                       else np.zeros_like(Bvr))
            else:
                Bvr, Bvi = vr, vi
            r = (Avr + 1j * Avi) - lam * (Bvr + 1j * Bvi)
        err = float(np.linalg.norm(r))
        t = str(error_type).lower()
        if t in ("relative", "eps_error_relative"):
            return err / max(abs(lam), np.finfo(np.float64).tiny)
        if t in ("absolute", "eps_error_absolute"):
            return err
        raise ValueError(f"unknown error type {error_type!r}")

    computeError = compute_error

    def __repr__(self):
        return (f"EPS(type={self._type!r}, problem={self._problem_type!r}, "
                f"nev={self.nev}, which={self._which!r}, tol={self.tol})")


def _ordered_schur(Hm: np.ndarray, want):
    """Schur form with the wanted eigenvalues ordered first.

    ``want(re, im) -> bool``. Real input: real Schur form — LAPACK keeps
    2x2 (complex-pair) blocks intact, so the returned ``sdim`` may differ
    from the requested count by one. Complex input: complex (triangular)
    Schur form — no 2x2 blocks exist, scipy's sort callback receives one
    complex argument.
    """
    import scipy.linalg
    if np.iscomplexobj(Hm):
        T, Z, sdim = scipy.linalg.schur(
            Hm, output="complex", sort=lambda lam: want(lam.real, lam.imag))
        return T, Z, sdim
    T, Z, sdim = scipy.linalg.schur(Hm, output="real", sort=want)
    return T, Z, sdim
