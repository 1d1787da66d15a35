"""Parallel cyclic reduction (PCR) — the scalable direct solver for
tridiagonal operators.

The reference's MUMPS slot (``test.py:41-43``: PC 'lu' +
``setFactorSolverType('mumps')``) factorizes arbitrarily large sparse
systems; a general multifrontal solver has no TPU-friendly equivalent
(SURVEY.md §7.4-1), but the *banded* family the reference itself ships —
``test2.py:6-18`` builds a symmetric tridiagonal — admits cyclic reduction,
which is pure data-parallel arithmetic: ``ceil(log2 n)`` sweeps of shifted
elementwise fused multiply-adds, no elimination tree, no pivot search, no
sequential recursion. Exactly the shape the VPU wants.

Split chosen here (mirrors how the block preconditioners are built):

- **setup on host, fp64** (:func:`pcr_setup`): the coefficient transforms
  of PCR do not involve the right-hand side, so the per-sweep reduction
  multipliers ``(alpha_k, gamma_k)`` and the final diagonal are precomputed
  once per factorization — the analog of MUMPS's symbolic+numeric phase at
  ``ksp.setUp()`` (reference call stack, SURVEY.md §3.1).
- **apply on device** (:func:`pcr_apply`): per solve, ``S = ceil(log2 n)``
  sweeps of ``d += alpha * shift(d, +2^k) + gamma * shift(d, -2^k)`` then
  one divide — O(n log n) work, O(n) memory traffic per sweep, all static
  shapes/shifts so XLA fuses each sweep into one pass.

PCR is pivotless: like Thomas/cyclic-reduction solvers everywhere, it is
exact for diagonally dominant / SPD tridiagonal systems and runs in fp64 by
default; KSPPREONLY's iterative-refinement steps polish the rest (see
``krylov.preonly_kernel``).
"""

from __future__ import annotations

import os

import numpy as np


def _pmap_blocks(fn, *arrays):
    """Apply ``fn`` over chunks of the leading (batch) axis on a host
    thread pool — numpy/LAPACK release the GIL, so batched inversions /
    solves / matmuls scale with cores (round-5 VERDICT item 5: the BPCR
    setup's batched b×b work is embarrassingly parallel). Single-core
    hosts (this dev box: ``nproc`` = 1, PARITY.md 'Direct solves') run
    inline with zero overhead."""
    ncpu = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    N = arrays[0].shape[0]
    if ncpu <= 1 or N < 2 * ncpu:
        return fn(*arrays)
    import concurrent.futures as cf
    bounds = np.linspace(0, N, 2 * ncpu + 1, dtype=int)
    out = None
    with cf.ThreadPoolExecutor(ncpu) as ex:
        futs = {ex.submit(fn, *(a[s:e] for a in arrays)): (s, e)
                for s, e in zip(bounds[:-1], bounds[1:]) if e > s}
        for fut in cf.as_completed(futs):
            s, e = futs[fut]
            res = fut.result()
            if out is None:
                out = np.empty((N,) + res.shape[1:], res.dtype)
            out[s:e] = res
    return out


def _neg_right_div(X, B):
    """``-X @ B^{-1}`` via a batched LAPACK solve — ~30% fewer flops than
    forming the inverse and multiplying (getrf+getrs vs getrf+getri+gemm),
    the setup's inner-loop operation. Raises LinAlgError on singular B."""
    Yt = np.linalg.solve(np.swapaxes(B, -1, -2), -np.swapaxes(X, -1, -2))
    return np.ascontiguousarray(np.swapaxes(Yt, -1, -2))


def pcr_setup(a: np.ndarray, b: np.ndarray, c: np.ndarray,
              apply_dtype=None):
    """Precompute PCR sweep coefficients for the tridiagonal (a, b, c).

    ``a`` is the subdiagonal (a[0] ignored/0), ``b`` the diagonal, ``c``
    the superdiagonal (c[-1] ignored/0), all length n. Setup runs in host
    fp64 (complex inputs: complex128 — the coefficient transforms are
    rational with real constants, so the complex case is the same sweep).

    Returns ``(alphas, gammas, bfin)``: two (S, n) arrays of per-sweep
    neighbour multipliers (S = ceil(log2 n)) and the length-n fully-reduced
    diagonal, such that for any rhs d::

        for k in range(S):
            s = 1 << k
            d = d + alphas[k] * shift_up(d, s) + gammas[k] * shift_down(d, s)
        x = d / bfin

    where ``shift_up(d, s)[i] = d[i-s]`` (zero fill) and ``shift_down``
    mirrors it. Rows beyond either end behave as identity equations.

    ``apply_dtype``: the dtype the device apply will run in. When it is
    lower-precision than the setup dtype, the factorization probe is re-run
    through the cast coefficients — a factorization can pass the fp64 probe
    yet lose its accuracy entirely at fp32 apply time (catastrophic, not
    roundoff-scale: the second probe gates at 0.1 because legitimate
    reduced-precision roundoff is recovered by KSPPREONLY's refinement).
    """
    from ..utils.dtypes import host_dtype
    host_dt = host_dtype(np.result_type(*(np.asarray(v) for v in (a, b, c))))
    a = np.asarray(a, host_dt).copy()
    b = np.asarray(b, host_dt).copy()
    c = np.asarray(c, host_dt).copy()
    n = b.shape[0]
    if n == 0:
        raise ValueError("pcr_setup: empty system")
    a[0] = 0.0
    c[-1] = 0.0
    if np.any(b == 0):
        raise ValueError(
            "PCR hit a zero diagonal entry — the pivotless tridiagonal "
            "reduction needs a nonzero (ideally dominant) diagonal; use an "
            "iterative KSP with pc 'jacobi'/'gamg' instead")
    b0_mul_ones = a + b + c   # A · ones, for the post-setup probe solve
    S = max(1, int(np.ceil(np.log2(n)))) if n > 1 else 1
    alphas = np.zeros((S, n), host_dt)
    gammas = np.zeros((S, n), host_dt)

    def up(v, s):      # v[i-s], identity-row fill
        return np.concatenate([np.zeros(s, host_dt), v[:-s]]) if s < n else \
            np.zeros(n, host_dt)

    def down(v, s):    # v[i+s]
        return np.concatenate([v[s:], np.zeros(s, host_dt)]) if s < n else \
            np.zeros(n, host_dt)

    def upb(v, s):     # diagonal of identity rows is 1, not 0
        return (np.concatenate([np.ones(s, host_dt), v[:-s]]) if s < n
                else np.ones(n, host_dt))

    def downb(v, s):
        return (np.concatenate([v[s:], np.ones(s, host_dt)]) if s < n
                else np.ones(n, host_dt))

    for k in range(S):
        s = 1 << k
        alpha = -a / upb(b, s)
        gamma = -c / downb(b, s)
        alphas[k] = alpha
        gammas[k] = gamma
        a_new = alpha * up(a, s)
        c_new = gamma * down(c, s)
        b_new = b + alpha * up(c, s) + gamma * down(a, s)
        if np.any(b_new == 0) or not np.all(np.isfinite(b_new)):
            raise ValueError(
                "PCR reduction broke down (zero/non-finite reduced "
                "diagonal) — the pivotless factorization is unstable for "
                "this matrix; use an iterative KSP with pc 'jacobi'/'gamg'")
        a, b, c = a_new, b_new, c_new
    if np.any(a != 0) or np.any(c != 0):
        raise AssertionError("PCR did not fully reduce — internal error")
    # factorization probe: zero/inf sweeps are caught above, but pivotless
    # element growth can also destroy accuracy while every intermediate
    # stays finite (e.g. a tiny diagonal under large off-diagonals). Solve
    # one known system (A·1) and demand the answer back — the direct-path
    # analog of MUMPS's backward-error analysis.
    d1 = b0_mul_ones
    x1 = pcr_apply_np(d1, alphas, gammas, b)
    # threshold: catastrophic growth yields errors of order >= 1, while
    # legitimate ill-conditioning stays ~kappa*eps (<= ~1e-4 at kappa 1e12)
    if not np.all(np.isfinite(x1)) or np.max(np.abs(x1 - 1.0)) > 1e-3:
        raise ValueError(
            "PCR factorization failed its probe solve (pivotless element "
            "growth) — this tridiagonal needs a pivoted factorization; use "
            "an iterative KSP with pc 'jacobi'/'gamg' instead")
    if apply_dtype is not None and \
            np.finfo(np.dtype(apply_dtype)).eps > np.finfo(host_dt).eps:
        # second probe through the dtype the device will actually apply:
        # the fp64 gate says nothing about fp32 sweep accuracy. Gate only
        # on catastrophic loss — plain fp32 roundoff (even at moderate
        # conditioning) is what preonly's refinement steps exist for.
        cast = np.dtype(apply_dtype)
        x1c = pcr_apply_np(d1.astype(cast), alphas.astype(cast),
                           gammas.astype(cast), b.astype(cast))
        if not np.all(np.isfinite(x1c)) or np.max(np.abs(x1c - 1.0)) > 0.1:
            raise ValueError(
                f"PCR factorization failed its probe solve in the operator "
                f"dtype {cast} (the fp64 factorization is fine, but the "
                "reduced-precision apply loses it) — assemble the operator "
                "in float64/complex128 or use an iterative KSP")
    return alphas, gammas, b


def pcr_apply_np(d, alphas, gammas, bfin):
    """Host-numpy mirror of :func:`pcr_apply` — used by the setup-time
    factorization probe (and as an oracle in tests). Runs in the common
    dtype of the rhs and the sweep arrays (fp64/complex128 setup probes,
    fp32/complex64 cast-dtype probes)."""
    dt = np.result_type(np.asarray(d).dtype, alphas.dtype)
    d = np.asarray(d, dt).copy()
    n = d.shape[0]
    for k in range(alphas.shape[0]):
        s = 1 << k
        du = np.concatenate([np.zeros(s, dt), d[:-s]]) if s < n else \
            np.zeros(n, dt)
        dd = np.concatenate([d[s:], np.zeros(s, dt)]) if s < n else \
            np.zeros(n, dt)
        d = d + alphas[k] * du + gammas[k] * dd
    return d / bfin


def pcr_apply(d, alphas, gammas, bfin):
    """Device-side PCR solve: apply the precomputed sweeps to rhs ``d``.

    ``d`` is the full-length (n,) rhs; arrays as from :func:`pcr_setup`
    (any common floating dtype). Pure jnp — callable inside jit/shard_map.
    """
    import jax.numpy as jnp

    n = d.shape[0]
    S = alphas.shape[0]
    for k in range(S):
        s = 1 << k
        if s < n:
            du = jnp.concatenate([jnp.zeros((s,), d.dtype), d[:-s]])
            dd = jnp.concatenate([d[s:], jnp.zeros((s,), d.dtype)])
        else:
            du = jnp.zeros_like(d)
            dd = jnp.zeros_like(d)
        d = d + alphas[k] * du + gammas[k] * dd
    return d / bfin


# ---------------------------------------------------------------------------
# BLOCK cyclic reduction: direct solves for bandwidth b > 1
# ---------------------------------------------------------------------------
# A matrix with dia_offsets ⊆ [-b..b] is block-tridiagonal in b×b blocks
# (pentadiagonal = b=2, etc.). The same log2(N) sweep structure applies with
# the scalar divisions replaced by batched b×b inverses/matmuls — exactly
# the MXU-friendly shape: every sweep is two (N, b, b) × (N, b) batched
# products. This extends the MUMPS-slot direct path (reference
# ``test.py:41-43``) from tridiagonal to small-bandwidth banded systems
# (SURVEY.md §7.4-1); general sparsity beyond banded stays iterative+strong
# -PC, documented in PARITY.md.


def banded_to_blocks(A_csr, b: int):
    """Extract block-tridiagonal (sub, diag, super) = (N, b, b) stacks from
    a sparse matrix with bandwidth <= b.

    Rows are grouped b at a time (the tail block is padded with identity
    rows, which decouple). Vectorized over the stored diagonals — no
    per-block slicing.
    """
    n = A_csr.shape[0]
    N = -(-n // b)
    from ..utils.dtypes import host_dtype
    host_dt = host_dtype(A_csr.dtype)
    Ab = np.zeros((N, b, b), host_dt)
    Cb = np.zeros((N, b, b), host_dt)
    Bb = np.zeros((N, b, b), host_dt)
    Bb[:] = np.eye(b, dtype=host_dt)        # padded tail rows stay identity
    # real rows get their true diagonal (dense .diagonal(0) includes zeros)
    for o in range(-b, b + 1):
        vals = np.asarray(A_csr.diagonal(o))
        if o >= 0:
            r = np.arange(0, n - o)
        else:
            r = np.arange(-o, n)
        c = r + o
        i_r, br = r // b, r % b
        i_c, bc = c // b, c % b
        mid = i_c == i_r
        lo = i_c == i_r - 1
        hi = i_c == i_r + 1
        if o == 0:
            # overwrite the identity diagonal for every REAL row first
            Bb[i_r, br, bc] = vals
            continue
        Bb[i_r[mid], br[mid], bc[mid]] = vals[mid]
        Ab[i_r[lo], br[lo], bc[lo]] = vals[lo]
        Cb[i_r[hi], br[hi], bc[hi]] = vals[hi]
    return Ab, Bb, Cb


def bpcr_setup(Ab, Bb, Cb, apply_dtype=None):
    """Precompute block-PCR sweep coefficients for the block-tridiagonal
    ``(Ab, Bb, Cb)`` — each ``(N, b, b)``, ``Ab[0]``/``Cb[-1]`` ignored.

    Returns ``(alphas, gammas, binv)``: two ``(S, N, b, b)`` stacks of
    per-sweep neighbour multiplier blocks (``S = ceil(log2 N)``) and the
    batched inverse of the fully-reduced diagonal, such that for any rhs
    ``D`` of shape (N, b)::

        for k in range(S):
            s = 1 << k
            D = D + alphas[k] @ shift_up(D, s) + gammas[k] @ shift_down(D, s)
        X = binv @ D          # batched (N, b, b) x (N, b)

    Same host-fp64 (complex: complex128) setup + probe-solve discipline as
    the scalar :func:`pcr_setup`; within-block arithmetic is pivoted
    (LAPACK batched inverses), the cross-block elimination is pivotless.
    """
    from ..utils.dtypes import host_dtype
    host_dt = host_dtype(
        np.result_type(*(np.asarray(v) for v in (Ab, Bb, Cb))))
    A = np.asarray(Ab, host_dt).copy()
    B = np.asarray(Bb, host_dt).copy()
    C = np.asarray(Cb, host_dt).copy()
    N, b = B.shape[0], B.shape[1]
    if N == 0:
        raise ValueError("bpcr_setup: empty system")
    A[0] = 0.0
    C[-1] = 0.0
    ones_b = np.ones(b, host_dt)
    d1 = (A + B + C) @ ones_b               # A · ones, for the probe solve
    S = max(1, int(np.ceil(np.log2(N)))) if N > 1 else 1
    alphas = np.zeros((S, N, b, b), host_dt)
    gammas = np.zeros((S, N, b, b), host_dt)

    def shift(M, s, fill_identity=False):
        """out[i] = M[i - s] (s may be negative); out-of-range blocks are
        zero (identity when fill_identity — the virtual rows' diagonal)."""
        out = np.zeros_like(M)
        if fill_identity:
            out[:] = np.eye(b, dtype=host_dt)
        if abs(s) < N:
            if s > 0:
                out[s:] = M[:-s]
            elif s < 0:
                out[:s] = M[-s:]
            else:
                out[:] = M
        return out

    def binv_or_raise(M, what):
        try:
            return _pmap_blocks(np.linalg.inv, M)
        except np.linalg.LinAlgError:
            raise ValueError(
                f"block PCR hit a singular {what} block — the pivotless "
                "cross-block reduction needs nonsingular (ideally "
                "dominant) diagonal blocks; use an iterative KSP with pc "
                "'jacobi'/'gamg' instead") from None

    for k in range(S):
        s = 1 << k
        # alpha = -A Bu^{-1}, gamma = -C Bd^{-1}: batched right-division
        # (no explicit inverses — _neg_right_div), chunked across host
        # cores (_pmap_blocks); both are the setup's dominant cost
        try:
            alpha = _pmap_blocks(_neg_right_div, A,
                                 shift(B, s, fill_identity=True))
            gamma = _pmap_blocks(_neg_right_div, C,
                                 shift(B, -s, fill_identity=True))
        except np.linalg.LinAlgError:
            raise ValueError(
                "block PCR hit a singular shifted block — the pivotless "
                "cross-block reduction needs nonsingular (ideally "
                "dominant) diagonal blocks; use an iterative KSP with pc "
                "'jacobi'/'gamg' instead") from None
        alphas[k] = alpha
        gammas[k] = gamma
        A_new = _pmap_blocks(np.matmul, alpha, shift(A, s))
        C_new = _pmap_blocks(np.matmul, gamma, shift(C, -s))
        B_new = (B + _pmap_blocks(np.matmul, alpha, shift(C, s))
                 + _pmap_blocks(np.matmul, gamma, shift(A, -s)))
        if not np.all(np.isfinite(B_new)):
            raise ValueError(
                "block PCR reduction broke down (non-finite reduced "
                "diagonal) — the pivotless cross-block factorization is "
                "unstable for this matrix; use an iterative KSP with pc "
                "'jacobi'/'gamg' instead")
        A, B, C = A_new, B_new, C_new
    if np.any(A != 0) or np.any(C != 0):
        raise AssertionError("block PCR did not fully reduce — internal "
                             "error")
    binv = binv_or_raise(B, "reduced diagonal")
    # probe solve (the MUMPS backward-error analog, as in pcr_setup)
    x1 = bpcr_apply_np(d1, alphas, gammas, binv)
    if not np.all(np.isfinite(x1)) or np.max(np.abs(x1 - 1.0)) > 1e-3:
        raise ValueError(
            "block PCR factorization failed its probe solve (pivotless "
            "cross-block element growth) — this banded system needs a "
            "pivoted factorization; use an iterative KSP with pc "
            "'jacobi'/'gamg' instead")
    if apply_dtype is not None and \
            np.finfo(np.dtype(apply_dtype)).eps > np.finfo(host_dt).eps:
        cast = np.dtype(apply_dtype)
        x1c = bpcr_apply_np(d1.astype(cast), alphas.astype(cast),
                            gammas.astype(cast), binv.astype(cast))
        if not np.all(np.isfinite(x1c)) or np.max(np.abs(x1c - 1.0)) > 0.1:
            raise ValueError(
                f"block PCR factorization failed its probe solve in the "
                f"operator dtype {cast} — assemble the operator in "
                "float64/complex128 or use an iterative KSP")
    return alphas, gammas, binv


_BPCR_SETUP_PROGRAMS: dict = {}   # (N, b, S, nnz, dt, cdt, mesh) -> jit fn


def bpcr_setup_device_csr(A_csr, b: int, comm, dtype, timings=None):
    """Device-side block-PCR factorization from the banded CSR itself —
    the production route (:func:`bpcr_setup_device` wraps dense stacks
    for tests/parity).

    Ships only the COO triplets (~16 bytes/nnz — a 256² RCM-Poisson is
    ~6 MB) and scatter-builds the (3, N, b, b) block stacks IN-PROGRAM
    instead of shipping the dense stacks, and this also skips the host
    ``banded_to_blocks`` densification entirely.

    ``timings``: optional dict filled with ``extract_s`` (host triplet
    prep) and ``invert_s`` (ship + program load + device factorization) —
    the same split PC bjacobi's ``setup_breakdown`` records.
    """
    import time
    t0 = time.perf_counter()
    n = A_csr.shape[0]
    N = -(-n // b)
    dt = np.dtype(dtype)
    coo = A_csr.tocoo()
    bi = (coo.row // b).astype(np.int64)
    bj = (coo.col // b).astype(np.int64)
    delta = bj - bi
    if delta.size and (delta.min() < -1 or delta.max() > 1):
        raise ValueError(
            f"bpcr_setup_device_csr: operator bandwidth exceeds the block "
            f"size {b}")
    npad = N * b - n                   # identity diagonal for tail padding
    pad_r = np.arange(n, N * b)
    idx = np.stack([
        np.concatenate([delta + 1, np.ones(npad, np.int64)]),
        np.concatenate([bi, pad_r // b]),
        np.concatenate([coo.row - bi * b, pad_r % b]),
        np.concatenate([coo.col - bj * b, pad_r % b]),
    ], axis=1).astype(np.int32)
    vals = np.concatenate([np.asarray(coo.data, dt), np.ones(npad, dt)])
    t1 = time.perf_counter()
    out = _bpcr_device_factor(comm, dt, N, b, vals, idx)
    if timings is not None:
        timings["extract_s"] = round(t1 - t0, 4)
        timings["invert_s"] = round(time.perf_counter() - t1, 4)
    return out


def bpcr_setup_device(Ab, Bb, Cb, comm, dtype):
    """Device-side block-PCR factorization from dense (N, b, b) stacks
    (``banded_to_blocks`` layout) — triplet-izes the nonzeros and defers
    to the shared :func:`_bpcr_device_factor`."""
    dt = np.dtype(dtype)
    A0 = np.asarray(Ab, dt).copy()
    B0 = np.asarray(Bb, dt)
    C0 = np.asarray(Cb, dt).copy()
    if B0.shape[0] == 0:
        raise ValueError("bpcr_setup_device: empty system")
    A0[0] = 0.0
    C0[-1] = 0.0
    T = np.stack([A0, B0, C0])
    d, bi, rr, cc = np.nonzero(T)
    idx = np.stack([d, bi, rr, cc], axis=1).astype(np.int32)
    return _bpcr_device_factor(comm, dt, B0.shape[0], B0.shape[1],
                               T[d, bi, rr, cc].astype(dt), idx)


def _bpcr_device_factor(comm, dt, N: int, b: int, vals, idx):
    """The round-5 device block-PCR factorization (the VERDICT's 'invert
    on device with refinement' alternative to the host-serial LAPACK
    batch).

    Same reduction as :func:`bpcr_setup`, but the ``S = ceil(log2 N)``
    sweeps run as ONE compiled program of batched (N, b, b) MXU work
    (``lax.fori_loop`` with roll+mask dynamic shifts — a statically
    unrolled version's 9 LU expansions made a ~40 MB executable). Precision discipline matches the host path: the
    reduction arithmetic runs in fp64 (complex128) — on TPU, XLA emulates
    f64 dots at near-f32 MXU throughput — and only the final factors are
    cast to the apply dtype. A pure apply-dtype reduction was measured
    and rejected: fp32 intermediate arithmetic explodes the pivotless
    reduction of the RCM-Poisson family (probe ~4e4) even though the CAST
    fp64 factors apply fine in fp32. XLA:TPU has no F64 LuDecomposition,
    so each block inverse seeds from an F32 (C64) LU and two f64 Newton
    polish steps restore ~1e-9 inverse quality (measured).

    Gating mirrors :func:`bpcr_setup`: the ``A·ones`` probe solve runs on
    device with the fp64 factors (gate 1e-3) AND with the cast factors
    (gate 0.1 — KSPPREONLY's stall-detecting refinement recovers
    reduced-precision roundoff); NaN-proof (XLA's max-reduce drops NaNs).
    Returns ``(alphas, gammas, binv)`` as replicated DEVICE arrays of
    ``dt`` — never fetched to host — or ``None`` when a probe or the
    device path fails (the caller falls back to the host fp64 setup).
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from ..utils.dtypes import host_dtype, is_complex

    cdt = np.dtype(host_dtype(dt))            # f64 / c128 compute dtype
    ldt = np.dtype(np.complex64 if is_complex(dt) else np.float32)  # LU seed
    S = max(1, int(np.ceil(np.log2(N)))) if N > 1 else 1
    eye = np.eye(b, dtype=cdt)
    nidx = np.arange(N)

    def shift_dyn(M, s, fill):
        """out[i] = M[i-s] in-range, else ``fill`` (s traced, ±)."""
        rolled = jnp.roll(M, s, axis=0)
        ok = (nidx >= s) & (nidx < N + s)
        return jnp.where(ok.reshape((N,) + (1,) * (M.ndim - 1)),
                         rolled, fill)

    # f32 seeding is a TPU workaround (no F64 LuDecomposition there);
    # backends with a native f64/c128 LU use it directly — better factors
    # for free. mesh is in the program-cache key, so this can't go stale.
    seed_low = comm.platform == "tpu" and cdt != ldt

    def binv_polished(B):
        if seed_low:
            X = jnp.linalg.inv(B.astype(ldt)).astype(cdt)
        else:
            X = jnp.linalg.inv(B)
        X = X + X @ (eye - B @ X)
        X = X + X @ (eye - B @ X)
        return X

    def probe(al, ga, binv, D):
        def sweep(k, D):
            s = jnp.left_shift(jnp.int32(1), k)
            Du = shift_dyn(D, s, jnp.zeros((), D.dtype))
            Dd = shift_dyn(D, -s, jnp.zeros((), D.dtype))
            return (D + jnp.einsum("nij,nj->ni", al[k], Du)
                    + jnp.einsum("nij,nj->ni", ga[k], Dd))
        D = lax.fori_loop(0, S, sweep, D)
        x1 = jnp.einsum("nij,nj->ni", binv, D)
        return jnp.where(jnp.all(jnp.isfinite(x1)),
                         jnp.max(jnp.abs(x1 - 1.0)), jnp.inf)

    def setup(vals, idx):
        # scatter-build the blocks, upcast, probe rhs: all in-program
        T = jnp.zeros((3, N, b, b), cdt).at[
            idx[:, 0], idx[:, 1], idx[:, 2], idx[:, 3]].add(
                vals.astype(cdt))
        A, B, C = T[0], T[1], T[2]
        d1 = jnp.einsum("nij,j->ni", A + B + C, jnp.ones(b, cdt))
        al0 = jnp.zeros((S, N, b, b), cdt)

        def sweep(k, st):
            A, B, C, al, ga = st
            s = jnp.left_shift(jnp.int32(1), k)
            invB = binv_polished(B)
            alpha = -(A @ shift_dyn(invB, s, eye))
            gamma = -(C @ shift_dyn(invB, -s, eye))
            al = al.at[k].set(alpha)
            ga = ga.at[k].set(gamma)
            zero = jnp.zeros((), cdt)
            A2 = alpha @ shift_dyn(A, s, zero)
            C2 = gamma @ shift_dyn(C, -s, zero)
            B2 = (B + alpha @ shift_dyn(C, s, zero)
                  + gamma @ shift_dyn(A, -s, zero))
            return (A2, B2, C2, al, ga)

        A, B, C, al, ga = lax.fori_loop(0, S, sweep, (A, B, C, al0, al0))
        binv = binv_polished(B)
        q64 = probe(al, ga, binv, d1)
        al_c, ga_c, binv_c = (al.astype(dt), ga.astype(dt),
                              binv.astype(dt))
        qc = probe(al_c, ga_c, binv_c, d1.astype(dt)) \
            if dt != cdt else q64
        finite = (jnp.all(jnp.isfinite(al)) & jnp.all(jnp.isfinite(ga))
                  & jnp.all(jnp.isfinite(binv)))
        q64 = jnp.where(finite, q64, jnp.inf)
        return al_c, ga_c, binv_c, q64, qc

    rep = comm.replicated_sharding
    key = (N, b, S, len(vals), dt.str, cdt.str, comm.mesh)
    fn = _BPCR_SETUP_PROGRAMS.get(key)
    if fn is None:
        # cache the jitted program: a fresh jax.jit per call would retrace
        # every time (same lesson as pc.py's module-level _inv_polish)
        fn = jax.jit(setup, out_shardings=(rep, rep, rep, rep, rep))
        _BPCR_SETUP_PROGRAMS[key] = fn
    # a compile or runtime error propagates; only the probe gate below
    # falls back to the host setup (counted in pc.gate_fallbacks)
    al, ga, binv, q64, qc = fn(comm.put_replicated(vals),
                               comm.put_replicated(idx))
    q64 = float(q64)   # sync: setup-time only, two scalars
    qc = float(qc)
    if not (np.isfinite(q64) and np.isfinite(qc)) \
            or q64 > 1e-3 or qc > 0.1:
        from .pc import gate_fallbacks
        gate_fallbacks["bpcr"] += 1
        import warnings
        warnings.warn(
            f"device block-PCR factorization failed its probe solve "
            f"(max|x-1| = {q64:.2e} in {cdt}, {qc:.2e} cast to {dt}); "
            "using the host fp64 setup", RuntimeWarning, stacklevel=2)
        return None
    return al, ga, binv


def bpcr_apply_np(D, alphas, gammas, binv):
    """Host-numpy mirror of :func:`bpcr_apply` (probe + test oracle).
    ``D``: (N, b) rhs blocks."""
    dt = np.result_type(np.asarray(D).dtype, alphas.dtype)
    D = np.asarray(D, dt).copy()
    N, b = D.shape
    for k in range(alphas.shape[0]):
        s = 1 << k
        Du = np.zeros_like(D)
        Dd = np.zeros_like(D)
        if s < N:
            Du[s:] = D[:-s]
            Dd[:-s] = D[s:]
        D = (D + np.einsum("nij,nj->ni", alphas[k], Du)
             + np.einsum("nij,nj->ni", gammas[k], Dd))
    return np.einsum("nij,nj->ni", binv, D)


def bpcr_apply(d, alphas, gammas, binv):
    """Device-side block-PCR solve: ``d`` is the flat (N*b,) rhs; arrays as
    from :func:`bpcr_setup`. Each sweep is two batched (N, b, b) x (N, b)
    MXU products over static shifts — pure jnp, safe inside jit/shard_map.
    """
    import jax.numpy as jnp

    N, b = binv.shape[0], binv.shape[1]
    D = d.reshape(N, b)
    S = alphas.shape[0]
    for k in range(S):
        s = 1 << k
        if s < N:
            Du = jnp.concatenate([jnp.zeros((s, b), D.dtype), D[:-s]])
            Dd = jnp.concatenate([D[s:], jnp.zeros((s, b), D.dtype)])
        else:
            Du = jnp.zeros_like(D)
            Dd = jnp.zeros_like(D)
        D = (D + jnp.einsum("nij,nj->ni", alphas[k], Du)
             + jnp.einsum("nij,nj->ni", gammas[k], Dd))
    return jnp.einsum("nij,nj->ni", binv, D).reshape(-1)
