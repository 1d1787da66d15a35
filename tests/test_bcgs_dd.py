"""fp64 BiCGStab as double-f32 Pallas sweeps (solvers/bcgs_dd.py), on the
CPU in interpret mode.

Pins, on ``convdiff2d(32)`` with 16 ILU(0) blocks of 2 lines (two groups
of 8):

* each sweep of the loop (the PC step with its p or s update, the two
  SpMVs with their dots, the x and r update with its dots, the first
  residual) against a numpy fp64 reference, to 1e-13;
* the pair product and sum (ops/double_f32.py) in a kernel, against
  fp64, to 2**-45;
* the ILU(0) kernel's (hi, lo) entry point against its fp64 one;
* which BCGS programs take the loop: fp64 DIA five-point operators on
  one device with PC bjacobi on the kernel's stack, where S1's kernel
  call fits VMEM; fp32, complex128, ELL, 8 devices, dense bjacobi
  blocks, PC jacobi or none and a live monitor keep ``bcgs_kernel``.

The whole solve is a case of ``test_bjacobi_ilu.py::
test_bcgs_bjacobi_matches_spsolve``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.models import convdiff2d
from jax.experimental import pallas as pl

from mpi_petsc4py_example_tpu.ops import double_f32
from mpi_petsc4py_example_tpu.ops.double_f32 import from_pair
from mpi_petsc4py_example_tpu.solvers import bcgs_dd, bjilu, krylov
from mpi_petsc4py_example_tpu.solvers import pc as pcmod

from test_bjacobi_ilu import _per_block, ilu0_solve_loops

NX = 32
BLOCKS = 16
TOL = 1e-13
GROUPS_OF = 8           # groups of 8 blocks a 2048^2 grid holds at 32 lines


@pytest.fixture()
def dd_on_cpu(monkeypatch):
    """The loop where Mosaic would compile it, on the CPU in interpret
    mode; bjacobi past a lowered dense cap on the kernel's stack."""
    monkeypatch.setattr(bcgs_dd, "PLATFORMS", ("tpu", "cpu"))
    monkeypatch.setattr(pcmod, "_DENSE_CAP", 64)
    monkeypatch.setattr(bjilu, "BLOCK_ROWS", NX * NX // BLOCKS)
    monkeypatch.setattr(bjilu, "use_kernel", lambda platform, dtype: True)


def _setup(comm):
    A = convdiff2d(NX).tocsr()
    M = tps.Mat.from_scipy(comm, A, np.float64)
    stack, info = bjilu.build(comm, M)
    assert info["apply"] == "pallas" and stack.shape[3] == bcgs_dd.GROUP
    lines = stack.shape[2]
    coef = [bcgs_dd.planes(M.dia_vals[:, d], lines, NX)
            for d in np.argsort(M.dia_offsets)]
    return A, stack, lines, coef


def _vec(seed, n=NX * NX):
    return np.random.default_rng(seed).standard_normal(n)


def _rel(got, want):
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def _back(pair):
    return np.asarray(bjilu.natural(from_pair(*pair)))


@pytest.mark.parametrize("op", ["mul", "add"])
def test_pair_arithmetic_in_a_kernel(op):
    """Dekker's product and the pair sum, in an interpret-mode kernel,
    within 2**-45 of fp64 (of ``|a| + |b|`` for the sum)."""
    rng = np.random.default_rng(5)
    a, b = (rng.standard_normal((64, 256)) for _ in range(2))
    f = {"mul": double_f32.dd_mul, "add": double_f32.dd_add}[op]

    def kernel(ah, al, bh, bl, zh, zl):
        zh[...], zl[...] = f((ah[...], al[...]), (bh[...], bl[...]))

    A, B = (double_f32.to_pair(jnp.asarray(v)) for v in (a, b))
    z = pl.pallas_call(
        kernel, out_shape=[jax.ShapeDtypeStruct(a.shape, jnp.float32)] * 2,
        interpret=True)(*A, *B)
    a, b = np.asarray(from_pair(*A)), np.asarray(from_pair(*B))
    want, scale = ((a * b, np.abs(a * b)) if op == "mul"
                   else (a + b, np.abs(a) + np.abs(b)))
    assert np.all(np.abs(np.asarray(from_pair(*z)) - want)
                  <= 2.0 ** -45 * scale)


@pytest.mark.parametrize("step", ["p", "s"])
@pytest.mark.parametrize("kind", ["bjacobi_ilu0"])
def test_pc_step(comm1, dd_on_cpu, kind, step):
    """``c = r + beta (p - omega v)`` (or ``s = r - alpha v``), written
    over its input, and ``M^-1 c``, in one pass of the ILU(0) kernel."""
    A, stack, lines, _ = _setup(comm1)
    r, p, v = (_vec(k) for k in range(3))
    beta, omega, alpha = 0.7, -1.3, 0.45
    if step == "p":
        want = r + beta * (p - omega * v)
        pairs, sc, reuse = [r, p, v], (beta, omega), 1
        update = bcgs_dd._update_p
    else:
        want = r - alpha * v
        pairs, sc, reuse = [r, v], (alpha,), 0
        update = bcgs_dd._update_s

    def run(*vs):
        return bjilu.apply_pallas_dd(
            stack, [bcgs_dd.planes(x, lines, NX) for x in vs],
            bcgs_dd.scalar_pairs(*sc), update, reuse, interpret=True)

    c, z = jax.jit(run)(*(jnp.asarray(x) for x in pairs))
    assert _rel(_back(c), want) <= TOL
    assert _rel(_back(z),
                _per_block(ilu0_solve_loops, A, want, BLOCKS)) <= 1e-12


@pytest.mark.parametrize("form", ["apply_dot", "apply_dots", "residual"])
def test_spmv_sweeps(comm1, dd_on_cpu, form):
    """``A u`` across group edges and line ends, with its dots."""
    A, _, lines, coef = _setup(comm1)
    u, w = _vec(11), _vec(12)
    Au = A @ u

    def run(u, w):
        pu, pw = (bcgs_dd.planes(x, lines, NX) for x in (u, w))
        out, *dots = getattr(bcgs_dd, form)(coef, pu, pw, True)
        return out, dots

    out, dots = jax.jit(run)(jnp.asarray(u), jnp.asarray(w))
    if form == "apply_dot":
        want, want_dots = Au, [w @ Au]
    elif form == "apply_dots":
        want, want_dots = Au, [Au @ w, Au @ Au, w @ w]
        dots = list(dots[0])
    else:                       # u is x, w is b: r = b - A x
        want = w - Au
        want_dots = [want @ want, w @ w]
    assert _rel(_back(out), want) <= TOL
    got = np.asarray(jnp.stack(dots)).ravel()
    np.testing.assert_allclose(got, want_dots, rtol=TOL, atol=0)


def test_update_sweep(comm1, dd_on_cpu):
    """S3: ``x + alpha p^ + omega s^`` and ``r = s - omega t`` with
    ``r^.r`` and ``r.r``."""
    _, _, lines, _ = _setup(comm1)
    x, ph, sh, s, t, rh = (_vec(20 + k) for k in range(6))
    alpha, omega = 0.31, 1.7

    def run(*vs):
        return bcgs_dd.update(*(bcgs_dd.planes(v, lines, NX) for v in vs),
                              bcgs_dd.scalar_pairs(alpha, omega), True)

    xn, r, rr, r2 = jax.jit(run)(*(jnp.asarray(v)
                                   for v in (x, ph, sh, s, t, rh)))
    want_r = s - omega * t
    assert _rel(_back(xn), x + alpha * ph + omega * sh) <= TOL
    assert _rel(_back(r), want_r) <= TOL
    np.testing.assert_allclose([float(rr), float(r2)],
                               [rh @ want_r, want_r @ want_r], rtol=TOL)


def test_ilu_pair_entry_matches_fp64(comm1, dd_on_cpu):
    """The kernel on (hi, lo) planes in its layout gives what its fp64
    entry point gives."""
    _, stack, lines, _ = _setup(comm1)
    r = jnp.asarray(_vec(30))
    z64 = np.asarray(jax.jit(
        lambda c, v: bjilu.apply_pallas(c, v, True))(stack, r))
    z = jax.jit(lambda c, v: bjilu.apply_pallas_dd(
        c, [bcgs_dd.planes(v, lines, NX)], interpret=True))(stack, r)
    assert _rel(_back(z), z64) <= 1e-14


def _loop(monkeypatch, case):
    """``build_ksp_program``'s choice for one BCGS configuration: each
    case departs from fp64 DIA on one device with PC bjacobi on the
    ILU(0) kernel's stack in the one way it names."""
    ndev, dtype, pc_type = 1, np.float64, "bjacobi"
    monitored = live = False
    if case == "fp32":
        dtype = np.float32
    elif case == "complex128":
        dtype = np.complex128
    elif case == "8_devices":
        ndev = 8
    elif case == "live_monitor":
        monitored = live = True
    elif case in ("jacobi", "none"):
        pc_type = case
    if case in ("dense_blocks", "complex128", "ell"):
        # complex and ELL operators have no ILU(0) line blocks: bjacobi
        # holds dense ones
        monkeypatch.setattr(pcmod, "_DENSE_CAP", 1 << 20)
    comm = tps.DeviceComm(n_devices=ndev)
    M = tps.Mat.from_scipy(comm, sp.csr_matrix(convdiff2d(NX), dtype=dtype),
                           dtype)
    if case == "ell":
        M.dia_vals, M.dia_offsets = None, ()
    pc = tps.PC(comm)
    pc.set_type(pc_type)
    pc.set_up(M)
    krylov._PROGRAM_CACHE.clear()
    prog = krylov.build_ksp_program(comm, "bcgs", pc, M, monitored=monitored,
                                    live=live, zero_guess=True)
    krylov._PROGRAM_CACHE.clear()
    assert pc.kind == {"jacobi": "jacobi", "none": "none",
                       "dense_blocks": "bjacobi", "complex128": "bjacobi",
                       "ell": "bjacobi"}.get(case, "bjacobi_ilu0")
    return prog.loop


@pytest.mark.parametrize("case,want", [
    ("bjacobi_ilu0", "dd_pallas"),
    ("jacobi", "xla"),
    ("none", "xla"),
    ("fp32", "xla"),
    ("complex128", "xla"),
    ("ell", "xla"),
    ("8_devices", "xla"),
    ("dense_blocks", "xla"),
    ("live_monitor", "xla"),
])
def test_loop_selection(dd_on_cpu, monkeypatch, case, want):
    assert _loop(monkeypatch, case) == want


def test_loop_needs_the_chip(monkeypatch):
    """Where Mosaic does not compile, every BCGS keeps ``bcgs_kernel``."""
    monkeypatch.setattr(pcmod, "_DENSE_CAP", 64)
    monkeypatch.setattr(bjilu, "BLOCK_ROWS", NX * NX // BLOCKS)
    monkeypatch.setattr(bjilu, "use_kernel", lambda platform, dtype: True)
    assert _loop(monkeypatch, "bjacobi_ilu0") == "xla"


def test_history_through_the_loop(dd_on_cpu):
    """A recorded residual history (no live monitor) keeps the loop and
    holds the initial norm and one norm an iteration, falling to the
    tolerance."""
    comm = tps.DeviceComm(n_devices=1)
    A = convdiff2d(NX).tocsr()
    M = tps.Mat.from_scipy(comm, A, np.float64)
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type("bcgs")
    ksp.get_pc().set_type("bjacobi")
    ksp.set_tolerances(rtol=1e-10, max_it=500)
    ksp.set_convergence_history()
    x, b = M.get_vecs()
    b.set_global(A @ np.ones(A.shape[0]))
    krylov._PROGRAM_CACHE.clear()
    res = ksp.solve(b, x)
    assert {p.loop for p in krylov._PROGRAM_CACHE.values()} == {"dd_pallas"}
    krylov._PROGRAM_CACHE.clear()
    assert res.converged
    hist = np.asarray(ksp.get_convergence_history())
    assert len(hist) == res.iterations + 1
    assert hist[0] == pytest.approx(np.linalg.norm(A @ np.ones(A.shape[0])),
                                    rel=1e-13)
    assert hist[-1] == pytest.approx(res.residual_norm, rel=1e-13)
    assert hist[-1] <= 1e-10 * hist[0]


@pytest.mark.parametrize("lines,want", [(32, (32, 2048)), (40, None)])
def test_layout_needs_s1_to_fit(lines, want):
    """At 2048-point lines in groups of 8 blocks, S1's kernel call (10
    planes besides the coefficients) fits VMEM at 32 lines a block, the
    cell's, and not at 40, where the plain kernel still does: the PC
    keeps its groups of 8 and BCGS keeps ``bcgs_kernel``."""
    from types import SimpleNamespace as NS
    m = 2048
    n = 8 * GROUPS_OF * lines * m
    op = NS(dtype=np.float64, shape=(n, n), dia_offsets=(-m, -1, 0, 1, m),
            dia_vals=object())
    stack = jax.ShapeDtypeStruct((GROUPS_OF, 10, lines, 8, m), jnp.float32)
    pc = NS(kind="bjacobi_ilu0", device_arrays=lambda: (stack,))
    comm = NS(platform="tpu", size=1)
    assert bjilu.group_size((8 * GROUPS_OF, 5, lines, m), 1) == 8
    assert bcgs_dd.layout(comm, "bcgs", pc, op) == want


def test_package_import_leaves_pallas_out():
    """Importing the package loads no Pallas (~1.7 s): the loop's module
    is imported where bjacobi's ILU(0) PC has loaded it already."""
    import os
    import subprocess
    import sys
    code = ("import sys, mpi_petsc4py_example_tpu; "
            "print('jax.experimental.pallas' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, check=True,
                         env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.stdout.strip() == "False"
