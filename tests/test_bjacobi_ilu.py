"""Block Jacobi with ILU(0) blocks (solvers/bjilu.py), PETSc's default
PCBJACOBI sub-solve, on five-point DIA operators past the dense cap.

Pins, in fp64 at small sizes with the caps lowered:

* the device apply, the XLA sweeps and the TPU kernel's double-f32 ones
  (interpret mode), against a plain sequential natural-order ILU(0) of
  each block, written as loops, to 1e-12; its transpose against
  ``(LU)^T`` of the same factors; the batched apply against its columns;
  the kernel's stack layout and group size;
* the TPU inner product of fp64 vectors as a product and a sum;
* which sub-solve bjacobi picks: dense below the cap, for an explicit
  block count that fits it, a non-five-point pattern, a coupling across
  a line end or a complex operator; ILU(0) on a real five-point DIA
  operator past it, of the explicit count where one is given;
* BCGS (its XLA loop and its double-f32 one, solvers/bcgs_dd.py) and
  BiCG + bjacobi through KSP, and CG + bjacobi through
  ``KSP.solve_many``, against ``scipy.sparse.linalg.spsolve``;
* the ``mat.assemble`` and ``pc.setup`` spans that say what ran.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp
import scipy.sparse.linalg as spla

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu import telemetry
from mpi_petsc4py_example_tpu.models import convdiff2d, poisson3d_csr
from mpi_petsc4py_example_tpu.solvers import bjilu
from mpi_petsc4py_example_tpu.solvers import pc as pcmod

from test_ksp import convdiff2d as convdiff2d_wrapped


def ilu0_rows_loops(B):
    """ILU(0) of the sparse block ``B`` in natural order, the textbook IKJ
    elimination restricted to B's pattern: row ``i`` as ``{column: value}``,
    L's multipliers left of the diagonal, U's row from it on."""
    B = B.tocsr()
    n = B.shape[0]
    rows = [dict(zip(B.indices[B.indptr[i]:B.indptr[i + 1]],
                     B.data[B.indptr[i]:B.indptr[i + 1]].astype(float)))
            for i in range(n)]
    for i in range(n):
        row = rows[i]
        for k in sorted(c for c in row if c < i):
            row[k] /= rows[k][k]
            for j, ukj in rows[k].items():
                if j > k and j in row:
                    row[j] -= row[k] * ukj
    return rows


def ilu0_solve_loops(B, r):
    """``(LU)^-1 r`` for ILU(0) of ``B``: the two triangular solves, row
    by row."""
    rows = ilu0_rows_loops(B)
    n = len(rows)
    y = np.zeros(n)
    for i in range(n):
        y[i] = r[i] - sum(v * y[j] for j, v in rows[i].items() if j < i)
    z = np.zeros(n)
    for i in reversed(range(n)):
        z[i] = (y[i] - sum(v * z[j] for j, v in rows[i].items()
                           if j > i)) / rows[i][i]
    return z


def ilu0_transpose_solve_loops(B, r):
    """``(LU)^-T r`` for ILU(0) of ``B``, from its factors made dense."""
    rows = ilu0_rows_loops(B)
    n = len(rows)
    L, U = np.eye(n), np.zeros((n, n))
    for i, row in enumerate(rows):
        for j, v in row.items():
            (L if j < i else U)[i, j] = v
    return np.linalg.solve((L @ U).T, r)


def _mat(comm, A, dtype=np.float64):
    return tps.Mat.from_scipy(comm, sp.csr_matrix(A), dtype)


def _build(comm, A, monkeypatch, blocks, kernel=False):
    """ILU(0) stack of ``blocks`` line blocks of ``A`` (the kernel's
    layout where ``kernel``, as on TPU in fp64)."""
    monkeypatch.setattr(bjilu, "BLOCK_ROWS", A.shape[0] // blocks)
    monkeypatch.setattr(bjilu, "use_kernel", lambda platform, dtype: kernel)
    return bjilu.build(comm, _mat(comm, A))


def _per_block(solve, A, r, blocks):
    bs = A.shape[0] // blocks
    return np.concatenate([
        solve(A[b * bs:(b + 1) * bs, b * bs:(b + 1) * bs],
              r[b * bs:(b + 1) * bs]) for b in range(blocks)])


@pytest.mark.parametrize("nx,ny,blocks", [(16, 16, 4), (64, 64, 4),
                                          (12, 20, 4), (33, 8, 2)])
def test_apply_matches_sequential_ilu0(comm1, monkeypatch, nx, ny, blocks):
    A = convdiff2d(nx, ny).tocsr()
    stack, info = _build(comm1, A, monkeypatch, blocks)
    assert info["blocks"] == blocks and info["line"] == nx
    r = np.random.default_rng(nx + ny).standard_normal(nx * ny)
    z = np.asarray(jax.jit(bjilu.apply)((stack,), jnp.asarray(r)))
    want = _per_block(ilu0_solve_loops, A, r, blocks)
    assert np.max(np.abs(z - want)) <= 1e-12 * np.max(np.abs(want))


@pytest.mark.parametrize("nx,ny,blocks", [(16, 16, 4), (64, 64, 16),
                                          (12, 20, 4), (128, 8, 2)])
def test_pallas_apply_matches_sequential_ilu0(comm1, monkeypatch, nx, ny,
                                              blocks):
    """The TPU kernel's double-f32 sweeps, in interpret mode: groups of
    up to 8 blocks, lines-major."""
    A = convdiff2d(nx, ny).tocsr()
    stack, info = _build(comm1, A, monkeypatch, blocks, kernel=True)
    assert info["apply"] == "pallas"
    assert stack.shape == (blocks // min(blocks, 8), 10, ny // blocks,
                           min(blocks, 8), nx)
    r = np.random.default_rng(nx * ny).standard_normal(nx * ny)
    z = np.asarray(jax.jit(lambda a, v: bjilu.apply(a, v, True))(
        (stack,), jnp.asarray(r)))
    want = _per_block(ilu0_solve_loops, A, r, blocks)
    assert np.max(np.abs(z - want)) <= 1e-12 * np.max(np.abs(want))


@pytest.mark.parametrize("kernel", [False, True])
@pytest.mark.parametrize("nx,ny,blocks", [(16, 16, 4), (12, 20, 2)])
def test_transpose_apply_matches_ilu0_transpose(comm1, monkeypatch, nx, ny,
                                                blocks, kernel):
    """``apply`` on ``transpose``'s stack solves ``(LU)^T z = r`` for each
    block, in both layouts (the kernel's in interpret mode)."""
    A = convdiff2d(nx, ny, beta=0.4).tocsr()
    stack, _ = _build(comm1, A, monkeypatch, blocks, kernel)
    r = np.random.default_rng(nx * 7 + ny).standard_normal(nx * ny)
    z = np.asarray(jax.jit(lambda a, v: bjilu.apply(
        (bjilu.transpose(a),), v, kernel))(stack, jnp.asarray(r)))
    want = _per_block(ilu0_transpose_solve_loops, A, r, blocks)
    assert np.max(np.abs(z - want)) <= 1e-12 * np.max(np.abs(want))


@pytest.mark.parametrize("kernel", [False, True])
def test_apply_many_matches_columns(comm1, monkeypatch, kernel):
    A = convdiff2d(16, 16).tocsr()
    stack, _ = _build(comm1, A, monkeypatch, 4, kernel)
    R = np.random.default_rng(6).standard_normal((256, 3))
    Z = np.asarray(jax.jit(lambda a, v: bjilu.apply_many(a, v, kernel))(
        (stack,), jnp.asarray(R)))
    want = np.stack([_per_block(ilu0_solve_loops, A, R[:, j], 4)
                     for j in range(3)], axis=1)
    assert np.max(np.abs(Z - want)) <= 1e-12 * np.max(np.abs(want))


def test_pallas_stack_round_trip():
    """Each (hi, lo) pair sums back to the fp64 coefficient, negated
    where the kernel adds in place of subtracting."""
    stack = np.random.default_rng(3).standard_normal((4, 5, 3, 16))
    packed = bjilu.pallas_stack(stack, 2)
    assert packed.shape == (2, 10, 3, 2, 16) and packed.dtype == np.float32
    for k, (row, sign) in enumerate(bjilu.PALLAS_ROWS):
        back = (packed[:, 2 * k].astype(np.float64)
                + packed[:, 2 * k + 1]).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(back.reshape(4, 3, 16),
                                   sign * stack[:, row], rtol=1e-14, atol=0)


@pytest.mark.parametrize("shape,ndev,want", [
    ((64, 5, 32, 2048), 1, 8),      # the cell: 8 groups of 8 blocks
    ((64, 5, 32, 2048), 16, 4),     # 4 blocks a device
    ((6, 5, 4, 64), 1, 2),
    ((8, 5, 64, 2048), 1, 4),       # 8 blocks would pass the VMEM budget
    ((1, 5, 4096, 4096), 1, 0),     # not even one block fits
])
def test_group_size(shape, ndev, want):
    assert bjilu.group_size(shape, ndev) == want


@pytest.mark.parametrize("dtype", [np.float64, np.float32, np.complex128])
def test_local_dot(dtype):
    """On TPU a real fp64 inner product is a product and a sum, not the
    split-operand fp64 ``dot_general``; everything else stays ``vdot``."""
    from mpi_petsc4py_example_tpu.solvers.krylov import _local_dot
    rng = np.random.default_rng(4)
    u, v = (jnp.asarray(rng.standard_normal(300) * (1 + 1j), dtype)
            if dtype == np.complex128 else
            jnp.asarray(rng.standard_normal(300), dtype) for _ in range(2))
    dot = _local_dot("tpu")
    got, want = complex(dot(u, v)), complex(jnp.vdot(u, v))
    assert got == pytest.approx(want, rel=10 * np.finfo(dtype).eps)
    jaxpr = str(jax.make_jaxpr(dot)(u, v))
    assert ("dot_general" in jaxpr) == (dtype != np.float64)
    assert _local_dot("cpu") is jnp.vdot


def _picked(comm, A, monkeypatch, cap=64, blocks=0, dtype=np.float64):
    monkeypatch.setattr(pcmod, "_DENSE_CAP", cap)
    monkeypatch.setattr(bjilu, "BLOCK_ROWS", 64)
    p = tps.PC(comm)
    p.set_type("bjacobi")
    p.bjacobi_blocks = blocks
    p.set_up(_mat(comm, A, dtype))
    return p.sub_solve, p.sub_blocks, p.kind


@pytest.mark.parametrize("case,want", [
    ("under_cap", ("dense", 1, "bjacobi")),
    ("five_point", ("ilu0", 4, "bjacobi_ilu0")),
    ("explicit_blocks", ("dense", 4, "bjacobi")),
    ("explicit_past_cap", ("ilu0", 2, "bjacobi_ilu0")),
    ("seven_point", ("dense", 32, "bjacobi")),
    ("line_crossing", ("dense", 16, "bjacobi")),
    ("complex", ("dense", 16, "bjacobi")),
])
def test_path_selection(comm1, monkeypatch, case, want):
    A, kw = convdiff2d(16), {}
    if case == "under_cap":
        kw["cap"] = 1024
    elif case == "explicit_blocks":
        kw["blocks"] = 4           # 64-row blocks: at the cap, dense
    elif case == "explicit_past_cap":
        kw["blocks"] = 2           # 128-row blocks: past it, 8 lines each
    elif case == "seven_point":
        A = poisson3d_csr(8)
    elif case == "line_crossing":
        A = convdiff2d_wrapped(16)   # its ±1 couplings wrap line ends
    elif case == "complex":
        kw["dtype"] = np.complex128
    monkeypatch.setattr(pcmod, "_AUTO_BLOCK_TARGET", 16)
    assert _picked(comm1, A, monkeypatch, **kw) == want


@pytest.mark.parametrize("apply", ["xla", "pallas", "dd"])
@pytest.mark.parametrize("ndev", [1, 8])
def test_bcgs_bjacobi_matches_spsolve(monkeypatch, ndev, apply):
    """16 ILU(0) blocks of 2 lines; sharded, two on each of 8 devices;
    the XLA sweeps and the TPU kernel's (interpret mode); "dd": the TPU
    kernel's, with the loop as double-f32 sweeps (solvers/bcgs_dd.py)
    where it engages, one device, within 2 iterations of the XLA
    loop's."""
    from mpi_petsc4py_example_tpu.solvers import bcgs_dd, krylov
    if apply == "dd":
        monkeypatch.setattr(bcgs_dd, "PLATFORMS", ("tpu", "cpu"))
    krylov._PROGRAM_CACHE.clear()
    x, want, pc, its = _ksp_past_cap(monkeypatch, ndev, apply, "bcgs")
    loops = {p.loop for p in krylov._PROGRAM_CACHE.values()}
    krylov._PROGRAM_CACHE.clear()
    assert loops == {"dd_pallas" if (apply, ndev) == ("dd", 1) else "xla"}
    assert (pc.sub_solve, pc.sub_blocks) == ("ilu0", 16)
    assert pc.setup_breakdown["apply"] == ("pallas" if apply == "dd"
                                           else apply)
    err = np.linalg.norm(x - want) / np.linalg.norm(want)
    assert err < 1e-9, err
    if apply == "dd":
        monkeypatch.setattr(bcgs_dd, "PLATFORMS", ())
        *_, its_xla = _ksp_past_cap(monkeypatch, ndev, apply, "bcgs")
        assert abs(its - its_xla) <= 2, (its, its_xla)


def _ksp_past_cap(monkeypatch, ndev, apply, ksp_type, nrhs=0, beta=0.3):
    """A KSP solve of ``convdiff2d(32)`` with bjacobi past a lowered dense
    cap (16 ILU(0) blocks of 2 lines), one right-hand side or ``nrhs``
    through ``solve_many``; returns (x, scipy's x, the PC, iterations)."""
    monkeypatch.setattr(pcmod, "_DENSE_CAP", 64)
    monkeypatch.setattr(bjilu, "BLOCK_ROWS", 64)
    monkeypatch.setattr(bjilu, "use_kernel",
                        lambda platform, dtype: apply in ("pallas", "dd"))
    comm = tps.DeviceComm(n_devices=ndev)
    A = convdiff2d(32, beta=beta).tocsr()
    X = np.random.default_rng(5).random((A.shape[0], max(nrhs, 1)))
    B = A @ X
    M = _mat(comm, A)
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type(ksp_type)
    ksp.get_pc().set_type("bjacobi")
    ksp.set_tolerances(rtol=1e-11, max_it=2000)
    want = spla.splu(A.tocsc()).solve(B)
    if nrhs:
        res = ksp.solve_many(B)
        assert res.converged
        return np.asarray(res.X), want, ksp.get_pc(), None
    x, bv = M.get_vecs()
    bv.set_global(B[:, 0])
    res = ksp.solve(bv, x)
    assert res.converged
    return x.to_numpy(), want[:, 0], ksp.get_pc(), int(res.iterations)


@pytest.mark.parametrize("apply", ["xla", "pallas"])
@pytest.mark.parametrize("ndev", [1, 8])
def test_bicg_bjacobi_past_cap(monkeypatch, ndev, apply):
    """BiCG's shadow recurrence applies the ILU(0) blocks' transpose."""
    x, want, pc, _ = _ksp_past_cap(monkeypatch, ndev, apply, "bicg")
    assert pc.kind == "bjacobi_ilu0"
    err = np.linalg.norm(x - want) / np.linalg.norm(want)
    assert err < 1e-9, err


@pytest.mark.parametrize("apply", ["xla", "pallas"])
@pytest.mark.parametrize("ndev", [1, 8])
def test_solve_many_bjacobi_past_cap(monkeypatch, ndev, apply):
    """Block CG with the ILU(0) blocks applied to every column at once:
    the batched program, not one solve a column (the five-point
    Laplacian, whose ILU(0) is symmetric)."""
    monkeypatch.setattr(tps.KSP, "_solve_many_sequential", None)
    X, want, pc, _ = _ksp_past_cap(monkeypatch, ndev, apply, "cg", nrhs=3,
                                beta=0.0)
    assert pc.kind == "bjacobi_ilu0"
    err = np.linalg.norm(X - want) / np.linalg.norm(want)
    assert err < 1e-9, err


def test_ilu0_beats_point_jacobi(comm1, monkeypatch):
    """ILU(0) blocks cut BCGS's iterations well below point Jacobi's."""
    monkeypatch.setattr(pcmod, "_DENSE_CAP", 64)
    A = convdiff2d(32).tocsr()
    b = A @ np.ones(A.shape[0])
    its = {}
    for pc_type in ("jacobi", "bjacobi"):
        M = _mat(comm1, A)
        ksp = tps.KSP().create(comm1)
        ksp.set_operators(M)
        ksp.set_type("bcgs")
        ksp.get_pc().set_type(pc_type)
        ksp.set_tolerances(rtol=1e-8, max_it=2000)
        x, bv = M.get_vecs()
        bv.set_global(b)
        its[pc_type] = ksp.solve(bv, x).iterations
    assert its["bjacobi"] * 2 < its["jacobi"], its


def test_spans_say_what_ran(comm1, monkeypatch):
    monkeypatch.setattr(pcmod, "_DENSE_CAP", 64)
    monkeypatch.setattr(bjilu, "BLOCK_ROWS", 64)
    telemetry.enable()
    try:
        telemetry.flight_recorder.clear()
        M = _mat(comm1, convdiff2d(16))
        p = tps.PC(comm1)
        p.set_type("bjacobi")
        p.set_up(M)
        spans = {s["name"]: s for s in telemetry.flight_recorder.spans()}
    finally:
        telemetry.disable()
    assert spans["mat.assemble"]["attrs"] == {"rows": 256, "format": "dia"}
    attrs = spans["pc.setup"]["attrs"]
    assert (attrs["sub_solve"], attrs["blocks"]) == ("ilu0", 4)
    assert p.setup_breakdown["lines_per_block"] == 4


def test_zero_pivot_raises():
    dia = np.zeros((16, 5))
    dia[:, 2] = 1.0
    dia[1:4, 1] = 1.0           # west couplings inside the first line
    dia[:3, 3] = 1.0            # east: pivot 1 - 1*1/1 = 0 at point 1
    with pytest.raises(ValueError, match="zero pivot"):
        bjilu.factor(dia, 4, 2)
