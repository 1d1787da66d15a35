"""Facade tests: petsc4py/slepc4py/mpi4py shims + unchanged-driver flows.

Covers the north-star requirement: reference-style drivers run unchanged
against the TPU backend, single-rank and under virtual multi-rank tpurun
(the mpirun -n N analog).
"""

import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
COMPAT = os.path.join(REPO, "compat")

# make the facade importable in-process
for p in (COMPAT, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)

import petsc4py  # noqa: E402

petsc4py.init([])

from mpi4py import MPI  # noqa: E402
from petsc4py import PETSc  # noqa: E402
from slepc4py import SLEPc  # noqa: E402

import petsc_funcs as pet  # noqa: E402

from mpi_petsc4py_example_tpu.models import random_system, tridiag_family  # noqa: E402


class TestMPIFacade:
    def test_world_single_rank(self):
        assert MPI.COMM_WORLD.Get_rank() == 0
        assert MPI.COMM_WORLD.Get_size() == 1

    def test_bcast_identity(self):
        assert MPI.COMM_WORLD.bcast((100, 100), root=0) == (100, 100)

    def test_gatherv_single(self):
        out = np.zeros(4)
        MPI.COMM_WORLD.Gatherv(np.arange(4.0), out)
        np.testing.assert_array_equal(out, np.arange(4.0))

    def test_send_requires_ranks(self):
        with pytest.raises(RuntimeError, match="tpurun"):
            MPI.COMM_WORLD.send({"x": 1}, dest=1)


class TestPETScFacade:
    def test_reference_test_py_flow(self):
        """The full test.py call sequence through the facade, size-1."""
        A, X_actual, B = random_system(100, seed=42, density=0.1)
        a = PETSc.Mat().createAIJ(comm=MPI.COMM_WORLD, size=A.shape,
                                  csr=(A.indptr, A.indices, A.data))
        a.setUp()
        a.assemblyBegin()
        a.assemblyEnd()
        x, b = a.getVecs()
        b.setArray(B)

        ksp = PETSc.KSP().create(MPI.COMM_WORLD)
        ksp.setType("preonly")
        pc = ksp.getPC()
        pc.setType("lu")
        pc.setFactorSolverType("mumps")
        ksp.setOperators(a)
        ksp.setFromOptions()
        ksp.setUp()
        ksp.solve(b, x)

        X = np.empty(100)
        MPI.COMM_WORLD.Gatherv(x.array, X)
        assert np.allclose(X, X_actual)

    def test_mat_queries(self):
        A, _, _ = random_system(50, seed=1)
        a = PETSc.Mat().createAIJ(size=A.shape,
                                  csr=(A.indptr, A.indices, A.data))
        assert a.getSize() == (50, 50)
        assert a.getLocalSize()[0] == 50
        assert a.getOwnershipRange() == (0, 50)
        assert a.isAssembled()

    def test_options_object(self):
        opts = PETSc.Options()
        opts.setValue("ksp_type", "cg")
        assert opts.getString("ksp_type") == "cg"
        assert opts.hasName("ksp_type")
        opts.delValue("ksp_type")
        assert not opts.hasName("ksp_type")

    def test_ksp_from_options_flags(self):
        """Runtime override via CLI flags, the reference's §3.4 capability."""
        petsc4py.init(["prog", "-ksp_type", "cg", "-pc_type", "jacobi",
                       "-ksp_rtol", "1e-9"])
        A, X_actual, B = random_system(100, seed=42)
        # make it SPD-ish for CG: use normal equations matrix
        import scipy.sparse as sp
        M = (A.T @ A + 10 * sp.eye(100)).tocsr()
        B2 = M @ X_actual
        a = PETSc.Mat().createAIJ(size=M.shape,
                                  csr=(M.indptr, M.indices, M.data))
        x, b = a.getVecs()
        b.setArray(B2)
        ksp = PETSc.KSP().create(MPI.COMM_WORLD)
        ksp.setType("preonly")  # overridden by -ksp_type cg
        ksp.setOperators(a)
        ksp.setFromOptions()
        ksp.solve(b, x)
        assert ksp.core.get_type() == "cg"
        assert np.allclose(x.array, X_actual, atol=1e-6)


# petsc4py-style nested setSizes hint: the local size slot is DECIDE
DECIDE_LOCAL = PETSc.DECIDE


class TestMatSetValues:
    """petsc4py-style entry-by-entry assembly: create/setSizes/setValues
    + INSERT/ADD with assemblyBegin/End building the CSR host-side
    (VERDICT missing #2). The ``csr=`` constructor fast path stays."""

    def test_tridiagonal_matches_csr_fast_path(self):
        """The test2.py tridiagonal, assembled entry-by-entry, is
        bit-identical to the csr= constructor's matrix."""
        N = 100
        A = PETSc.Mat().create(MPI.COMM_WORLD)
        A.setSizes((N, N))
        A.setType("aij")
        A.setFromOptions()
        for i in range(N):
            cols = [j for j in (i - 1, i, i + 1) if 0 <= j < N]
            A.setValues([i], cols, [float(i + j + 1) for j in cols],
                        addv=PETSc.InsertMode.INSERT_VALUES)
        A.assemblyBegin()
        A.assemblyEnd()
        assert A.isAssembled()
        CSR = tridiag_family(N)
        B = PETSc.Mat().createAIJ(size=CSR.shape,
                                  csr=(CSR.indptr, CSR.indices, CSR.data))
        assert abs(A.core.to_scipy() - B.core.to_scipy()).max() == 0.0

    def test_setvalues_solve_matches_reference_flow(self):
        """A KSP solve through the setValues-assembled operator gives the
        same answer as the csr= path (the matrix IS the same object
        shape-wise — this pins the end-to-end flow)."""
        N = 100
        CSR = tridiag_family(N)
        A = PETSc.Mat().create(MPI.COMM_WORLD)
        A.setSizes(((DECIDE_LOCAL, N), (DECIDE_LOCAL, N)))
        A.setType("aij")
        for i in range(N):
            cols = [j for j in (i - 1, i, i + 1) if 0 <= j < N]
            A.setValues([i], cols, [float(i + j + 1) for j in cols])
        A.assemble()
        x, b = A.getVecs()
        rhs = np.asarray(CSR @ np.ones(N))
        b.setArray(rhs)
        ksp = PETSc.KSP().create(MPI.COMM_WORLD)
        ksp.setType("gmres")
        ksp.getPC().setType("jacobi")
        ksp.setOperators(A)
        ksp.core.set_tolerances(rtol=1e-10)
        ksp.setUp()
        ksp.solve(b, x)
        assert np.abs(x.array - 1.0).max() < 1e-6

    def test_add_values_sums_duplicates(self):
        M = PETSc.Mat().create(MPI.COMM_WORLD)
        M.setSizes(4)
        M.setType("aij")
        M.setValues([0], [0], [1.0], addv=PETSc.InsertMode.ADD_VALUES)
        M.setValues([0], [0], [2.0], addv=True)      # petsc4py bool form
        for i in range(1, 4):
            M.setValue(i, i, float(i), addv=True)
        M.assemble()
        S = M.core.to_scipy()
        assert S[0, 0] == 3.0
        assert S[2, 2] == 2.0

    def test_insert_last_write_wins(self):
        M = PETSc.Mat().create(MPI.COMM_WORLD)
        M.setSizes(3)
        M.setType("aij")
        M.setValues([0, 1, 2], [0, 1, 2], np.diag([1.0, 2.0, 3.0]))
        M.setValue(1, 1, 9.0)                        # overrides the 2.0
        M.assemble()
        assert M.core.to_scipy()[1, 1] == 9.0

    def test_numpy_bool_addv_means_add(self):
        """np.True_ (e.g. ``addv=np.any(mask)``) must mean ADD like the
        Python bool — under int equality np.True_ == INSERT_VALUES, the
        trap the bool-first normalization exists for."""
        M = PETSc.Mat().create(MPI.COMM_WORLD)
        M.setSizes(2)
        M.setType("aij")
        M.setValue(0, 0, 1.0, addv=np.True_)
        M.setValue(0, 0, 2.0, addv=np.True_)
        M.setValue(1, 1, 1.0, addv=np.True_)
        M.assemble()
        assert M.core.to_scipy()[0, 0] == 3.0

    def test_mixing_modes_without_assembly_raises(self):
        M = PETSc.Mat().create(MPI.COMM_WORLD)
        M.setSizes(3)
        M.setType("aij")
        M.setValue(0, 0, 1.0)
        with pytest.raises(RuntimeError, match="mix"):
            M.setValue(0, 0, 1.0, addv=True)

    def test_out_of_range_index_raises(self):
        M = PETSc.Mat().create(MPI.COMM_WORLD)
        M.setSizes(3)
        M.setType("aij")
        M.setValue(0, 7, 1.0)
        with pytest.raises(ValueError, match="out of range"):
            M.assemble()

    def test_setvalues_after_assembly_rejected(self):
        M = PETSc.Mat().create(MPI.COMM_WORLD)
        M.setSizes(2)
        M.setType("aij")
        M.setValue(0, 0, 1.0)
        M.setValue(1, 1, 1.0)
        M.assemble()
        with pytest.raises(RuntimeError, match="assemblyEnd"):
            M.setValue(0, 0, 2.0)


class TestSLEPcFacade:
    def test_reference_test2_flow(self):
        """The test2.py call sequence: wrapper API + HEP eigensolve."""
        CSR = tridiag_family(100)
        A = pet.createPETScMat(MPI.COMM_WORLD, CSR.shape,
                               (CSR.indptr, CSR.indices, CSR.data))
        E = pet.solveSLEPcEigenvalues(MPI.COMM_WORLD, A)
        nconv = E.getConverged()
        assert nconv >= 1
        vr, wr = A.getVecs()
        vi, wi = A.getVecs()
        lam = E.getEigenpair(0, vr, vi)
        lam_exact = np.linalg.eigvalsh(CSR.toarray())
        target = lam_exact[np.argmax(np.abs(lam_exact))]
        np.testing.assert_allclose(lam.real, target, rtol=1e-6)
        # eigenvector residual through the facade views
        v = vr.array
        assert np.linalg.norm(CSR @ v - lam.real * v) < 1e-5

    def test_eps_nev_option(self):
        petsc4py.init(["prog", "-eps_nev", "3"])
        CSR = tridiag_family(60)
        A = pet.createPETScMat(MPI.COMM_WORLD, CSR.shape,
                               (CSR.indptr, CSR.indices, CSR.data))
        E = pet.solveSLEPcEigenvalues(MPI.COMM_WORLD, A)
        assert E.getConverged() >= 3


def run_driver(script, nranks, extra=()):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        " --xla_force_host_platform_device_count=8").strip()
    cmd = [sys.executable, os.path.join(REPO, "tools", "tpurun.py"),
           "-n", str(nranks), os.path.join(REPO, script), *extra]
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=600, cwd=REPO)


@pytest.mark.parametrize("nranks", [1, 4])
class TestDriversUnderTpurun:
    def test_solve_linear(self, nranks):
        r = run_driver("examples/solve_linear.py", nranks)
        assert r.returncode == 0, r.stderr
        assert "True" in r.stdout

    def test_eigensolve(self, nranks):
        r = run_driver("examples/eigensolve.py", nranks)
        assert r.returncode == 0, r.stderr
        assert "Eigenvalue:" in r.stdout

    def test_assemble_setvalues(self, nranks):
        """The setValues assembly driver: per-rank MatSetValues of owned
        rows == the csr= fast path, then the test2.py eigensolve."""
        r = run_driver("examples/assemble_setvalues.py", nranks)
        assert r.returncode == 0, r.stderr
        assert "max |diff|: 0.000e+00" in r.stdout
        assert "Eigenvalue:" in r.stdout


REFERENCE_DIR = os.environ.get("REFERENCE_DIR", "/root/reference")


@pytest.mark.skipif(
    not os.path.exists(os.path.join(REFERENCE_DIR, "test.py")),
    reason="reference repo not mounted (set REFERENCE_DIR)")
class TestLiteralReferenceDrivers:
    """The north star, literally: the UNMODIFIED reference drivers.

    Executes /root/reference/test.py and test2.py byte-for-byte through
    tools/tpurun.py with compat/ on sys.path — petsc4py/slepc4py/mpi4py
    resolve to the facades, the solves run on the TPU backend, and the
    drivers' own printed verification is the oracle (test.py:148-149 prints
    np.allclose; test2.py:94-97 prints eigenvalues).  n=3 exercises uneven
    row counts (34/33/33), where the facade Gatherv uses true per-shard
    counts (the reference's equal-block assumption, test.py:145, would
    misassemble there under real mpi4py).
    """

    def run_reference(self, script, nranks):
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            " --xla_force_host_platform_device_count=8").strip()
        cmd = [sys.executable, os.path.join(REPO, "tools", "tpurun.py"),
               "-n", str(nranks), os.path.join(REFERENCE_DIR, script)]
        return subprocess.run(cmd, capture_output=True, text=True, env=env,
                              timeout=600, cwd=REPO)

    @pytest.mark.parametrize("nranks", [1, 2, 3, 4])
    def test_reference_test_py_verbatim(self, nranks):
        r = self.run_reference("test.py", nranks)
        assert r.returncode == 0, r.stderr
        assert "True" in r.stdout, r.stdout

    @pytest.mark.parametrize("nranks", [1, 4])
    def test_reference_test2_py_verbatim(self, nranks):
        """test2.py imports the reference's own petsc_funcs (sibling module,
        test2.py:4) which in turn imports the petsc4py/slepc4py facades;
        getEigenpair(i, vr, vi) is called positionally under rank==0 only
        (test2.py:94-96) — the facade keeps that collective-safe."""
        r = self.run_reference("test2.py", nranks)
        assert r.returncode == 0, r.stderr
        assert "Eigenvalue:" in r.stdout, r.stdout
        # dominant eigenvalue of the n=100 symmetric tridiagonal family
        lam = complex(
            r.stdout.split("Eigenvalue:")[1].strip().splitlines()[0]).real
        CSR = tridiag_family(100)
        lam_exact = np.linalg.eigvalsh(CSR.toarray())
        target = lam_exact[np.argmax(np.abs(lam_exact))]
        np.testing.assert_allclose(lam, target, rtol=1e-6)


class TestDriverOptionsOverride:
    def test_solve_linear_gmres(self):
        """BASELINE configs: same driver, solver swapped from the CLI.

        Uses unpreconditioned GMRES on the unsymmetric random system (its
        diagonal is mostly zero — scipy.sparse.random — so Jacobi would be
        singular, and restarted GMRES(30) stagnates on this nonnormal matrix
        exactly as real PETSc's does — full-Krylov restart=100 converges)."""
        r = run_driver("examples/solve_linear.py", 4,
                       ("-ksp_type", "gmres", "-pc_type", "none",
                        "-ksp_rtol", "1e-12", "-ksp_max_it", "2000",
                        "-ksp_gmres_restart", "100"))
        assert r.returncode == 0, r.stderr
        assert "True" in r.stdout

    def test_eigensolve_nev(self):
        r = run_driver("examples/eigensolve.py", 4, ("-eps_nev", "4"))
        assert r.returncode == 0, r.stderr
        assert r.stdout.count("Eigenvalue:") >= 4
