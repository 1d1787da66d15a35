"""Shell operators and PC extensibility: ShellMat, PCSHELL, PCCOMPOSITE,
multi-block PCBJACOBI.

PETSc's extension points (MatCreateShell, PCShellSetApply,
PCCompositeAddPCType, -pc_bjacobi_blocks) mapped onto the compiled shard_map
architecture: user functions are jax-traceable and inline into the same XLA
program as the Krylov iteration.
"""

import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sp

import mpi_petsc4py_example_tpu as tps


def poisson1d(n):
    return sp.diags([-np.ones(n - 1), 2 * np.ones(n), -np.ones(n - 1)],
                    [-1, 0, 1]).tocsr()


def poisson2d(nx):
    I = sp.eye(nx)
    T = poisson1d(nx)
    return (sp.kron(I, T) + sp.kron(T, I)).tocsr()


def manufactured(A, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random(A.shape[0])
    return x, A @ x


def shell_from_scipy(comm, A):
    """A ShellMat applying a scipy matrix through dense jnp ops."""
    Ad = jnp.asarray(A.toarray())
    return tps.ShellMat(comm, A.shape, lambda x: Ad @ x,
                        mult_transpose=lambda x: Ad.T @ x,
                        diagonal=np.asarray(A.diagonal()))


def run_ksp(comm, op, b, ksp_type="cg", pc=None, rtol=1e-10, max_it=5000):
    ksp = tps.KSP().create(comm)
    ksp.set_operators(op)
    ksp.set_type(ksp_type)
    if pc is not None:
        if isinstance(pc, str):
            ksp.get_pc().set_type(pc)
        else:
            ksp.set_pc(pc)
    ksp.set_tolerances(rtol=rtol, max_it=max_it)
    x, bv = op.get_vecs()
    bv.set_global(b)
    res = ksp.solve(bv, x)
    return x.to_numpy(), res, ksp


class TestShellMat:
    def test_mult_matches_assembled(self, comm):
        A = poisson2d(7)
        S = shell_from_scipy(comm, A)
        x = np.random.default_rng(1).random(A.shape[0])
        y = S.mult(tps.Vec.from_global(comm, x)).to_numpy()
        np.testing.assert_allclose(y, A @ x, rtol=1e-12)

    @pytest.mark.parametrize("ksp_type", ["cg", "gmres", "bcgs"])
    def test_krylov_on_shell(self, comm, ksp_type):
        A = poisson2d(9)
        x_true, b = manufactured(A)
        S = shell_from_scipy(comm, A)
        x, res, _ = run_ksp(comm, S, b, ksp_type, pc="jacobi")
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)

    def test_transpose_ksp_on_shell(self, comm8):
        """lsqr exercises local_spmv_t (the user mult_transpose)."""
        A = poisson2d(6)
        x_true, b = manufactured(A)
        S = shell_from_scipy(comm8, A)
        x, res, _ = run_ksp(comm8, S, b, "lsqr", rtol=1e-12, max_it=2000)
        np.testing.assert_allclose(x, x_true, rtol=1e-5, atol=1e-7)

    def test_matrix_free_variable_coefficient(self, comm8):
        """A genuinely never-assembled operator: diag(w) + Laplacian."""
        n = 64
        w = 2.0 + np.arange(n) / n

        def mult(x):
            lap = 2 * x - jnp.concatenate([x[1:], jnp.zeros(1)]) \
                - jnp.concatenate([jnp.zeros(1), x[:-1]])
            return jnp.asarray(w) * x + lap

        S = tps.ShellMat(comm8, n, mult, diagonal=w + 2.0)
        A = sp.diags(w) + poisson1d(n)
        x_true, b = manufactured(A.tocsr())
        x, res, _ = run_ksp(comm8, S, b, "cg", pc="jacobi")
        assert res.converged
        np.testing.assert_allclose(x, x_true, rtol=1e-8, atol=1e-10)

    def test_no_diagonal_raises_for_jacobi(self, comm1):
        S = tps.ShellMat(comm1, 8, lambda x: 2.0 * x)
        b = np.ones(8)
        with pytest.raises(ValueError, match="no diagonal"):
            run_ksp(comm1, S, b, "cg", pc="jacobi")

    def test_eps_on_shell(self, comm8):
        """Eigensolve on a matrix-free operator (EPS takes the protocol)."""
        A = poisson1d(40)
        Ad = jnp.asarray(A.toarray())
        S = tps.ShellMat(comm8, 40, lambda x: Ad @ x)
        eps = tps.EPS().create(comm8)
        eps.set_operators(S)
        eps.set_problem_type("hep")
        eps.set_dimensions(nev=1)
        eps.solve()
        assert eps.get_converged() >= 1
        lam = eps.get_eigenpair(0)
        exact = np.linalg.eigvalsh(A.toarray()).max()
        np.testing.assert_allclose(lam, exact, rtol=1e-6)


class TestPCShell:
    def test_shell_jacobi_equivalence(self, comm):
        """A shell PC implementing Jacobi matches the built-in iteration
        count exactly (same preconditioned system)."""
        A = poisson2d(8)
        x_true, b = manufactured(A)
        dinv = jnp.asarray(1.0 / A.diagonal())

        pc = tps.PC(comm)
        pc.set_type("shell")
        pc.set_shell_apply(lambda r: dinv * r)
        x, res, _ = run_ksp(comm, tps.Mat.from_scipy(comm, A), b, "cg", pc=pc)
        x2, res2, _ = run_ksp(comm, tps.Mat.from_scipy(comm, A), b, "cg",
                              pc="jacobi")
        assert res.converged
        assert res.iterations == res2.iterations
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)

    def test_unset_apply_raises(self, comm1):
        A = poisson2d(4)
        pc = tps.PC(comm1)
        pc.set_type("shell")
        with pytest.raises(RuntimeError, match="no apply function"):
            run_ksp(comm1, tps.Mat.from_scipy(comm1, A), np.ones(16), "cg",
                    pc=pc)

    def test_two_instances_no_cache_collision(self, comm1):
        """Two PC instances with different shell fns must compile distinct
        programs (the uid is a global counter, not per-instance)."""
        n = 36
        w = 1.0 + np.arange(n) / 4.0
        A = (poisson2d(6) + sp.diags(w)).tocsr()
        _, b = manufactured(A)
        M = tps.Mat.from_scipy(comm1, A)
        dinv = jnp.asarray(1.0 / A.diagonal())
        pc1 = tps.PC(comm1)
        pc1.set_type("shell")
        pc1.set_shell_apply(lambda r: r)
        _, res1, _ = run_ksp(comm1, M, b, "cg", pc=pc1)
        pc2 = tps.PC(comm1)
        pc2.set_type("shell")
        pc2.set_shell_apply(lambda r: dinv * r)
        _, res2, _ = run_ksp(comm1, M, b, "cg", pc=pc2)
        _, res_j, _ = run_ksp(comm1, M, b, "cg", pc="jacobi")
        assert res2.iterations == res_j.iterations
        assert res1.iterations != res2.iterations

    def test_reset_apply_invalidates_cache(self, comm1):
        """Swapping the shell function must not reuse the old program."""
        n = 36
        w = 1.0 + np.arange(n) / 4.0              # non-constant diagonal —
        A = (poisson2d(6) + sp.diags(w)).tocsr()  # Jacobi ≠ scaled identity
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm1, A)
        dinv = jnp.asarray(1.0 / A.diagonal())

        pc = tps.PC(comm1)
        pc.set_type("shell")
        pc.set_shell_apply(lambda r: r)           # identity → like pc none
        _, res_id, _ = run_ksp(comm1, M, b, "cg", pc=pc)
        pc.set_shell_apply(lambda r: dinv * r)    # now Jacobi
        _, res_j, _ = run_ksp(comm1, M, b, "cg", pc=pc)
        _, res_jb, _ = run_ksp(comm1, M, b, "cg", pc="jacobi")
        assert res_j.iterations == res_jb.iterations
        assert res_id.iterations != res_j.iterations


class TestPCComposite:
    def test_additive_converges(self, comm):
        A = poisson2d(8)
        x_true, b = manufactured(A)
        pc = tps.PC(comm)
        pc.set_type("composite")
        pc.set_composite_pcs("jacobi", "sor")
        x, res, _ = run_ksp(comm, tps.Mat.from_scipy(comm, A), b, "fgmres",
                            pc=pc)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)

    def test_multiplicative_beats_single_child(self, comm8):
        A = poisson2d(10)
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)
        pc = tps.PC(comm8)
        pc.set_type("composite")
        pc.set_composite_type("multiplicative")
        pc.set_composite_pcs("jacobi", "sor")
        x, res, _ = run_ksp(comm8, M, b, "fgmres", pc=pc)
        _, res_j, _ = run_ksp(comm8, M, b, "fgmres", pc="jacobi")
        assert res.converged
        assert res.iterations <= res_j.iterations
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)

    def test_additive_is_sum_of_children(self, comm1):
        """additive(jacobi, jacobi) ≡ scaling by 2/diag — same iterations as
        a shell PC applying exactly that."""
        A = poisson2d(6)
        _, b = manufactured(A)
        M = tps.Mat.from_scipy(comm1, A)
        pc = tps.PC(comm1)
        pc.set_type("composite")
        pc.set_composite_pcs("jacobi", "jacobi")
        _, res, _ = run_ksp(comm1, M, b, "cg", pc=pc)
        dinv = jnp.asarray(2.0 / A.diagonal())
        pc2 = tps.PC(comm1)
        pc2.set_type("shell")
        pc2.set_shell_apply(lambda r: dinv * r)
        _, res2, _ = run_ksp(comm1, M, b, "cg", pc=pc2)
        assert res.iterations == res2.iterations

    def test_options_wiring(self, comm1):
        tps.global_options().set("pc_type", "composite")
        tps.global_options().set("pc_composite_type", "multiplicative")
        tps.global_options().set("pc_composite_pcs", "jacobi,sor")
        A = poisson2d(6)
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm1, A)
        ksp = tps.KSP().create(comm1)
        ksp.set_operators(M)
        ksp.set_type("fgmres")
        ksp.set_from_options()
        pc = ksp.get_pc()
        assert pc.get_type() == "composite"
        assert pc.composite_type == "multiplicative"
        assert [c.get_type() for c in pc._sub_pcs] == ["jacobi", "sor"]
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.converged
        np.testing.assert_allclose(x.to_numpy(), x_true, rtol=1e-6,
                                   atol=1e-8)

    def test_no_children_raises(self, comm1):
        pc = tps.PC(comm1)
        pc.set_type("composite")
        with pytest.raises(RuntimeError, match="no children"):
            run_ksp(comm1, tps.Mat.from_scipy(comm1, poisson2d(4)),
                    np.ones(16), "cg", pc=pc)


class TestBJacobiBlocks:
    def test_explicit_blocks_converge(self, comm8):
        A = poisson2d(8)          # n=64, lsize=8 → 2 blocks/device of 4
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)
        pc = tps.PC(comm8)
        pc.set_type("bjacobi")
        pc.bjacobi_blocks = 16
        x, res, _ = run_ksp(comm8, M, b, "cg", pc=pc)
        assert res.converged
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)

    def test_more_blocks_weaker_pc(self, comm1):
        """One big block is exact (1 iter-ish); many blocks take more."""
        A = poisson2d(8)
        _, b = manufactured(A)
        M = tps.Mat.from_scipy(comm1, A)
        iters = {}
        for blocks in (1, 16):
            pc = tps.PC(comm1)
            pc.set_type("bjacobi")
            pc.bjacobi_blocks = blocks
            _, res, _ = run_ksp(comm1, M, b, "cg", pc=pc)
            assert res.converged
            iters[blocks] = res.iterations
        assert iters[1] < iters[16]

    def test_invalid_blocks_raise(self, comm8):
        A = poisson2d(8)
        M = tps.Mat.from_scipy(comm8, A)
        pc = tps.PC(comm8)
        pc.set_type("bjacobi")
        pc.bjacobi_blocks = 9     # not a multiple of 8 devices
        with pytest.raises(ValueError, match="multiple of the"):
            run_ksp(comm8, M, np.ones(64), "cg", pc=pc)

    def test_auto_split_over_cap(self, comm1, monkeypatch):
        """Past the dense cap the default splits instead of failing (the
        cfg4-on-one-device path). A 3D operator: a 2D five-point one
        takes ILU(0) blocks there (tests/test_bjacobi_ilu.py)."""
        from mpi_petsc4py_example_tpu.models import poisson3d_csr
        from mpi_petsc4py_example_tpu.solvers import pc as pcmod
        monkeypatch.setattr(pcmod, "_DENSE_CAP", 32)
        monkeypatch.setattr(pcmod, "_AUTO_BLOCK_TARGET", 16)
        A = poisson3d_csr(4)      # lsize 64 > cap 32 → auto 4 blocks
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm1, A)
        x, res, ksp = run_ksp(comm1, M, b, "cg", pc="bjacobi")
        assert res.converged
        assert (ksp.get_pc().sub_solve, ksp.get_pc().sub_blocks) == \
            ("dense", 4)
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)


class TestFacadeShell:
    def test_create_shell_and_solve(self):
        import os
        import sys
        compat = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "compat")
        if compat not in sys.path:
            sys.path.insert(0, compat)
        from petsc4py import PETSc
        A = poisson2d(6)
        Ad = jnp.asarray(A.toarray())
        m = PETSc.Mat().createShell(A.shape, lambda x: Ad @ x,
                                    diagonal=np.asarray(A.diagonal()))
        x, b = m.getVecs()
        x_true, bh = manufactured(A)
        b.setArray(bh)
        ksp = PETSc.KSP().create()
        ksp.setOperators(m)
        ksp.setType("cg")
        ksp.getPC().setType("jacobi")
        ksp.setTolerances(rtol=1e-10)
        ksp.solve(b, x)
        np.testing.assert_allclose(x.array, x_true, rtol=1e-7, atol=1e-9)

    def test_mult_transpose_host_level(self, comm8):
        A = poisson2d(6) + sp.diags(np.arange(36.0))
        A = A.tocsr()
        S = shell_from_scipy(comm8, A)
        x = np.random.default_rng(3).random(36)
        y = S.mult_transpose(tps.Vec.from_global(comm8, x)).to_numpy()
        np.testing.assert_allclose(y, A.T @ x, rtol=1e-12)

    def test_mult_transpose_missing_raises(self, comm1):
        S = tps.ShellMat(comm1, 8, lambda v: 2.0 * v)
        with pytest.raises(ValueError, match="mult_transpose"):
            S.mult_transpose(tps.Vec.from_global(comm1, np.ones(8)))

    def test_bicg_with_shell_transpose(self, comm8):
        """A shell PC with both applies runs under bicg; without the
        transpose apply bicg raises the PCApplyTranspose error."""
        n = 36
        w = 1.0 + np.arange(n) / 4.0
        A = (poisson2d(6) + sp.diags(w)).tocsr()
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)
        dinv = jnp.asarray(1.0 / A.diagonal())

        pc = tps.PC(comm8)
        pc.set_type("shell")
        pc.set_shell_apply(lambda r: dinv * r)
        with pytest.raises(ValueError, match="PCApplyTranspose"):
            run_ksp(comm8, M, b, "bicg", pc=pc)
        pc.set_shell_apply_transpose(lambda r: dinv * r)  # symmetric here
        x, res, _ = run_ksp(comm8, M, b, "bicg", pc=pc)
        assert res.converged
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)
