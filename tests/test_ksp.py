"""KSP solver correctness: manufactured-solution oracles vs scipy.

Mirrors the reference's oracle pattern (generate X, form B=A·X, solve,
compare — ``test.py:12-17`` + ``test.py:148-149``) across every KSP type and
PC combination, on simulated multi-device meshes.
"""

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


def convdiff2d(nx, beta=0.3):
    """Unsymmetric convection-diffusion (5-point + upwind convection)."""
    n = nx * nx
    A = poisson2d(nx).tolil()
    for i in range(n):
        if i + 1 < n:
            A[i, i + 1] -= beta
        if i - 1 >= 0:
            A[i, i - 1] += beta
    return A.tocsr()


def manufactured(A, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random(A.shape[0])
    return x, A @ x


def solve(comm, A, b, ksp_type, pc_type, rtol=1e-10, **kw):
    M = tps.Mat.from_scipy(comm, A)
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type(ksp_type)
    ksp.get_pc().set_type(pc_type)
    ksp.set_tolerances(rtol=rtol, max_it=kw.pop("max_it", 5000))
    for k, v in kw.items():
        setattr(ksp, k, v)
    x, bv = M.get_vecs()
    bv.set_global(b)
    res = ksp.solve(bv, x)
    return x.to_numpy(), res, ksp


class TestCG:
    @pytest.mark.parametrize("pc", ["none", "jacobi", "bjacobi"])
    def test_poisson2d(self, comm, pc):
        A = poisson2d(12)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm, A, b, "cg", pc)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)

    def test_random_spd(self, comm8):
        rng = np.random.default_rng(3)
        B = sp.random(80, 80, density=0.1, random_state=rng)
        A = (B @ B.T + 10 * sp.eye(80)).tocsr()
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "cg", "jacobi")
        assert res.converged
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)

    def test_residual_parity_with_scipy(self, comm8):
        """BASELINE gate: residual parity at rtol=1e-6 vs CPU oracle."""
        A = poisson2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "cg", "none", rtol=1e-6)
        r_ours = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
        assert r_ours <= 1e-6

    def test_iteration_count_reasonable(self, comm8):
        A = poisson1d(64)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "cg", "none")
        # CG on 1-D Poisson converges in at most n iterations
        assert res.iterations <= 64


class TestGMRES:
    @pytest.mark.parametrize("pc", ["none", "jacobi", "bjacobi"])
    def test_convdiff(self, comm, pc):
        A = convdiff2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm, A, b, "gmres", pc, rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    def test_gmres_restart_config(self, comm8):
        A = poisson2d(8)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "gmres", "jacobi", restart=10)
        assert res.converged
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)


class TestBCGS:
    @pytest.mark.parametrize("pc", ["none", "jacobi", "bjacobi"])
    def test_convdiff(self, comm, pc):
        A = convdiff2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm, A, b, "bcgs", pc, rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    @pytest.mark.parametrize("cos, lifted", [(0.9, False), (0.3, True),
                                             (0.0, False)])
    @pytest.mark.parametrize("scale, dtype", [(1.0, np.float64),
                                              (1e-12, np.float32)])
    def test_omega_floor(self, cos, lifted, scale, dtype):
        # omega = (t,s)/(t,t) is scaled to kappa ||s||/||t|| where
        # |cos(t,s)| < kappa, and not at a breakdown (cos 0); at 1e-12 the
        # float32 product (t,t)(s,s) underflows, the floor must not
        from mpi_petsc4py_example_tpu.solvers.krylov import (OMEGA_KAPPA,
                                                             _limit_omega)
        s = np.array([1.0, 0.0]) * scale
        t = 2.0 * np.array([cos, np.sqrt(1 - cos * cos)]) * scale
        ts, tt, ss = (np.asarray(v, dtype) for v in (t @ s, t @ t, s @ s))
        got = float(_limit_omega(ts / tt, ts, tt, ss))
        want = OMEGA_KAPPA / 2 if lifted else cos / 2
        assert got == pytest.approx(want, rel=1e-5)

    @pytest.mark.parametrize("ksp_type", ["bcgs", "fbcgsr"])
    def test_omega_floor_iterates(self, comm8, ksp_type):
        # the kernels take the iterations of a plain numpy BiCGStab with the
        # same floor, on a system where the floor acts
        from mpi_petsc4py_example_tpu.solvers.krylov import OMEGA_KAPPA
        A = convdiff2d(16, beta=0.6)
        _, b = manufactured(A, seed=4)
        dinv = 1.0 / A.diagonal()
        x, r = np.zeros_like(b), b.copy()
        rhat, p, v = r.copy(), np.zeros_like(b), np.zeros_like(b)
        rho = alpha = omega = 1.0
        lifts = 0
        for k in range(1, 500):
            rho_new = rhat @ r
            p = r + rho_new / rho * alpha / omega * (p - omega * v)
            v = A @ (dinv * p)
            alpha = rho_new / (rhat @ v)
            s = r - alpha * v
            t = A @ (dinv * s)
            omega = (t @ s) / (t @ t)
            cos = abs(t @ s) / (np.linalg.norm(t) * np.linalg.norm(s))
            if cos < OMEGA_KAPPA:
                omega *= OMEGA_KAPPA / cos
                lifts += 1
            x += dinv * (alpha * p + omega * s)
            r = s - omega * t
            rho = rho_new
            if np.linalg.norm(r) <= 1e-10 * np.linalg.norm(b):
                break
        assert lifts > 0
        _, res, _ = solve(comm8, A, b, ksp_type, "jacobi", rtol=1e-10)
        assert res.converged, res
        assert abs(res.iterations - k) <= 1, (res.iterations, k)


class TestDirect:
    def test_preonly_lu_reference_system(self, comm):
        """The reference's exact flow: random system, preonly+LU ('mumps')."""
        rng = np.random.default_rng(42)
        A = sp.random(100, 100, density=0.1, format="csr", dtype=np.float64,
                      random_state=rng)
        X = rng.random(100)
        B = A @ X
        ksp_x, res, ksp = solve(comm, A, B, "preonly", "lu", max_it=1)
        assert np.allclose(ksp_x, X)  # the reference's oracle (test.py:148)

    def test_preonly_lu_mumps_string_accepted(self, comm1):
        A = poisson1d(30)
        M = tps.Mat.from_scipy(comm1, A)
        ksp = tps.KSP().create(comm1)
        ksp.set_type("preonly")
        pc = ksp.get_pc()
        pc.set_type("lu")
        pc.set_factor_solver_type("mumps")  # reference string, test.py:43
        ksp.set_operators(M)
        x_true, b = manufactured(A)
        x, bv = M.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, x)
        np.testing.assert_allclose(x.to_numpy(), x_true, rtol=1e-10)

    def test_lu_huge_irreducible_takes_hostlu(self, comm1, monkeypatch):
        """Round 5: past the dense cap, irreducible sparsity the block-CR
        model cannot hold no longer REJECTS — it routes into the host
        sparse-LU fallback (the MUMPS slot's closing move; full coverage
        in tests/test_rcm_direct.py). Caps are patched small so the test
        factorizes a tiny system through the same dispatch."""
        import mpi_petsc4py_example_tpu.solvers.pc as pcmod
        monkeypatch.setattr(pcmod, "_DENSE_CAP", 128)
        monkeypatch.setattr(pcmod, "_BCR_ELEM_CAP", 500)
        pc = tps.PC()
        pc.set_type("lu")
        n = 400
        rng = np.random.default_rng(1)
        R = sp.random(n, n, density=0.02, format="csr", random_state=rng)
        A = (R + R.T + sp.eye(n) * 50.0).tocsr()
        M = tps.Mat.from_scipy(comm1, A)
        pc.set_up(M)
        assert pc._factor_mode == "hostlu"


class TestKSPObject:
    def test_defaults_match_petsc(self):
        ksp = tps.KSP()
        assert ksp.get_type() == "gmres"
        assert ksp.rtol == 1e-5
        assert ksp.max_it == 10000

    def test_unknown_type_raises(self):
        with pytest.raises(ValueError, match="unknown KSP type"):
            tps.KSP().set_type("nosuch")

    def test_monitor_called(self, comm8):
        A = poisson1d(32)
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        seen = []
        ksp.set_monitor(lambda ksp, k, rn: seen.append((k, rn)))
        x, bv = M.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, x)
        assert len(seen) >= 1
        assert seen[-1][1] <= 1e-5 * np.linalg.norm(b)

    def test_converged_reason_names(self):
        assert tps.ConvergedReason.name(2) == "CONVERGED_RTOL"
        assert tps.ConvergedReason.name(-3) == "DIVERGED_MAX_IT"

    def test_max_it_divergence_reported(self, comm8):
        A = poisson2d(12)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "cg", "none", rtol=1e-14, max_it=3)
        assert not res.converged
        assert res.reason == tps.ConvergedReason.DIVERGED_MAX_IT


class TestMINRES:
    @pytest.mark.parametrize("pc", ["none", "jacobi"])
    def test_spd(self, comm8, pc):
        A = poisson2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "minres", pc, rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    def test_symmetric_indefinite(self, comm8):
        """MINRES's raison d'etre: symmetric but indefinite operator."""
        A = (poisson2d(8) - 3.0 * sp.eye(64)).tocsr()
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "minres", "none", rtol=1e-10,
                          max_it=2000)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-5, atol=1e-7)


class TestChebyshev:
    def test_poisson_jacobi(self, comm8):
        A = poisson2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "chebyshev", "jacobi", rtol=1e-8,
                          max_it=5000)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-5, atol=1e-6)


class TestPipelinedCG:
    """Single-reduction CG (Chronopoulos-Gear) — must match CG's answer."""

    @pytest.mark.parametrize("pc", ["none", "jacobi", "bjacobi"])
    def test_spd(self, comm8, pc):
        A = poisson2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "pipecg", pc, rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    def test_iteration_count_close_to_cg(self, comm8):
        A = poisson2d(12)
        _, b = manufactured(A)
        _, r_cg, _ = solve(comm8, A, b, "cg", "jacobi", rtol=1e-8)
        _, r_pipe, _ = solve(comm8, A, b, "pipecg", "jacobi", rtol=1e-8)
        assert abs(r_pipe.iterations - r_cg.iterations) <= 5


class TestFGMRES:
    @pytest.mark.parametrize("pc", ["jacobi", "bjacobi"])
    def test_unsymmetric(self, comm8, pc):
        A = convdiff2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "fgmres", pc, rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    def test_true_residual_norm(self, comm8):
        """FGMRES monitors the unpreconditioned residual."""
        A = poisson2d(8)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "fgmres", "jacobi", rtol=1e-9)
        r = np.linalg.norm(b - A @ x)
        assert r <= 1e-9 * np.linalg.norm(b) * 1.01


class TestCGSAndTFQMR:
    @pytest.mark.parametrize("ksp", ["cgs", "tfqmr"])
    @pytest.mark.parametrize("pc", ["none", "jacobi"])
    def test_unsymmetric(self, comm8, ksp, pc):
        A = convdiff2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, ksp, pc, rtol=1e-10, max_it=2000)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-5, atol=1e-7)

    @pytest.mark.parametrize("ksp", ["cgs", "tfqmr"])
    def test_spd(self, comm8, ksp):
        A = poisson2d(8)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, ksp, "jacobi", rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-5, atol=1e-7)


class TestCR:
    @pytest.mark.parametrize("pc", ["none", "jacobi"])
    def test_spd(self, comm8, pc):
        A = poisson2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "cr", pc, rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)


class TestLSQR:
    def test_banded_unsymmetric(self, comm8):
        """DIA-layout transpose path (convdiff is banded)."""
        A = convdiff2d(8)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "lsqr", "none", rtol=1e-12,
                          max_it=3000)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-5, atol=1e-7)

    def test_general_sparsity_ell_transpose(self, comm8):
        """Random unsymmetric sparse matrix exercises the ELL scatter-add
        transpose (no diagonal structure)."""
        rng = np.random.default_rng(3)
        n = 60
        A = sp.random(n, n, density=0.15, random_state=3,
                      data_rvs=lambda k: rng.random(k)).tocsr()
        A = A + sp.diags(np.full(n, n / 4.0))  # make it nonsingular
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "lsqr", "none", rtol=1e-12,
                          max_it=5000)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-5, atol=1e-7)

    def test_transpose_mult_correct(self, comm8):
        """Direct oracle for local_spmv_t on both layouts."""
        import jax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        for Amat in (convdiff2d(8), sp.random(50, 50, density=0.2,
                                              random_state=1).tocsr()):
            M = tps.Mat.from_scipy(comm8, Amat)
            comm = M.comm
            v = np.random.default_rng(0).random(Amat.shape[0])
            vd = tps.Vec.from_global(comm, v)
            spmv_t = M.local_spmv_t(comm)
            fn = jax.jit(comm.shard_map(
                lambda op, x: spmv_t(op, x),
                (M.op_specs(comm.axis), P(comm.axis)), P(comm.axis)))
            out = np.asarray(fn(M.device_arrays(), vd.data))[:Amat.shape[0]]
            np.testing.assert_allclose(out, Amat.T @ v, rtol=1e-10,
                                       atol=1e-12)


class TestNewPCs:
    """sor/ssor, ilu/icc, asm — block preconditioners."""

    @pytest.mark.parametrize("pc", ["sor", "ssor", "ilu", "icc", "asm"])
    def test_cg_poisson(self, comm8, pc):
        A = poisson2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "cg", pc, rtol=1e-10)
        assert res.converged, (pc, res)
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    @pytest.mark.parametrize("pc", ["sor", "ilu", "asm"])
    def test_gmres_unsymmetric(self, comm8, pc):
        A = convdiff2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "gmres", pc, rtol=1e-10)
        assert res.converged, (pc, res)
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    def test_stronger_than_jacobi(self, comm8):
        """Block PCs must beat pointwise Jacobi on iteration count."""
        A = poisson2d(14)
        _, b = manufactured(A)
        _, r_jac, _ = solve(comm8, A, b, "cg", "jacobi", rtol=1e-8)
        for pc in ("ssor", "ilu", "asm"):
            _, r_pc, _ = solve(comm8, A, b, "cg", pc, rtol=1e-8)
            assert r_pc.iterations < r_jac.iterations, (pc, r_pc, r_jac)

    def test_asm_overlap_helps(self, comm8):
        """More overlap => fewer iterations (the point of Schwarz overlap).

        Restricted additive Schwarz is a NONsymmetric preconditioner even
        for symmetric A, so the comparison runs under GMRES (PETSc makes
        the same caveat for PCASM+CG)."""
        A = poisson2d(12)
        _, b = manufactured(A)
        iters = {}
        for ov in (0, 4):
            M = tps.Mat.from_scipy(comm8, A)
            ksp = tps.KSP().create(comm8)
            ksp.set_operators(M)
            ksp.set_type("gmres")
            pc = ksp.get_pc()
            pc.set_type("asm")
            pc.asm_overlap = ov
            ksp.set_tolerances(rtol=1e-8, max_it=2000)
            x, bv = M.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            assert res.converged
            iters[ov] = res.iterations
        assert iters[4] <= iters[0], iters

    def test_sor_omega_option(self, comm8):
        """-pc_sor_omega reaches the PC through set_from_options."""
        from mpi_petsc4py_example_tpu.utils.options import global_options
        A = poisson2d(8)
        _, b = manufactured(A)
        opt = global_options()
        opt.parse_argv(["prog", "-pc_type", "sor",
                           "-pc_sor_omega", "1.5"])
        try:
            M = tps.Mat.from_scipy(comm8, A)
            ksp = tps.KSP().create(comm8)
            ksp.set_operators(M)
            ksp.set_type("cg")
            ksp.set_from_options()
            assert ksp.get_pc().get_type() == "sor"
            assert ksp.get_pc().sor_omega == 1.5
            x, bv = M.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            assert res.converged
        finally:
            opt.clear()


class TestGAMG:
    """Smoothed-aggregation AMG (PCGAMG analog) — solvers/amg.py."""

    def test_cg_gamg_poisson2d(self, comm):
        A = poisson2d(40)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm, A, b, "cg", "gamg", rtol=1e-9)
        assert res.converged
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_much_faster_than_jacobi(self, comm8):
        A = poisson2d(48)
        _, b = manufactured(A)
        _, res_j, _ = solve(comm8, A, b, "cg", "jacobi", rtol=1e-8)
        _, res_g, _ = solve(comm8, A, b, "cg", "gamg", rtol=1e-8)
        assert res_g.converged
        assert res_g.iterations < res_j.iterations // 3

    def test_mesh_independent_iterations(self, comm8):
        # the AMG promise: iteration counts roughly flat as n grows
        iters = []
        for nx in (16, 32, 48):
            A = poisson2d(nx)
            _, b = manufactured(A)
            _, res, _ = solve(comm8, A, b, "cg", "gamg", rtol=1e-8)
            assert res.converged
            iters.append(res.iterations)
        assert max(iters) <= min(iters) + 6

    def test_amg_alias_and_options(self, comm8):
        A = poisson2d(24)
        x_true, b = manufactured(A)
        opt = tps.global_options()
        opt.set("pc_type", "amg")
        opt.set("pc_gamg_threshold", 0.02)
        opt.set("pc_gamg_coarse_eq_limit", 32)
        try:
            M = tps.Mat.from_scipy(comm8, A)
            ksp = tps.KSP().create(comm8)
            ksp.set_operators(M)
            ksp.set_type("cg")
            ksp.set_from_options()
            assert ksp.get_pc().get_type() == "amg"
            assert ksp.get_pc().gamg_threshold == 0.02
            assert ksp.get_pc().gamg_coarse_size == 32
            ksp.set_tolerances(rtol=1e-10)
            x, bv = M.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            assert res.converged
            np.testing.assert_allclose(x.to_numpy(), x_true, atol=1e-7)
        finally:
            opt.clear()

    def test_tiny_matrix_direct_coarse(self, comm8):
        # n below the coarse cap: hierarchy is a pure direct solve
        A = poisson1d(20)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "cg", "gamg", rtol=1e-10)
        assert res.converged and res.iterations <= 3
        np.testing.assert_allclose(x, x_true, atol=1e-8)

    def test_matrix_free_rejected(self, comm8):
        from mpi_petsc4py_example_tpu.models import StencilPoisson3D
        op = StencilPoisson3D(comm8, 8)
        pc = tps.PC()
        pc.set_type("gamg")
        with pytest.raises(ValueError, match="assembled"):
            pc.set_up(op)

    def test_setup_reuse_cached(self, comm8):
        A = poisson2d(24)
        M = tps.Mat.from_scipy(comm8, A)
        pc = tps.PC()
        pc.set_type("gamg")
        pc.set_up(M)
        h1 = pc._amg
        pc.set_up(M)            # unchanged operator+tunables: no rebuild
        assert pc._amg is h1
        pc.gamg_threshold = 0.1
        pc.set_up(M)            # tunable changed: rebuild
        assert pc._amg is not h1


class TestBiCGAndGCRAndCGNE:
    def test_bicg_unsymmetric(self, comm8):
        A = convdiff2d(16)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "bicg", "jacobi", rtol=1e-10,
                          max_it=2000)
        assert res.converged
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_bicg_matches_cg_on_spd(self, comm8):
        # on SPD systems BiCG reduces to CG (same iterates)
        A = poisson2d(12)
        x_true, b = manufactured(A)
        x_b, res_b, _ = solve(comm8, A, b, "bicg", "jacobi", rtol=1e-10)
        x_c, res_c, _ = solve(comm8, A, b, "cg", "jacobi", rtol=1e-10)
        assert res_b.converged and abs(res_b.iterations - res_c.iterations) <= 1
        np.testing.assert_allclose(x_b, x_c, atol=1e-8)

    def test_gcr_unsymmetric(self, comm):
        A = convdiff2d(16)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm, A, b, "gcr", "jacobi", rtol=1e-10,
                          max_it=3000)
        assert res.converged
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_gcr_flexible_with_gamg(self, comm8):
        A = poisson2d(32)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "gcr", "gamg", rtol=1e-9)
        assert res.converged and res.iterations <= 25
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_cgne_unsymmetric(self, comm8):
        A = convdiff2d(12)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "cgne", "none", rtol=1e-9,
                          max_it=20000)
        assert res.converged
        np.testing.assert_allclose(x, x_true, atol=1e-5)

    def test_transpose_free_operator_rejected(self, comm8):
        from mpi_petsc4py_example_tpu.models import StencilPoisson3D
        op = StencilPoisson3D(comm8, 8)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("bicg")
        x, b = op.get_vecs()
        b.set_global(np.ones(op.shape[0]))
        with pytest.raises(ValueError, match="transpose"):
            ksp.solve(b, x)

    def test_bicg_rejects_pc_without_transpose_apply(self, comm8):
        """PCs with no PCApplyTranspose (asm's restricted windows) raise;
        block kinds (ilu/bjacobi/sor) are supported via transposed inverses
        — see TestBicgTransposePC."""
        A = convdiff2d(8)
        x_true, b = manufactured(A)
        with pytest.raises(ValueError, match="PCApplyTranspose"):
            solve(comm8, A, b, "bicg", "asm")


class TestSymmlqFcgLgmresBcgsl:
    def test_symmlq_spd(self, comm):
        A = poisson2d(12)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm, A, b, "symmlq", "jacobi", rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    def test_symmlq_indefinite(self, comm8):
        # symmetric indefinite (shifted Laplacian) — CG's breakdown case,
        # SYMMLQ's home turf
        A = (poisson2d(12) - 3.0 * sp.eye(144)).tocsr()
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "symmlq", "none", rtol=1e-10,
                          max_it=2000)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-7)

    def test_fcg_spd(self, comm):
        A = poisson2d(12)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm, A, b, "fcg", "jacobi", rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    def test_fcg_flexible_with_gamg(self, comm8):
        A = poisson2d(32)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "fcg", "gamg", rtol=1e-9)
        assert res.converged and res.iterations <= 25
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_lgmres_unsymmetric(self, comm8):
        A = convdiff2d(16)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "lgmres", "jacobi", rtol=1e-10,
                          restart=10, max_it=3000)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_lgmres_beats_restarted_gmres(self, comm8):
        # small restart makes GMRES(m) stall; augmentation recovers it
        A = convdiff2d(20, beta=0.8)
        x_true, b = manufactured(A)
        x_l, res_l, _ = solve(comm8, A, b, "lgmres", "none", rtol=1e-8,
                              restart=6, max_it=4000)
        x_g, res_g, _ = solve(comm8, A, b, "gmres", "none", rtol=1e-8,
                              restart=6, max_it=4000)
        assert res_l.converged
        assert res_l.iterations <= res_g.iterations
        np.testing.assert_allclose(x_l, x_true, atol=1e-6)

    def test_bcgsl_unsymmetric(self, comm):
        A = convdiff2d(16)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm, A, b, "bcgsl", "jacobi", rtol=1e-10,
                          max_it=3000)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_bcgsl_ell3(self, comm8):
        A = convdiff2d(12, beta=0.6)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "bcgsl", "jacobi", rtol=1e-10,
                          max_it=3000, bcgsl_ell=3)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_fbcgs_alias(self, comm8):
        A = convdiff2d(12)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "fbcgs", "ilu", rtol=1e-10)
        assert res.converged
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_fbcgsr_merged_reductions(self, comm8):
        # distinct recurrence (krylov.py::fbcgsr_kernel): same answer as
        # bcgs on an unsymmetric system, via two fused reduction phases
        A = convdiff2d(12)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "fbcgsr", "ilu", rtol=1e-10)
        assert res.converged, res
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_fbcgsr_iteration_parity_with_bcgs(self, comm8):
        # mathematically equivalent recurrences: iteration counts track each
        # other closely on a well-conditioned SPD system
        A = poisson2d(12)
        x_true, b = manufactured(A)
        _, res_f, _ = solve(comm8, A, b, "fbcgsr", "jacobi", rtol=1e-8)
        _, res_b, _ = solve(comm8, A, b, "bcgs", "jacobi", rtol=1e-8)
        assert res_f.converged and res_b.converged
        assert abs(res_f.iterations - res_b.iterations) <= 3

    def test_options_db_new_keys(self, comm8):
        tps.global_options().parse_argv(
            ["prog", "-ksp_type", "lgmres", "-ksp_lgmres_augment", "4",
             "-ksp_bcgsl_ell", "3"])
        ksp = tps.KSP().create(comm8)
        ksp.set_from_options()
        assert ksp.get_type() == "lgmres"
        assert ksp.lgmres_augment == 4
        assert ksp.bcgsl_ell == 3

    def test_lgmres_aug0_is_plain_gmres(self, comm8):
        A = convdiff2d(12)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "lgmres", "jacobi", rtol=1e-9,
                          lgmres_augment=0)
        assert res.converged
        np.testing.assert_allclose(x, x_true, atol=1e-7)

    def test_symmlq_converged_guess_untouched(self, comm8):
        A = poisson2d(10)
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("symmlq")
        ksp.set_tolerances(rtol=1e-6, max_it=500)
        ksp.set_initial_guess_nonzero(True)
        x, bv = M.get_vecs()
        bv.set_global(b)
        x.set_global(x_true)          # exact solution as the initial guess
        res = ksp.solve(bv, x)
        assert res.converged and res.iterations == 0
        np.testing.assert_allclose(x.to_numpy(), x_true, rtol=0, atol=1e-12)


class TestDivtol:
    """KSPSetTolerances dtol — divergence detection (KSP_DIVERGED_DTOL)."""

    def test_richardson_divergence_detected(self, comm8):
        # unpreconditioned Richardson on diag(5): error amplified 4x/iter
        A = sp.diags(np.full(40, 5.0)).tocsr()
        b = np.ones(40)
        x, res, _ = solve(comm8, A, b, "richardson", "none", rtol=1e-10,
                          max_it=300)
        assert res.reason == tps.ConvergedReason.DIVERGED_DTOL
        assert res.iterations < 300      # stopped early, not at max_it

    def test_divtol_disabled_runs_to_maxit(self, comm8):
        A = sp.diags(np.full(40, 5.0)).tocsr()
        b = np.ones(40)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("richardson")
        ksp.get_pc().set_type("none")
        ksp.set_tolerances(rtol=1e-10, divtol=0.0, max_it=25)
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.reason == tps.ConvergedReason.DIVERGED_MAX_IT
        assert res.iterations == 25

    def test_converging_solve_unaffected(self, comm8):
        A = poisson2d(10)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "cg", "jacobi", rtol=1e-10)
        assert res.converged
        np.testing.assert_allclose(x, x_true, rtol=1e-7, atol=1e-9)

    def test_divtol_from_options(self, comm8):
        tps.global_options().parse_argv(["prog", "-ksp_divtol", "1e3"])
        ksp = tps.KSP().create(comm8)
        ksp.set_from_options()
        assert ksp.divtol == 1e3

    def test_large_initial_guess_not_false_divergence(self, comm8):
        """dtol baselines on the INITIAL residual (PETSc), so a far-off
        nonzero guess on a trivial system must converge, not DIVERGED_DTOL."""
        A = sp.eye(16, format="csr")
        b = 1e-3 * np.ones(16)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_tolerances(rtol=1e-10)
        ksp.set_initial_guess_nonzero(True)
        x, bv = M.get_vecs()
        bv.set_global(b)
        x.set_global(1e6 * np.ones(16))
        res = ksp.solve(bv, x)
        assert res.converged, res
        np.testing.assert_allclose(x.to_numpy(), b, rtol=1e-6)


class TestUnroll:
    """-ksp_unroll packs masked CG steps per loop dispatch — iteration
    counts and reasons must be identical to unroll=1, and iterates equal
    to a few ulps (the per-step masking keeps the ARITHMETIC identical,
    but XLA schedules/contracts the differently-shaped loop bodies
    differently — measured: unroll=2 drifts <= 2 ulps on CPU while 4 and
    7 happen to compile bit-identically; demanding bit equality pinned
    compiler instruction scheduling, not solver semantics)."""

    @pytest.mark.parametrize("unroll", [2, 4, 7])
    def test_identical_results(self, comm8, unroll):
        A = poisson2d(12)
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)

        def run(u):
            ksp = tps.KSP().create(comm8)
            ksp.set_operators(M)
            ksp.set_type("cg")
            ksp.get_pc().set_type("jacobi")
            ksp.set_tolerances(rtol=1e-10)
            ksp.unroll = u
            x, bv = M.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            return x.to_numpy(), res

        x1, r1 = run(1)
        xu, ru = run(unroll)
        assert ru.iterations == r1.iterations
        assert ru.reason == r1.reason
        # ulp-level equality: same arithmetic, compiler-scheduling noise
        # only (fp64 eps = 2.2e-16; 1e-14 relative = a few dozen ulps of
        # headroom without admitting any algorithmic drift)
        np.testing.assert_allclose(xu, x1, rtol=1e-14, atol=0.0)

    def test_option_wiring(self, comm8):
        tps.global_options().parse_argv(["prog", "-ksp_unroll", "6"])
        ksp = tps.KSP().create(comm8)
        ksp.set_from_options()
        assert ksp.unroll == 6

    def test_monitored_stays_exact(self, comm8):
        """Monitored solves fall back to unroll=1 — one callback per step."""
        A = poisson2d(8)
        _, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)
        seen = []
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_tolerances(rtol=1e-8)
        ksp.unroll = 4
        ksp.set_monitor(lambda k, it, rn: seen.append(it))
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert len(seen) == res.iterations + 1    # +1: the iteration-0 norm
        assert seen == sorted(set(seen))          # each step exactly once


class TestNormType:
    """KSPSetNormType: 'none' disables the convergence test (smoother mode);
    mismatched types raise rather than silently mislabeling the monitor."""

    def test_none_runs_fixed_iterations(self, comm8):
        A = poisson2d(10)
        _, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_norm_type("none")
        ksp.set_tolerances(rtol=1e-10, max_it=7)
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.iterations == 7
        assert res.reason == tps.ConvergedReason.CONVERGED_ITS
        assert res.converged

    def test_none_as_smoother_reduces_residual(self, comm8):
        A = poisson2d(10)
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("richardson")
        ksp.get_pc().set_type("jacobi")
        ksp.set_norm_type("none")
        ksp.set_tolerances(max_it=5)
        x, bv = M.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, x)
        r = np.linalg.norm(b - A @ x.to_numpy())
        assert r < np.linalg.norm(b)          # smoothing happened

    def test_matching_type_accepted(self, comm8):
        ksp = tps.KSP().create(comm8)
        ksp.set_type("gmres")
        ksp.set_norm_type("preconditioned")
        ksp.set_operators(tps.Mat.from_scipy(comm8, poisson2d(4)))
        ksp._check_norm_type()                # no raise
        assert ksp.get_norm_type() == "preconditioned"

    @pytest.mark.parametrize("ksp_type", ["cg", "fcg", "cr"])
    def test_natural_semantics(self, comm8, ksp_type):
        """KSP_NORM_NATURAL (PETSc's CG default): the monitored norm is
        sqrt <r, M r>, relative tolerance against its initial value. With
        jacobi M the exact value is checkable against the true residual."""
        A = poisson2d(10)
        x_true, b = manufactured(A)
        d = A.diagonal()
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type(ksp_type)
        ksp.get_pc().set_type("jacobi")
        ksp.set_norm_type("natural")          # string key
        ksp.set_tolerances(rtol=1e-9, max_it=500)
        ksp.set_convergence_history()
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.converged
        np.testing.assert_allclose(x.to_numpy(), x_true, atol=1e-6)
        h = ksp.get_convergence_history()
        if ksp_type in ("cg", "fcg"):
            # natural norm of b (zero initial guess): sqrt(b . b/d)
            np.testing.assert_allclose(h[0], np.sqrt(b @ (b / d)),
                                       rtol=1e-10)
            # the reported final norm is the natural norm of the true
            # residual
            r = b - A @ x.to_numpy()
            np.testing.assert_allclose(res.residual_norm,
                                       np.sqrt(max(r @ (r / d), 0.0)),
                                       rtol=1e-5, atol=1e-12)
        assert h[-1] <= 1e-9 * h[0]

    def test_natural_int_constant_and_reject(self, comm8):
        """petsc4py's integer NormType 3 maps to natural; unsupported types
        raise at solve (like PETSc's KSPSetUp check)."""
        M = tps.Mat.from_scipy(comm8, poisson2d(4))
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_norm_type(3)
        assert ksp.get_norm_type() == "natural"
        ksp.set_type("gmres")
        x, bv = M.get_vecs()
        with pytest.raises(ValueError, match="natural"):
            ksp.solve(bv, x)

    def test_natural_matches_default_iterates(self, comm8):
        """The natural norm changes only the MONITORED quantity — the CG
        iterates are identical, so the solution matches the default-norm
        solve at the same iteration count."""
        A = poisson2d(8)
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)

        def run(norm):
            ksp = tps.KSP().create(comm8)
            ksp.set_operators(M)
            ksp.set_type("cg")
            ksp.get_pc().set_type("jacobi")
            if norm:
                ksp.set_norm_type(norm)
            ksp.set_tolerances(rtol=0.0, atol=0.0, max_it=25)
            x, bv = M.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            return x.to_numpy(), res
        xa, ra = run(None)
        xb, rb = run("natural")
        assert ra.iterations == rb.iterations == 25
        np.testing.assert_allclose(xa, xb, rtol=1e-12, atol=1e-14)

    def test_mismatched_type_raises(self, comm8):
        A = poisson2d(4)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("gmres")
        ksp.set_norm_type("unpreconditioned")
        x, bv = M.get_vecs()
        with pytest.raises(ValueError, match="monitors the preconditioned"):
            ksp.solve(bv, x)

    def test_option_wiring(self, comm8):
        tps.global_options().parse_argv(["prog", "-ksp_norm_type", "none"])
        ksp = tps.KSP().create(comm8)
        ksp.set_from_options()
        assert ksp.get_norm_type() == "none"

    def test_default_reporting(self):
        assert tps.KSP().set_type("cg").get_norm_type() == "unpreconditioned"
        assert tps.KSP().set_type("gmres").get_norm_type() == "preconditioned"

    def test_restarted_rejects_none(self, comm8):
        M = tps.Mat.from_scipy(comm8, poisson2d(4))
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("gmres")
        ksp.set_norm_type("none")
        x, bv = M.get_vecs()
        with pytest.raises(ValueError, match="restart cycle"):
            ksp.solve(bv, x)

    def test_bcgsl_rejects_none(self, comm8):
        # bcgsl advances ell steps per loop body, so a fixed max_it contract
        # cannot hold under norm type 'none'
        M = tps.Mat.from_scipy(comm8, poisson2d(4))
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("bcgsl")
        ksp.set_norm_type("none")
        x, bv = M.get_vecs()
        with pytest.raises(ValueError, match="ell steps"):
            ksp.solve(bv, x)

    def test_natural_accepted_at_set(self):
        ksp = tps.KSP().set_norm_type("natural")
        assert ksp.get_norm_type() == "natural"

    def test_integer_enum_accepted(self):
        ksp = tps.KSP()
        ksp.set_norm_type(0)                      # petsc4py NormType.NONE
        assert ksp.get_norm_type() == "none"
        ksp.set_norm_type(2)
        assert ksp._norm_type == "unpreconditioned"

    def test_breakdown_stays_visible_under_none(self, comm8):
        """NORM_NONE must not mask a genuine CG breakdown."""
        A = sp.diags([1.0] * 8 + [-1.0] * 8).tocsr()   # indefinite
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_norm_type("none")
        ksp.set_tolerances(max_it=50)
        x, bv = M.get_vecs()
        b = np.ones(16)
        b[8:] = 1.0
        bv.set_global(b)
        res = ksp.solve(bv, x)
        # on this matrix CG either breaks down (visible) or completes ITS
        assert res.reason in (tps.ConvergedReason.CONVERGED_ITS,
                              tps.ConvergedReason.DIVERGED_BREAKDOWN)


class TestBicgTransposePC:
    """KSPBICG with unsymmetric PCs via PCApplyTranspose (the shadow
    recurrence preconditions with M^T, like PETSc)."""

    @pytest.mark.parametrize("pc", ["bjacobi", "ilu", "sor", "lu"])
    def test_unsymmetric_system(self, comm8, pc):
        A = convdiff2d(10, beta=0.35)
        x_true, b = manufactured(A)
        x, res, _ = solve(comm8, A, b, "bicg", pc, rtol=1e-10, max_it=2000)
        assert res.converged, (pc, res)
        np.testing.assert_allclose(x, x_true, rtol=1e-6, atol=1e-8)

    def test_composite_additive_transpose(self, comm8):
        A = convdiff2d(9, beta=0.3)
        x_true, b = manufactured(A)
        M = tps.Mat.from_scipy(comm8, A)
        pc = tps.PC(comm8)
        pc.set_type("composite")
        pc.set_composite_pcs("jacobi", "sor")
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("bicg")
        ksp.set_pc(pc)
        ksp.set_tolerances(rtol=1e-10, max_it=2000)
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.converged
        np.testing.assert_allclose(x.to_numpy(), x_true, rtol=1e-6,
                                   atol=1e-8)
