"""Pipelined single-reduction CG (ISSUE 7): parity, guard, batching.

The pipelined kernel is a REDUCTION PLAN over the same composable loop
builder as classic CG (solvers/cg_plans.py), so the contract is: same
answers (iterates to ~rtol), same reasons, iteration counts one higher
(the pipelined norm lags one body), ONE reduce site per iteration (the
collective-volume gate, tests/test_collective_volume.py), and the full
PR-5 silent-corruption guard — ABFT partials folded into the single
stacked psum, replacement bounding the pipelined drift, rollback to
verified iterates — working inside the pipelined recurrences.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.models import (StencilPoisson3D,
                                             poisson3d_csr, tridiag_family)
from mpi_petsc4py_example_tpu.resilience import faults


def _ell_matrix(n, seed=11):
    rng = np.random.default_rng(seed)
    A = sp.random(n, n, density=0.02, random_state=rng, format="csr")
    A = A + A.T                              # pipecg needs SPD
    return (A + sp.eye(n, format="csr") * n).tocsr()


def _operator(kind, comm):
    """(framework operator, host CSR oracle) per operator family."""
    if kind == "ell":
        A = _ell_matrix(512)
        assert tps.Mat.from_scipy(comm, A).dia_vals is None
        return tps.Mat.from_scipy(comm, A), A
    if kind == "dia":
        # n=256 keeps the i+j+1 tridiagonal's conditioning (~n^2) low
        # enough that 1e-10 iterate parity is meaningful rather than
        # sitting exactly at the drift floor of a 500-iteration solve
        A = tridiag_family(256)
        M = tps.Mat.from_scipy(comm, A)
        assert M.dia_vals is not None
        return M, A
    nz = ((16 + comm.size - 1) // comm.size) * comm.size
    return (StencilPoisson3D(comm, 16, 16, nz),
            poisson3d_csr(16, 16, nz))


def _solve(comm, op, b, ksp_type, pc="jacobi", rtol=1e-11, max_it=5000,
           **attrs):
    ksp = tps.KSP().create(comm)
    ksp.set_operators(op)
    ksp.set_type(ksp_type)
    ksp.get_pc().set_type(pc)
    ksp.set_tolerances(rtol=rtol, max_it=max_it)
    for k, v in attrs.items():
        setattr(ksp, k, v)
    x, bv = op.get_vecs()
    bv.set_global(b)
    res = ksp.solve(bv, x)
    return x.to_numpy(), res


class TestPipecgParity:
    """Iterate/reason parity vs classic CG across operator families and
    mesh sizes (the 1/2/4/8-device sweep of the ISSUE acceptance)."""

    @pytest.mark.parametrize("ndev", [1, 2, 4, 8])
    @pytest.mark.parametrize("kind", ["ell", "dia", "stencil"])
    def test_iterate_reason_parity(self, ndev, kind):
        comm = tps.DeviceComm(n_devices=ndev)
        op, A = _operator(kind, comm)
        x_true = np.random.default_rng(3).random(A.shape[0])
        b = np.asarray(A @ x_true)
        xc, rc = _solve(comm, op, b, "cg")
        xp, rp = _solve(comm, op, b, "pipecg")
        assert rc.converged and rp.converged, (rc, rp)
        assert rp.reason == rc.reason
        # the pipelined norm lags one body, biasing pipecg one iteration
        # late; on long ill-conditioned solves the u/w recurrences also
        # follow a different rounding path than classic CG, so the count
        # drifts a few iterations in EITHER direction (the known pipecg
        # trade, bounded by the replacement gate when armed) — pin the
        # count to within max(2, 2%) of classic CG.
        slack = max(2, (2 * rc.iterations) // 100)
        assert abs(rp.iterations - rc.iterations) <= slack, \
            (rc.iterations, rp.iterations)
        rel = np.linalg.norm(xp - xc) / np.linalg.norm(xc)
        assert rel <= 1e-10, rel

    def test_fixed_iteration_contract(self, comm8):
        """-ksp_norm_type none on the stencil: EXACTLY max_it
        iterations, reported as CONVERGED_ITS."""
        op, A = _operator("stencil", comm8)
        b = np.asarray(A @ np.ones(A.shape[0]))
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("pipecg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_norm_type("none")
        ksp.set_tolerances(max_it=13)
        x, bv = op.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.iterations == 13, res
        assert res.reason == tps.ConvergedReason.CONVERGED_ITS

    def test_pc_none_and_bjacobi(self, comm8):
        op, A = _operator("ell", comm8)
        x_true = np.random.default_rng(5).random(A.shape[0])
        b = np.asarray(A @ x_true)
        for pc in ("none", "bjacobi"):
            xp, rp = _solve(comm8, op, b, "pipecg", pc=pc)
            assert rp.converged, (pc, rp)
            rel = np.linalg.norm(xp - x_true) / np.linalg.norm(x_true)
            assert rel <= 1e-8, (pc, rel)

    def test_stencil_fast_path_engaged(self, comm8, monkeypatch):
        """The grid-carry pipelined stencil kernel (no in-loop reshapes)
        must actually be what a stencil pipecg solve runs — the parity
        tests would pass vacuously through the general path."""
        import mpi_petsc4py_example_tpu.solvers.krylov as krylov
        calls = []
        orig = krylov.pipecg_stencil_kernel

        def spy(*a, **k):
            calls.append(1)
            return orig(*a, **k)

        monkeypatch.setattr(krylov, "pipecg_stencil_kernel", spy)
        krylov._PROGRAM_CACHE.clear()
        try:
            op, A = _operator("stencil", comm8)
            b = np.asarray(A @ np.ones(A.shape[0]))
            xp, rp = _solve(comm8, op, b, "pipecg", rtol=1e-9)
            assert rp.converged
            assert calls, "stencil pipecg solve bypassed the fast path"
            np.testing.assert_allclose(xp, np.ones(A.shape[0]),
                                       rtol=1e-6, atol=1e-8)
        finally:
            krylov._PROGRAM_CACHE.clear()


class TestPipecgGuard:
    """ABFT + replacement inside the pipelined recurrences (PR-5 guard
    semantics under the 1-reduce-site schedule)."""

    def _setup(self, comm):
        from mpi_petsc4py_example_tpu.models import poisson2d_csr
        A = poisson2d_csr(12)
        M = tps.Mat.from_scipy(comm, A)
        x_true = np.random.default_rng(0).random(A.shape[0])
        return M, A, x_true, np.asarray(A @ x_true)

    def test_clean_path_no_false_positive(self, comm8):
        M, A, x_true, b = self._setup(comm8)
        x, res = _solve(comm8, M, b, "pipecg", rtol=1e-10, abft=True,
                        residual_replacement=25)
        assert res.converged, res
        assert res.residual_replacements >= 1
        assert res.abft_checks > 0
        rel = np.linalg.norm(x - x_true) / np.linalg.norm(x_true)
        assert rel <= 1e-7, rel

    # the in-loop A apply is the 3rd trace-time call of the pipelined
    # program (init residual, init w = A u, body n = A m); the in-loop
    # PC apply the 2nd (init u = M r, body m = M w)
    @pytest.mark.parametrize("point,at,detector", [
        ("spmv.result", 3, "abft"),
        ("spmv.result", 2, "abft"),          # init w = A u, caught body 1
        ("pc.apply", 2, "abft_pc"),
    ])
    def test_bitflip_detected(self, comm8, point, at, detector):
        M, A, x_true, b = self._setup(comm8)
        with faults.inject_faults(f"{point}=bitflip:at={at}:times=1"):
            with pytest.raises(tps.SilentCorruptionError) as ei:
                _solve(comm8, M, b, "pipecg", rtol=1e-10, abft=True)
        assert ei.value.detector == detector

    def test_rollback_and_recovery(self, comm8):
        """resilient_solve through the pipelined loop: detection rolls
        back to the verified iterate, re-enters, re-verifies."""
        M, A, x_true, b = self._setup(comm8)
        with faults.inject_faults("spmv.result=bitflip:at=3:times=1"):
            ksp = tps.KSP().create(comm8)
            ksp.set_operators(M)
            ksp.set_type("pipecg")
            ksp.get_pc().set_type("jacobi")
            ksp.set_tolerances(rtol=1e-10, max_it=2000)
            ksp.abft = True
            ksp.residual_replacement = 20
            x, bv = M.get_vecs()
            bv.set_global(b)
            res = tps.resilient_solve(ksp, bv, x,
                                      tps.RetryPolicy(sleep=lambda d: None))
        assert res.converged, res
        kinds = [e.kind for e in res.recovery_events]
        assert "rollback" in kinds and "verify" in kinds, kinds
        rel = (np.linalg.norm(x.to_numpy() - x_true)
               / np.linalg.norm(x_true))
        assert rel <= 1e-7, rel

    def test_auto_replacement_knob(self, comm8):
        """-ksp_pipeline_auto_replacement arms the drift bound for
        pipecg when -ksp_residual_replacement is unset — and stays inert
        for classic cg."""
        M, A, x_true, b = self._setup(comm8)
        tps.global_options().set("ksp_pipeline_auto_replacement", 20)
        try:
            for tp, expect_rr in (("pipecg", True), ("cg", False)):
                ksp = tps.KSP().create(comm8)
                ksp.set_operators(M)
                ksp.set_type(tp)
                ksp.get_pc().set_type("jacobi")
                ksp.set_tolerances(rtol=1e-10, max_it=2000)
                ksp.set_from_options()
                x, bv = M.get_vecs()
                bv.set_global(b)
                res = ksp.solve(bv, x)
                assert res.converged, (tp, res)
                got_rr = getattr(res, "residual_replacements", 0) > 0
                assert got_rr == expect_rr, (tp, res)
        finally:
            tps.global_options().clear()


class TestPipecgBatched:
    """solve_many routes pipecg through the batched pipelined kernel:
    per-column results match per-column single solves, masked columns
    freeze, the guard detects per column."""

    def test_solve_many_parity(self, comm8):
        op, A = _operator("ell", comm8)
        n = A.shape[0]
        Xt = np.random.default_rng(2).random((n, 4))
        B = np.asarray(A @ Xt)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("pipecg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-10, max_it=5000)
        res = ksp.solve_many(B)
        assert res.converged, res
        for j in range(4):
            xj, rj = _solve(comm8, op, B[:, j], "pipecg", rtol=1e-10)
            assert res.reasons[j] == rj.reason
            assert abs(res.iterations[j] - rj.iterations) <= 1
            rel = np.linalg.norm(res.X[:, j] - xj) / np.linalg.norm(xj)
            assert rel <= 1e-9, (j, rel)

    def test_solve_many_mixed_difficulty_freezes(self, comm8):
        """An easy column (aligned with the dominant scale) freezes while
        a hard one keeps iterating — per-column masked convergence in the
        pipelined lockstep."""
        op, A = _operator("dia", comm8)
        n = A.shape[0]
        rng = np.random.default_rng(4)
        B = np.stack([np.asarray(A @ np.ones(n)) * 1e-3,
                      np.asarray(A @ rng.random(n))], axis=1)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("pipecg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-10, max_it=5000)
        res = ksp.solve_many(B)
        assert res.converged, res
        for j in range(2):
            r = np.linalg.norm(B[:, j] - A @ res.X[:, j])
            assert r <= 1e-9 * np.linalg.norm(B[:, j]) * 1.1, (j, r)

    def test_solve_many_guarded_detects(self, comm8):
        op, A = _operator("ell", comm8)
        n = A.shape[0]
        B = np.asarray(A @ np.random.default_rng(6).random((n, 3)))
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("pipecg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-10, max_it=5000)
        ksp.abft = True
        # batched program call sites: init R, init W, body N -> at=3
        with faults.inject_faults("spmv.result=bitflip:at=3:times=1"):
            with pytest.raises(tps.SilentCorruptionError):
                ksp.solve_many(B)
        # clean re-solve on the same KSP converges
        res = ksp.solve_many(B)
        assert res.converged, res


class TestPipecgServing:
    def test_server_session_dispatches_batched(self, comm8):
        """A pipecg serving session coalesces without the no-batched-
        kernel warning and answers with residual parity."""
        import warnings
        op, A = _operator("ell", comm8)
        n = A.shape[0]
        rng = np.random.default_rng(8)
        B = np.asarray(A @ rng.random((n, 4)))
        srv = tps.SolveServer(comm8, window=0.01, max_k=8,
                              autostart=False)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            srv.register_operator("p", op, ksp_type="pipecg",
                                  pc_type="jacobi", rtol=1e-9)
        futs = [srv.submit("p", B[:, j]) for j in range(4)]
        srv.start()
        try:
            results = [f.result(300) for f in futs]
        finally:
            srv.shutdown()
        for j, r in enumerate(results):
            assert r.converged, (j, r)
            rres = (np.linalg.norm(B[:, j] - A @ r.x)
                    / np.linalg.norm(B[:, j]))
            assert rres <= 1e-9 * 1.1, (j, rres)
        assert max(r.batch_width for r in results) >= 2
