"""s-step communication-avoiding CG (ISSUE 15): parity, guard, batching,
auto-selection.

The s-step kernel is a REDUCTION PLAN over the composable loop builder
(solvers/cg_plans.sstep_cg_loop): s CG iterations advance per while body
around ONE stacked Gram psum, with the iterations run as host-free
coefficient recurrences in basis coordinates. The contract pinned here:
same answers as classic CG (refined to rtol 1e-10 across operator
families and mesh sizes), exact fixed-iteration counts, ONE reduce site
per s-block (tests/test_collective_volume.py), the CA-CG stability path
(basis-stall detection -> restart -> demote-to-classic-CG with a
RecoveryEvent), and the measured-latency auto-selector
(-ksp_reduction_auto) behind its disk-cached probe.
"""

import json
import os

import numpy as np
import pytest
import scipy.sparse as sp

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.models import (StencilPoisson3D,
                                             poisson2d_csr, poisson3d_csr,
                                             tridiag_family)
from mpi_petsc4py_example_tpu.resilience import faults


def _ell_matrix(n, seed=11):
    rng = np.random.default_rng(seed)
    A = sp.random(n, n, density=0.02, random_state=rng, format="csr")
    A = A + A.T                              # sstep needs SPD
    return (A + sp.eye(n, format="csr") * n).tocsr()


def _operator(kind, comm):
    """(framework operator, host CSR oracle) per operator family."""
    if kind == "ell":
        A = _ell_matrix(512)
        assert tps.Mat.from_scipy(comm, A).dia_vals is None
        return tps.Mat.from_scipy(comm, A), A
    if kind == "dia":
        A = tridiag_family(256)
        M = tps.Mat.from_scipy(comm, A)
        assert M.dia_vals is not None
        return M, A
    nz = ((16 + comm.size - 1) // comm.size) * comm.size
    return (StencilPoisson3D(comm, 16, 16, nz),
            poisson3d_csr(16, 16, nz))


def _solve(comm, op, b, ksp_type, pc="jacobi", rtol=1e-10, max_it=5000,
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


class TestSstepParity:
    """Acceptance: sstep converges to parity with classic CG, refined to
    rtol 1e-10, across ELL/DIA/stencil x 1/4/8 devices."""

    @pytest.mark.parametrize("ndev", [1, 4, 8])
    @pytest.mark.parametrize("kind", ["ell", "dia", "stencil"])
    def test_refined_rtol_1e10_parity(self, ndev, kind):
        from mpi_petsc4py_example_tpu.solvers.refine import RefinedKSP
        comm = tps.DeviceComm(n_devices=ndev)
        _op, A = _operator(kind, comm)
        x_true = np.random.default_rng(3).random(A.shape[0])
        b = np.asarray(A @ x_true)
        rk = RefinedKSP(comm)
        rk.set_inner_precision("f32")
        rk.set_operators(sp.csr_matrix(A))
        rk.set_type("sstep")
        rk.inner.sstep_s = 4
        rk.get_pc().set_type("jacobi")
        rk.set_tolerances(rtol=1e-10)
        x, res = rk.solve(b)
        assert res.converged, (kind, ndev, res)
        rel = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
        assert rel <= 1e-10, (kind, ndev, rel)

    @pytest.mark.parametrize("s", [1, 2, 4, 8])
    def test_iterate_parity_vs_cg(self, comm8, s):
        """Direct fp64 iterate/iteration-count parity: the coordinate
        recurrences reproduce classic CG (to the basis-conditioning
        rounding drift, which grows with s)."""
        op, A = _operator("ell", comm8)
        x_true = np.random.default_rng(5).random(A.shape[0])
        b = np.asarray(A @ x_true)
        xc, rc = _solve(comm8, op, b, "cg")
        xs, rs = _solve(comm8, op, b, "sstep", sstep_s=s)
        assert rs.converged and rc.converged, (rc, rs)
        # the s-step coordinate norms and the re-blocking around the
        # resolution floor shift the exit by a few iterations at most
        slack = max(2 + s, (4 * rc.iterations) // 100)
        assert abs(rs.iterations - rc.iterations) <= slack, (
            rc.iterations, rs.iterations)
        rel = np.linalg.norm(xs - xc) / np.linalg.norm(xc)
        assert rel <= 1e-7, (s, rel)

    def test_stencil_parity_vs_cg(self, comm8):
        """On the sharded stencil every block size converges to classic
        CG's answer (rtol 1e-8, iterates within 1e-6)."""
        op, A = _operator("stencil", comm8)
        b = np.asarray(A @ np.random.default_rng(7).standard_normal(
            A.shape[0]))
        xc, rc = _solve(comm8, op, b, "cg", rtol=1e-8)
        assert rc.converged, rc
        for s in (2, 4, 8):
            xs, rs = _solve(comm8, op, b, "sstep", rtol=1e-8, sstep_s=s)
            assert rs.converged, (s, rs)
            rel = np.linalg.norm(xs - xc) / np.linalg.norm(xc)
            assert rel <= 1e-6, (s, rel)

    def test_pc_none_and_bjacobi(self, comm8):
        op, A = _operator("ell", comm8)
        x_true = np.random.default_rng(7).random(A.shape[0])
        b = np.asarray(A @ x_true)
        for pc in ("none", "bjacobi"):
            xs, rs = _solve(comm8, op, b, "sstep", pc=pc, rtol=1e-9)
            assert rs.converged, (pc, rs)
            rel = np.linalg.norm(xs - x_true) / np.linalg.norm(x_true)
            assert rel <= 1e-7, (pc, rel)

    def test_fixed_iteration_contract(self, comm8):
        """-ksp_norm_type none: EXACTLY max_it iterations whatever the
        blocking (partial blocks freeze by per-step masking)."""
        op, A = _operator("stencil", comm8)
        b = np.asarray(A @ np.ones(A.shape[0]))
        for s, iters in ((2, 21), (4, 10), (8, 40)):
            ksp = tps.KSP().create(comm8)
            ksp.set_operators(op)
            ksp.set_type("sstep")
            ksp.sstep_s = s
            ksp.get_pc().set_type("jacobi")
            ksp.set_norm_type("none")
            ksp.set_tolerances(max_it=iters)
            x, bv = op.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            assert res.iterations == iters, (s, iters, res)
            assert res.reason == tps.ConvergedReason.CONVERGED_ITS

    def test_options_wiring(self, comm8):
        """-ksp_sstep_s / -ksp_sstep_max_replacements /
        -ksp_sstep_auto_replacement / -ksp_reduction_* reach the KSP."""
        opt = tps.global_options()
        opt.set("ksp_type", "sstep")
        opt.set("ksp_sstep_s", 6)
        opt.set("ksp_sstep_max_replacements", 7)
        opt.set("ksp_sstep_auto_replacement", 30)
        opt.set("ksp_reduction_probe_refresh", 1)
        ksp = tps.KSP().create(comm8)
        ksp.set_from_options()
        assert ksp.get_type() == "sstep"
        assert ksp.sstep_s == 6
        assert ksp.sstep_max_replacements == 7
        assert ksp.sstep_auto_replacement == 30
        assert ksp.reduction_probe_refresh is True
        # the sstep auto-replacement arms the drift gate like pipecg's
        assert ksp._effective_replacement() == 30
        ksp.set_type("cg")
        assert ksp._effective_replacement() == 0

    def test_monitor_history(self, comm8):
        """Monitored sstep records one residual per ITERATION (not per
        block), iteration-0 initial norm included."""
        op, A = _operator("ell", comm8)
        b = np.asarray(A @ np.ones(A.shape[0]))
        seen = []
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("sstep")
        ksp.sstep_s = 4
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-9, max_it=2000)
        ksp.set_monitor(lambda _k, it, rn: seen.append((it, rn)))
        x, bv = op.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.converged
        its = [it for it, _ in seen]
        assert its[0] == 0
        assert its == sorted(set(its)), its       # one record per iter
        assert its[-1] == res.iterations


class TestSstepBatched:
    def test_solve_many_parity(self, comm8):
        op, A = _operator("ell", comm8)
        n = A.shape[0]
        Xt = np.random.default_rng(2).random((n, 4))
        B = np.asarray(A @ Xt)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("sstep")
        ksp.sstep_s = 4
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-10, max_it=5000)
        res = ksp.solve_many(B)
        assert res.converged, res
        for j in range(4):
            xj, rj = _solve(comm8, op, B[:, j], "sstep", sstep_s=4)
            assert res.reasons[j] == rj.reason
            assert abs(res.iterations[j] - rj.iterations) <= 4
            rel = np.linalg.norm(res.X[:, j] - xj) / np.linalg.norm(xj)
            assert rel <= 1e-8, (j, rel)

    def test_solve_many_mixed_difficulty_freezes(self, comm8):
        """An easy column freezes while a hard one keeps iterating —
        per-column masked convergence in the lockstep CA-CG blocks."""
        op, A = _operator("dia", comm8)
        n = A.shape[0]
        rng = np.random.default_rng(4)
        B = np.stack([np.asarray(A @ np.ones(n)) * 1e-3,
                      np.asarray(A @ rng.random(n))], axis=1)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("sstep")
        ksp.sstep_s = 4
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-9, max_it=5000)
        res = ksp.solve_many(B)
        assert res.converged, res
        for j in range(2):
            r = np.linalg.norm(B[:, j] - A @ res.X[:, j])
            assert r <= 1e-8 * np.linalg.norm(B[:, j]) * 1.1, (j, r)

    def test_zero_column_freezes_at_zero(self, comm8):
        """A zero RHS column (the serving pow2 padding shape) freezes at
        iteration 0."""
        op, A = _operator("ell", comm8)
        n = A.shape[0]
        B = np.zeros((n, 2))
        B[:, 0] = np.asarray(A @ np.ones(n))
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("sstep")
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-9, max_it=5000)
        res = ksp.solve_many(B)
        assert res.iterations[1] == 0, res.iterations
        assert np.allclose(res.X[:, 1], 0.0)


class TestSstepGuard:
    """The PR-5 silent-corruption guard inside the s-step blocks: ABFT
    partials riding the one stacked Gram psum, and the CA-CG stability
    path (stall -> basis restart -> demote)."""

    def _setup(self, comm):
        A = poisson2d_csr(12)
        M = tps.Mat.from_scipy(comm, A)
        x_true = np.random.default_rng(0).random(A.shape[0])
        return M, A, x_true, np.asarray(A @ x_true)

    def test_clean_path_no_false_positive(self, comm8):
        M, A, x_true, b = self._setup(comm8)
        x, res = _solve(comm8, M, b, "sstep", rtol=1e-10, sstep_s=4,
                        abft=True, residual_replacement=24)
        assert res.converged, res
        assert res.abft_checks > 0
        assert not res.recovery_events       # no demotion on health
        rel = np.linalg.norm(x - x_true) / np.linalg.norm(x_true)
        assert rel <= 1e-7, rel

    @pytest.mark.parametrize("point,at,detector", [
        # basis-build call sites: the init residual is spmv site 1, the
        # first block's P-chain applies follow — at=2 lands inside the
        # s-block's basis build; pc.apply at=3 lands on a chain M apply
        ("spmv.result", 2, "abft"),
        ("spmv.result", 4, "abft"),
        ("pc.apply", 3, "abft_pc"),
    ])
    def test_bitflip_detected(self, comm8, point, at, detector):
        M, A, x_true, b = self._setup(comm8)
        with faults.inject_faults(f"{point}=bitflip:at={at}:times=1"):
            with pytest.raises(tps.SilentCorruptionError) as ei:
                _solve(comm8, M, b, "sstep", rtol=1e-10, sstep_s=4,
                       abft=True)
        assert ei.value.detector == detector

    def test_rollback_and_recovery(self, comm8):
        """resilient_solve through the s-step loop: detection rolls back
        to the verified iterate, re-enters, re-verifies."""
        M, A, x_true, b = self._setup(comm8)
        with faults.inject_faults("spmv.result=bitflip:at=2:times=1"):
            ksp = tps.KSP().create(comm8)
            ksp.set_operators(M)
            ksp.set_type("sstep")
            ksp.sstep_s = 4
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

    def test_ill_conditioned_basis_demotes_to_cg(self, comm8):
        """The satellite acceptance: a deliberately ill-conditioned
        monomial basis (large s on a high-kappa operator) trips the
        stall gate, restarts the basis, and past
        -ksp_sstep_max_replacements demotes to classic CG with a
        RecoveryEvent — and the demoted solve CONVERGES."""
        A = tridiag_family(384)
        M = tps.Mat.from_scipy(comm8, A)
        b = np.asarray(A @ np.random.default_rng(5).random(384))
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("sstep")
        ksp.sstep_s = 12                      # basis cond ~ kappa^(s/2)
        ksp.get_pc().set_type("none")
        ksp.set_tolerances(rtol=1e-12, max_it=8000)
        ksp.residual_replacement = 24
        ksp.sstep_max_replacements = 1
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.converged, res
        evs = [e for e in res.recovery_events if e.kind == "sstep_demote"]
        assert evs, res.recovery_events
        assert evs[0].detector == "drift"
        rel = np.linalg.norm(b - A @ x.to_numpy()) / np.linalg.norm(b)
        assert rel <= 1e-11, rel

    def test_healthy_solve_never_demotes(self, comm8):
        """The demotion budget is a stability escape, not a routine
        path: a well-conditioned solve with the gate armed keeps its
        s-step schedule (no recovery events, type unchanged)."""
        M, A, x_true, b = self._setup(comm8)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("sstep")
        ksp.sstep_s = 4
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-10, max_it=2000)
        ksp.residual_replacement = 24
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.converged and not res.recovery_events, res
        assert ksp.get_type() == "sstep"      # demotion never mutates

    def test_batched_guard_detects_per_column(self, comm8):
        M, A, x_true, b = self._setup(comm8)
        B = np.asarray(A @ np.random.default_rng(6).random(
            (A.shape[0], 3)))
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("sstep")
        ksp.sstep_s = 4
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-10, max_it=5000)
        ksp.abft = True
        with faults.inject_faults("spmv.result=bitflip:at=2:times=1"):
            with pytest.raises(tps.SilentCorruptionError):
                ksp.solve_many(B)
        res = ksp.solve_many(B)               # clean re-solve converges
        assert res.converged, res


class TestSstepMegasolve:
    def test_fused_parity_and_one_dispatch(self, comm8):
        """-ksp_megasolve routes sstep through the fused whole-solve
        program: one launch, verified fp64 true residual."""
        from mpi_petsc4py_example_tpu.utils.profiling import (
            dispatch_counts)
        op, A = _operator("ell", comm8)
        b = np.asarray(A @ np.random.default_rng(9).random(A.shape[0]))
        x_un, r_un = _solve(comm8, op, b, "sstep", sstep_s=4, rtol=1e-9)
        before = dict(dispatch_counts())
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(op)
        ksp.set_type("sstep")
        ksp.sstep_s = 4
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-9, max_it=5000)
        ksp.megasolve = True
        x, bv = op.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        after = dict(dispatch_counts())
        assert res.converged, res
        assert after.get("megasolve", 0) - before.get("megasolve", 0) == 1
        assert after.get("ksp", 0) == before.get("ksp", 0)
        rel = (np.linalg.norm(x.to_numpy() - x_un)
               / np.linalg.norm(x_un))
        assert rel <= 1e-7, rel
        # the fused gate's exit condition IS the true residual
        rres = np.linalg.norm(b - A @ x.to_numpy()) / np.linalg.norm(b)
        assert rres <= 1e-9 * 1.1, rres


class TestSstepRefinedFused:
    def test_refined_megasolve_fused_sstep_one_dispatch(self, comm8):
        """RefinedKSP + -ksp_megasolve + inner sstep: the whole
        refinement recurrence (f32 inner CA-CG blocks nested inside the
        fp64 outer while_loop) runs as ONE launch to the verified fp64
        answer."""
        from mpi_petsc4py_example_tpu.solvers.refine import RefinedKSP
        from mpi_petsc4py_example_tpu.utils.profiling import (
            dispatch_counts)
        A = poisson2d_csr(16)
        x_true = np.random.default_rng(4).random(A.shape[0])
        b = np.asarray(A @ x_true)
        rk = RefinedKSP(comm8)
        rk.set_inner_precision("f32")
        rk.set_operators(A)
        rk.set_type("sstep")
        rk.inner.sstep_s = 4
        rk.get_pc().set_type("jacobi")
        rk.set_tolerances(rtol=1e-10)
        rk.megasolve = True
        before = dict(dispatch_counts())
        x, res = rk.solve(b)
        after = dict(dispatch_counts())
        assert res.converged, res
        rel = np.linalg.norm(b - A @ x) / np.linalg.norm(b)
        assert rel <= 1e-10, rel
        assert (after.get("megasolve", 0)
                - before.get("megasolve", 0)) == 1
        assert after.get("ksp", 0) == before.get("ksp", 0)


class TestSstepServing:
    def test_server_session_dispatches_batched(self, comm8):
        """An sstep serving session coalesces without the no-batched-
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
            srv.register_operator("p", op, ksp_type="sstep",
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

    def test_coalescer_schedule_in_compatibility_key(self):
        """The ISSUE 15 serving satellite: requests whose sessions run
        different reduction plans (or different s) must NEVER share a
        coalesced block, even when operator name, tolerances, and
        precision all match (the re-registered-session hazard)."""
        from concurrent.futures import Future
        from mpi_petsc4py_example_tpu.serving.coalescer import (
            SolveRequest, coalesce)
        mk = lambda sched: SolveRequest(
            op="p", b=np.zeros(4), rtol=1e-8, atol=0.0, max_it=100,
            future=Future(), precision="float64", schedule=sched)
        reqs = [mk("sstep:4"), mk("sstep:4"), mk("sstep:8"), mk("cg"),
                mk("pipecg")]
        batches = coalesce(reqs, max_k=8)
        assert len(batches) == 4, [len(bt) for bt in batches]
        for bt in batches:
            assert len({r.schedule for r in bt}) == 1
        # and the server stamps the session's schedule on its requests
        comm = tps.DeviceComm()
        A = _ell_matrix(512)
        srv = tps.SolveServer(comm, window=0.01, max_k=4,
                              autostart=False)
        srv.register_operator("s4", A, ksp_type="sstep",
                              pc_type="jacobi")
        assert srv._sessions["s4"].schedule == "sstep:4"
        srv.register_operator("pc", A, ksp_type="pipecg",
                              pc_type="jacobi")
        assert srv._sessions["pc"].schedule == "pipecg"
        srv.shutdown(wait=False)


class TestAutoselect:
    def test_model_constants_match_pinned_schedules(self):
        """The selector's site model must mirror the gated schedules:
        cg 3 (general), pipecg 1, sstep 1/s — a drifted model would
        rank plans against schedules the programs don't run."""
        from mpi_petsc4py_example_tpu.solvers.autoselect import (
            _plan_model)
        from mpi_petsc4py_example_tpu.solvers.ksp import KSP
        assert _plan_model("cg", None) == (1.0, 3.0)
        assert _plan_model("cg", None)[1] == KSP._REDUCE_SITES[("cg",
                                                               False)]
        assert _plan_model("pipecg", None)[1] == KSP._REDUCE_SITES[
            ("pipecg", False)]
        for s in (2, 4, 8):
            applies, sites = _plan_model("sstep", s)
            assert sites == pytest.approx(1.0 / s)
            assert applies == pytest.approx((2 * s - 1) / s)

    def test_ranking_high_latency_prefers_sstep(self):
        from mpi_petsc4py_example_tpu.solvers.autoselect import (
            rank_reduction_plans)
        ranked = rank_reduction_plans(psum_us=500.0, apply_us=100.0)
        assert ranked[0]["ksp_type"] == "sstep"
        assert ranked[0]["s"] == 8
        ranked_low = rank_reduction_plans(psum_us=0.01, apply_us=100.0)
        assert ranked_low[0]["ksp_type"] in ("cg", "pipecg")

    def test_probe_cache_roundtrip_refresh_and_fallback(self, comm8,
                                                        tmp_path,
                                                        monkeypatch):
        """The ISSUE 15 probe-cache satellite: disk round trip keyed by
        machine+mesh, refresh kill switch, silent fallback on a corrupt
        blob."""
        from mpi_petsc4py_example_tpu.solvers import autoselect
        monkeypatch.setenv("TPU_SOLVE_AOT_DIR", str(tmp_path / "aot"))
        v1, cached1 = autoselect.probe_psum_latency_us(comm8)
        assert not cached1 and v1 > 0
        v2, cached2 = autoselect.probe_psum_latency_us(comm8)
        assert cached2 and v2 == v1          # exact round trip
        v3, cached3 = autoselect.probe_psum_latency_us(comm8,
                                                       refresh=True)
        assert not cached3 and v3 > 0        # kill switch re-measures
        # corrupt blob: silent fallback to a fresh measurement
        path = autoselect._probe_path(comm8)
        with open(path, "w", encoding="utf-8") as fh:
            fh.write("{not json")
        v4, cached4 = autoselect.probe_psum_latency_us(comm8)
        assert not cached4 and v4 > 0
        v5, cached5 = autoselect.probe_psum_latency_us(comm8)
        assert cached5                       # rewritten after fallback

    def test_ksp_reduction_auto_selects_and_reports(self, comm8,
                                                    tmp_path,
                                                    monkeypatch):
        """-ksp_reduction_auto at setUp picks a CG-family plan from the
        measured probe, records the report, and never touches non-CG
        types."""
        monkeypatch.setenv("TPU_SOLVE_AOT_DIR", str(tmp_path / "aot"))
        op, A = _operator("ell", comm8)
        b = np.asarray(A @ np.ones(A.shape[0]))
        tps.global_options().set("ksp_reduction_auto", 1)
        try:
            ksp = tps.KSP().create(comm8)
            ksp.set_operators(op)
            ksp.set_type("cg")
            ksp.get_pc().set_type("jacobi")
            ksp.set_from_options()
            ksp.set_tolerances(rtol=1e-8)
            x, bv = op.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            assert res.converged
            rep = ksp._reduction_report
            assert ksp.get_type() == rep.ksp_type
            assert rep.ksp_type in ("cg", "pipecg", "sstep")
            assert rep.psum_us > 0 and rep.apply_us > 0
            assert len(rep.ranking) == 5
            # a gmres KSP must be left alone
            k2 = tps.KSP().create(comm8)
            k2.set_operators(op)
            k2.set_type("gmres")
            k2.set_from_options()
            k2.set_up()
            assert k2.get_type() == "gmres"
        finally:
            tps.global_options().clear()
