"""Megasolve — whole-solve fusion (ISSUE 12): one dispatch per request.

The fused programs (solvers/megasolve.py) run the ENTIRE refinement/
verification recurrence — inner low-precision CG, fp64 true residual,
correction AXPY, exit-gate verification — as one ``lax.while_loop``
device program. These tests pin the tentpole's contracts:

* fused-vs-unfused parity at fp64 rtol 1e-10 (RefinedKSP, KSP, and the
  batched blocks), with the fused answer's TRUE residual meeting the
  target by construction;
* the one-dispatch measurement: the telemetry ``dispatch.programs``
  counter and the root span's ``dispatches`` attribute both read
  exactly 1 per fused request (vs one launch per outer refine step
  unfused);
* resilience semantics: a bitflip inside the fused loop is detected by
  the nested guarded plan, the caller's iterate rolls back to the
  verified carry, and the resilient ladder re-enters to a verified
  answer at one dispatch per attempt;
* routing: ``-ksp_megasolve`` options wiring, silent fallback for
  configurations without a fused equivalent, serving sessions
  dispatching coalesced blocks as one launch.
"""

import numpy as np
import pytest
import scipy.sparse as sp

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu import telemetry
from mpi_petsc4py_example_tpu.solvers.refine import RefinedKSP
from mpi_petsc4py_example_tpu.utils.errors import SilentCorruptionError
from mpi_petsc4py_example_tpu.utils.profiling import dispatch_counts


def _spd(n, seed=3):
    """A well-conditioned SPD test operator (diagonally dominant)."""
    rng = np.random.default_rng(seed)
    A = sp.random(n, n, density=0.02, random_state=rng, format="csr")
    A = A + A.T
    A = A + sp.eye(n, format="csr") * (abs(A).sum(axis=1).max() + 1.0)
    return A.tocsr()


def _poisson1d(n):
    return sp.diags([-1, 2.0001, -1], [-1, 0, 1], shape=(n, n)).tocsr()


def _refined(comm, A, precision="f32", ksp_type="cg", fused=False,
             rtol=1e-10, **knobs):
    rk = RefinedKSP().create(comm)
    rk.set_inner_precision(precision)
    rk.set_operators(A)
    rk.set_type(ksp_type)
    rk.get_pc().set_type("jacobi")
    rk.set_tolerances(rtol=rtol)
    rk.megasolve = fused
    for k, v in knobs.items():
        setattr(rk.inner, k, v)
    return rk


def _ksp(comm, M, ksp_type="cg", fused=True, rtol=1e-10, **knobs):
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type(ksp_type)
    ksp.get_pc().set_type("jacobi")
    ksp.set_tolerances(rtol=rtol, max_it=20000)
    ksp.megasolve = fused
    for k, v in knobs.items():
        setattr(ksp, k, v)
    return ksp


class TestRefinedFusedParity:
    """Fused RefinedKSP == unfused RefinedKSP at fp64 rtol 1e-10."""

    @pytest.mark.parametrize("precision", ["f32", "bf16", "f64"])
    def test_parity_across_inner_precisions(self, comm8, precision):
        A = _spd(512)
        rng = np.random.default_rng(0)
        b = rng.standard_normal(512)
        bn = np.linalg.norm(b)
        xu, ru = _refined(comm8, A, precision).solve(b)
        rk = _refined(comm8, A, precision, fused=True)
        xf, rf = rk.solve(b)
        assert rf.converged, rf
        # the fused exit gate IS the fp64 true residual: verified
        assert np.linalg.norm(b - A @ xf) <= 1e-10 * bn * 1.05
        assert np.linalg.norm(b - A @ xu) <= 1e-10 * bn * 1.05
        # same answer to refinement accuracy
        assert np.linalg.norm(xf - xu) <= 1e-8 * np.linalg.norm(xu)

    def test_pipecg_inner_fused(self, comm8):
        A = _spd(512)
        b = np.random.default_rng(1).standard_normal(512)
        rk = _refined(comm8, A, "f32", ksp_type="pipecg", fused=True)
        x, res = rk.solve(b)
        assert res.converged
        assert (np.linalg.norm(b - A @ x)
                <= 1e-10 * np.linalg.norm(b) * 1.05)

    def test_fused_solve_many_block(self, comm8):
        A = _spd(512)
        B = np.random.default_rng(2).standard_normal((512, 5))
        rk = _refined(comm8, A, "f32", fused=True)
        X, res = rk.solve_many(B)
        assert res.converged, res
        rel = (np.linalg.norm(B - A @ X, axis=0)
               / np.linalg.norm(B, axis=0))
        assert np.all(rel <= 1e-10 * 1.05), rel

    def test_stagnation_parity_with_unfused(self, comm8):
        """An operator bf16 cannot resolve stagnates the SAME way both
        ways (DIVERGED_BREAKDOWN after the 0.9-factor guard)."""
        A = _poisson1d(512)           # cond ~1e5: beyond bf16+jacobi
        b = np.random.default_rng(0).standard_normal(512)
        xu, ru = _refined(comm8, A, "bf16").solve(b)
        xf, rf = _refined(comm8, A, "bf16", fused=True).solve(b)
        assert ru.reason == rf.reason
        assert not rf.converged

    def test_explicit_outer_op_stencil(self, comm8):
        """Custom inner operator + explicit fp64 outer twin: the fused
        exact-residual channel applies the caller's outer operator."""
        import jax.numpy as jnp
        from mpi_petsc4py_example_tpu.models import (StencilPoisson3D,
                                                     poisson3d_csr)
        nx = 16
        A = poisson3d_csr(nx)
        inner = StencilPoisson3D(comm8, nx, nx, nx, dtype=jnp.float32)
        outer = StencilPoisson3D(comm8, nx, nx, nx, dtype=jnp.float64)
        rk = RefinedKSP().create(comm8)
        rk.set_inner_precision("f32")
        rk.set_operators(A, inner_op=inner, outer_op=outer)
        rk.set_type("cg")
        rk.get_pc().set_type("jacobi")
        rk.set_tolerances(rtol=1e-10)
        rk.megasolve = True
        b = np.random.default_rng(4).standard_normal(nx ** 3)
        x, res = rk.solve(b)
        assert res.converged
        assert (np.linalg.norm(b - A @ x)
                <= 1e-10 * np.linalg.norm(b) * 1.05)

    def test_custom_inner_without_outer_falls_back(self, comm8):
        """A custom inner operator with NO fp64 twin cannot fuse — the
        solve silently takes the unfused host loop (and still
        converges)."""
        import jax.numpy as jnp
        from mpi_petsc4py_example_tpu.models import (StencilPoisson3D,
                                                     poisson3d_csr)
        nx = 16
        A = poisson3d_csr(nx)
        inner = StencilPoisson3D(comm8, nx, nx, nx, dtype=jnp.float32)
        rk = RefinedKSP().create(comm8)
        rk.set_inner_precision("f32")
        rk.set_operators(A, inner_op=inner)
        rk.set_type("cg")
        rk.get_pc().set_type("jacobi")
        rk.set_tolerances(rtol=1e-10)
        rk.megasolve = True
        assert not rk._megasolve_available()
        b = np.random.default_rng(4).standard_normal(nx ** 3)
        x, res = rk.solve(b)
        assert res.converged


class TestKSPFusedPath:
    """-ksp_megasolve on a uniform-precision KSP: the in-program
    true-residual gate at one dispatch."""

    @pytest.mark.parametrize("ksp_type", ["cg", "pipecg"])
    def test_fused_verified_answer(self, comm8, ksp_type):
        A = _spd(512)
        M = tps.Mat.from_scipy(comm8, A)
        b = np.random.default_rng(5).standard_normal(512)
        ksp = _ksp(comm8, M, ksp_type)
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)
        assert res.converged
        # the reported rnorm IS the true residual (the exit gate's own
        # measurement)
        rtrue = np.linalg.norm(b - A @ x.to_numpy())
        assert res.residual_norm == pytest.approx(rtrue, rel=1e-6)
        assert rtrue <= 1e-10 * np.linalg.norm(b) * 1.05
        assert res.megasolve_steps >= 1

    def test_fused_matches_unfused_gated(self, comm8):
        A = _spd(512)
        M = tps.Mat.from_scipy(comm8, A)
        b = np.random.default_rng(6).standard_normal(512)
        xs = []
        for fused in (False, True):
            ksp = _ksp(comm8, M, fused=fused)
            if not fused:
                ksp.set_true_residual_check(True)
            x, bv = M.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            assert res.converged
            xs.append(x.to_numpy())
        assert np.linalg.norm(xs[0] - xs[1]) \
            <= 1e-8 * np.linalg.norm(xs[0])

    def test_fused_solve_many_per_column(self, comm8):
        """Batched fused: per-column convergence, mixed easy/hard
        columns both land on their targets."""
        A = _spd(512)
        M = tps.Mat.from_scipy(comm8, A)
        rng = np.random.default_rng(7)
        B = rng.standard_normal((512, 4))
        B[:, 2] *= 1e-3               # small-scale column
        ksp = _ksp(comm8, M)
        res = ksp.solve_many(B)
        assert res.converged, res.reasons
        rel = (np.linalg.norm(B - A @ res.X, axis=0)
               / np.linalg.norm(B, axis=0))
        assert np.all(rel <= 1e-10 * 1.05), rel
        assert res.megasolve_steps >= 1

    def test_nonzero_initial_guess(self, comm8):
        A = _spd(512)
        M = tps.Mat.from_scipy(comm8, A)
        b = np.random.default_rng(8).standard_normal(512)
        ksp = _ksp(comm8, M)
        ksp.set_initial_guess_nonzero(True)
        x, bv = M.get_vecs()
        bv.set_global(b)
        # a warm guess near the answer converges in far fewer inner
        # iterations than a cold start
        cold = _ksp(comm8, M)
        xc, bc = M.get_vecs()
        bc.set_global(b)
        rc = cold.solve(bc, xc)
        x.set_global(xc.to_numpy() + 1e-6)
        res = ksp.solve(bv, x)
        assert res.converged
        assert res.iterations < rc.iterations

    def test_ineligible_configurations_fall_back(self, comm8):
        """No fused equivalent -> the unfused path, silently: non-CG
        types, monitors, norm-type overrides."""
        A = _spd(256)
        M = tps.Mat.from_scipy(comm8, A)
        b = np.random.default_rng(9).standard_normal(256)
        # gmres: no fused program
        ksp = _ksp(comm8, M, ksp_type="gmres", rtol=1e-8)
        assert not ksp._megasolve_eligible()
        x, bv = M.get_vecs()
        bv.set_global(b)
        assert ksp.solve(bv, x).converged
        # a monitor forces the unfused (history-capable) program
        ksp2 = _ksp(comm8, M, rtol=1e-8)
        seen = []
        ksp2.set_monitor(lambda k, it, rn: seen.append(it))
        assert not ksp2._megasolve_eligible()
        x2, bv2 = M.get_vecs()
        bv2.set_global(b)
        assert ksp2.solve(bv2, x2).converged
        assert seen                   # the monitor actually fired
        # norm-type override
        ksp3 = _ksp(comm8, M, rtol=1e-8)
        ksp3.set_norm_type("natural")
        assert not ksp3._megasolve_eligible()

    def test_options_wiring(self, comm8):
        """-ksp_megasolve arms KSP and RefinedKSP via set_from_options;
        RefinedKSP keeps its INNER KSP unfused (the refinement loop is
        fused at the outer level, never nested twice)."""
        tps.global_options().set("ksp_megasolve", "true")
        try:
            ksp = tps.KSP().create(comm8)
            ksp.set_from_options()
            assert ksp.megasolve is True
            rk = RefinedKSP().create(comm8)
            rk.set_from_options()
            assert rk.megasolve is True
            assert rk.inner.megasolve is False
        finally:
            tps.global_options().clear("ksp_megasolve")


class TestOneDispatch:
    """The tentpole's measured fact: exactly ONE compiled-program launch
    per fused request, read from the telemetry dispatch counter."""

    def test_refined_fused_is_one_launch(self, comm8):
        A = _spd(512)
        b = np.random.default_rng(10).standard_normal(512)
        rk = _refined(comm8, A, "f32", fused=True)
        rk.solve(b)                   # build/compile outside the count
        before = dispatch_counts()
        x, res = rk.solve(b)
        after = dispatch_counts()
        assert int(sum(after.values()) - sum(before.values())) == 1
        assert int(after.get("megasolve", 0)
                   - before.get("megasolve", 0)) == 1

    def test_unfused_refined_pays_per_step(self, comm8):
        A = _spd(512)
        b = np.random.default_rng(10).standard_normal(512)
        rk = _refined(comm8, A, "f32", fused=False)
        rk.solve(b)
        before = dispatch_counts()
        rk.solve(b)
        after = dispatch_counts()
        launches = int(sum(after.values()) - sum(before.values()))
        assert launches == rk.refine_steps >= 2

    def test_root_span_dispatches_attr(self, comm8):
        """With telemetry armed, the refine.outer root span carries
        dispatches=1 for the fused solve — the -log_view/flight view of
        the same measurement."""
        A = _spd(512)
        b = np.random.default_rng(11).standard_normal(512)
        rk = _refined(comm8, A, "f32", fused=True)
        rk.solve(b)
        telemetry.enable()
        try:
            telemetry.flight_recorder.clear()
            rk.solve(b)
            roots = [t for t in telemetry.flight_recorder.spans()
                     if t["name"] == "refine.outer"]
            assert roots and roots[-1]["attrs"]["dispatches"] == 1, roots
        finally:
            telemetry.disable()
            telemetry.flight_recorder.clear()

    def test_fused_solve_many_is_one_launch(self, comm8):
        A = _spd(512)
        M = tps.Mat.from_scipy(comm8, A)
        B = np.random.default_rng(12).standard_normal((512, 4))
        ksp = _ksp(comm8, M)
        ksp.solve_many(B)
        before = dispatch_counts()
        ksp.solve_many(B)
        after = dispatch_counts()
        assert int(sum(after.values()) - sum(before.values())) == 1
        assert int(after.get("megasolve_many", 0)
                   - before.get("megasolve_many", 0)) == 1

    def test_log_view_dispatch_row(self, comm8, capsys):
        from mpi_petsc4py_example_tpu.utils.profiling import (clear_events,
                                                              log_view)
        import sys
        A = _spd(256)
        b = np.random.default_rng(13).standard_normal(256)
        rk = _refined(comm8, A, "f32", fused=True, rtol=1e-8)
        clear_events()
        rk.solve(b)
        log_view(file=sys.stdout)
        out = capsys.readouterr().out
        assert "compiled-program dispatches:" in out
        assert "megasolve: 1" in out


class TestFusedGuardResilience:
    """Detection inside the fused loop surfaces the verified-iterate
    carry exactly as the unfused path does."""

    def test_bitflip_detected_and_rolled_back(self, comm8):
        A = _spd(512)
        M = tps.Mat.from_scipy(comm8, A)
        b = np.random.default_rng(14).standard_normal(512)
        ksp = _ksp(comm8, M, abft=True)
        x, bv = M.get_vecs()
        bv.set_global(b)
        with tps.inject_faults("spmv.result=bitflip:at=2:times=1"):
            with pytest.raises(SilentCorruptionError) as ei:
                ksp.solve(bv, x)
        assert ei.value.detector == "abft"
        # rollback target: the zero-guess fused solve detects during the
        # FIRST correction, so the verified carry is the initial iterate
        np.testing.assert_array_equal(x.to_numpy(), 0.0)

    def test_resilient_reentry_to_verified_answer(self, comm8):
        A = _spd(512)
        M = tps.Mat.from_scipy(comm8, A)
        x_true = np.random.default_rng(15).random(512)
        b = A @ x_true
        ksp = _ksp(comm8, M, abft=True)
        x, bv = M.get_vecs()
        bv.set_global(b)
        with tps.inject_faults("spmv.result=bitflip:at=2:times=1"):
            res = tps.resilient_solve(
                ksp, bv, x, tps.RetryPolicy(sleep=lambda _d: None))
        assert res.converged
        assert any(e.kind == "fault" and e.detector == "abft"
                   for e in res.recovery_events)
        assert any(e.kind == "verify" for e in res.recovery_events)
        np.testing.assert_allclose(x.to_numpy(), x_true, atol=1e-7)

    def test_batched_fused_guard_detects(self, comm8):
        A = _spd(512)
        M = tps.Mat.from_scipy(comm8, A)
        B = np.random.default_rng(16).standard_normal((512, 3))
        ksp = _ksp(comm8, M, abft=True)
        with tps.inject_faults("spmv.result=bitflip:at=2:times=1"):
            with pytest.raises(SilentCorruptionError):
                ksp.solve_many(B)

    def test_clean_guarded_fused_parity(self, comm8):
        """The guarded fused program converges to the same verified
        answer as the plain fused one (ABFT adds checks, not error)."""
        A = _spd(512)
        b = np.random.default_rng(17).standard_normal(512)
        M = tps.Mat.from_scipy(comm8, A)
        xs = []
        for abft in (False, True):
            ksp = _ksp(comm8, M, abft=abft)
            x, bv = M.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            assert res.converged
            xs.append(x.to_numpy())
            if abft:
                assert res.abft_checks > 0
        assert np.linalg.norm(xs[0] - xs[1]) \
            <= 1e-9 * np.linalg.norm(xs[0])


class TestFusedServing:
    """A served request is one launch: the session's coalesced blocks
    dispatch through the fused batched program."""

    def test_one_launch_per_dispatched_block(self, comm8):
        from mpi_petsc4py_example_tpu.serving import SolveServer
        A = _spd(512)
        M = tps.Mat.from_scipy(comm8, A)
        rng = np.random.default_rng(18)
        with SolveServer(comm8, window=0.01, autostart=False) as srv:
            srv.register_operator("op", M, pc_type="jacobi", rtol=1e-9,
                                  megasolve=True, warm_widths=(4,))
            before = dispatch_counts()
            rhs = [rng.standard_normal(512) for _ in range(3)]
            futs = [srv.submit("op", b) for b in rhs]
            srv.start()
            results = [f.result(120) for f in futs]
            assert srv.drain(120)
            after = dispatch_counts()
            stats = srv.stats()
        launches = int(after.get("megasolve_many", 0)
                       - before.get("megasolve_many", 0))
        assert launches == stats["batches"] >= 1
        for i, r in enumerate(results):
            assert r.converged, r
            # each request meets its rtol in the fp64 TRUE residual
            rel = np.linalg.norm(rhs[i] - A @ r.x) / np.linalg.norm(rhs[i])
            assert rel <= 1e-9 * 1.05, (i, rel)
        # and no unfused block launches leaked onto the hot path
        assert int(after.get("ksp_many", 0)
                   - before.get("ksp_many", 0)) == 0


class TestStencilFastPath:
    """-ksp_megasolve_stencil_fastpath: the megasolve INNER loop's CG
    plan routes SpMV + <p, Ap> through the stencil operator's fused
    Pallas dot kernel — one fewer reduce site per inner iteration, same
    iterates."""

    def _stencil(self, comm, nx=8):
        from mpi_petsc4py_example_tpu.models import StencilPoisson3D
        return StencilPoisson3D(comm, nx, dtype=np.float64)

    def test_fastpath_parity_and_iterations_single(self, comm8):
        """Fast path on/off produce the SAME iterate sequence: equal
        iteration counts and answers at matched tolerance."""
        op = self._stencil(comm8)
        b = np.random.default_rng(20).standard_normal(op.shape[0])
        outs = []
        for fast in (False, True):
            ksp = _ksp(comm8, op, megasolve_stencil_fastpath=fast)
            x, bv = op.get_vecs()
            bv.set_global(b)
            res = ksp.solve(bv, x)
            assert res.converged, (fast, res)
            outs.append((res.iterations, x.to_numpy()))
        assert outs[0][0] == outs[1][0] > 0
        assert (np.linalg.norm(outs[0][1] - outs[1][1])
                <= 1e-12 * np.linalg.norm(outs[0][1]))

    def test_fastpath_parity_solve_many(self, comm8):
        """Batched twin: per-column masked iteration counts match the
        flat-apply plan column for column."""
        op = self._stencil(comm8)
        B = np.random.default_rng(21).standard_normal((op.shape[0], 4))
        B[:, 2] *= 1e3                     # mixed difficulty/scale
        outs = []
        for fast in (False, True):
            ksp = _ksp(comm8, op, megasolve_stencil_fastpath=fast)
            res = ksp.solve_many(B)
            assert all(r > 0 for r in res.reasons), (fast, res.reasons)
            outs.append(res)
        assert list(outs[0].iterations) == list(outs[1].iterations)
        assert (np.linalg.norm(outs[0].X - outs[1].X)
                <= 1e-12 * np.linalg.norm(outs[0].X))

    def test_fastpath_is_one_launch(self, comm8):
        op = self._stencil(comm8)
        b = np.random.default_rng(22).standard_normal(op.shape[0])
        ksp = _ksp(comm8, op, megasolve_stencil_fastpath=True)
        x, bv = op.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, x)               # compile outside the count
        before = dispatch_counts()
        ksp.solve(bv, x)
        after = dispatch_counts()
        assert int(sum(after.values()) - sum(before.values())) == 1
        assert int(after.get("megasolve", 0)
                   - before.get("megasolve", 0)) == 1

    def test_eligibility_gate(self, comm8):
        """The gate mirrors krylov's stencil_cg gate minus the guarded
        flavors: CG + none/jacobi + a uniform-diagonal stencil operator,
        never a flat ELL Mat, never under the ABFT guard."""
        from mpi_petsc4py_example_tpu.solvers.megasolve import (
            megasolve_stencil_supported)
        op = self._stencil(comm8)
        M = tps.Mat.from_scipy(comm8, _spd(128))
        pc = _ksp(comm8, op).get_pc()
        ksp = _ksp(comm8, op)
        x, bv = op.get_vecs()
        bv.set_global(np.ones(op.shape[0]))
        ksp.solve(bv, x)               # binds pc._mat to the operator
        pc = ksp.get_pc()
        assert megasolve_stencil_supported("cg", pc, op)
        assert megasolve_stencil_supported("cg", pc, op, nrhs=4)
        assert not megasolve_stencil_supported("bicgstab", pc, op)
        assert not megasolve_stencil_supported("cg", pc, op, guard=True)
        assert not megasolve_stencil_supported("cg", pc, M)

    def test_forced_fastpath_on_flat_operator_raises(self, comm8):
        from mpi_petsc4py_example_tpu.solvers.megasolve import (
            build_megasolve_program)
        M = tps.Mat.from_scipy(comm8, _spd(128))
        ksp = _ksp(comm8, M)
        x, bv = M.get_vecs()
        bv.set_global(np.ones(128))
        ksp.solve(bv, x)               # sets up the jacobi PC
        with pytest.raises(ValueError, match="stencil"):
            build_megasolve_program(comm8, "cg", ksp.get_pc(), M, M,
                                    stencil_fastpath=True)

    def test_options_flag_wires_fastpath(self, comm8):
        """-ksp_megasolve_stencil_fastpath flows options -> KSP ->
        builder: the flagged solve matches the unflagged one exactly."""
        op = self._stencil(comm8)
        b = np.random.default_rng(23).standard_normal(op.shape[0])
        ref = _ksp(comm8, op)
        x0, bv0 = op.get_vecs()
        bv0.set_global(b)
        r0 = ref.solve(bv0, x0)
        tps.global_options().set("ksp_megasolve_stencil_fastpath",
                                 "true")
        ksp = _ksp(comm8, op)
        ksp.set_from_options()
        assert ksp.megasolve_stencil_fastpath is True
        x1, bv1 = op.get_vecs()
        bv1.set_global(b)
        r1 = ksp.solve(bv1, x1)
        assert r1.converged and r1.iterations == r0.iterations

    def test_fastpath_reduce_site_contract(self, comm8):
        """The measured fact the tpscheck contract pins: the fused-dot
        inner loop carries 2 reduce sites (flat-apply: 3) inside the
        same (outer, inner) nesting, and the stencil halo exchange
        introduces no all_gather."""
        from mpi_petsc4py_example_tpu import contracts as C
        from mpi_petsc4py_example_tpu.utils import hlo
        fast = C.lower_megasolve(comm8, "cg", operator="stencil",
                                 stencil_fastpath=True)
        flat = C.lower_megasolve(comm8, "cg", operator="stencil",
                                 stencil_fastpath=False)
        assert list(hlo.nested_loop_reduce_site_chain(fast)) == [4, 2]
        assert list(hlo.nested_loop_reduce_site_chain(flat)) == [4, 3]
        assert "all_gather" not in fast
