"""Auxiliary subsystems: checkpoint/resume, solve-event log, options DB."""

import io

import numpy as np
import pytest
import scipy.sparse as sp

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.models import poisson2d_csr
from mpi_petsc4py_example_tpu.utils import checkpoint, profiling
from mpi_petsc4py_example_tpu.utils.options import Options


class TestCheckpoint:
    def test_vec_roundtrip(self, comm8, tmp_path):
        v = tps.Vec.from_global(comm8, np.arange(37.0))
        p = str(tmp_path / "v.npz")
        checkpoint.save_vec(p, v)
        v2 = checkpoint.load_vec(p, comm8)
        np.testing.assert_array_equal(v2.to_numpy(), v.to_numpy())

    def test_mat_roundtrip_across_mesh_sizes(self, comm8, comm1, tmp_path):
        A = poisson2d_csr(7)
        M = tps.Mat.from_scipy(comm8, A)
        p = str(tmp_path / "m.npz")
        checkpoint.save_mat(p, M)
        M2 = checkpoint.load_mat(p, comm1)  # restore on a different mesh
        assert (M2.to_scipy() != A).nnz == 0

    def test_solve_state_resume(self, comm8, tmp_path):
        """Interrupt a solve, checkpoint, restore, continue to convergence."""
        A = poisson2d_csr(10)
        x_true = np.random.default_rng(0).random(100)
        b = A @ x_true
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_tolerances(rtol=1e-12, max_it=5)  # "interrupted" early
        x, bv = M.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, x)
        p = str(tmp_path / "state.npz")
        checkpoint.save_solve_state(p, M, x, bv,
                                    iteration=ksp.get_iteration_number())
        M2, x2, b2, it0 = checkpoint.load_solve_state(p, comm8)
        assert it0 == 5
        ksp2 = tps.KSP().create(comm8)
        ksp2.set_operators(M2)
        ksp2.set_type("cg")
        ksp2.set_tolerances(rtol=1e-10, max_it=1000)
        ksp2.set_initial_guess_nonzero(True)  # resume from the iterate
        res = ksp2.solve(b2, x2)
        assert res.converged
        np.testing.assert_allclose(x2.to_numpy(), x_true, rtol=1e-7,
                                   atol=1e-9)

    @pytest.mark.parametrize("dtype", [np.float32, np.float64,
                                       np.complex128],
                             ids=["f32", "f64", "c128"])
    def test_solve_state_elastic_roundtrip(self, comm8, comm1, comm,
                                           tmp_path, dtype):
        """save_solve_state on one mesh size restores bit-identically on
        1/3/8-device meshes, across dtypes (the elastic-restart story)."""
        A = poisson2d_csr(7).astype(dtype)
        n = A.shape[0]
        rng = np.random.default_rng(3)
        xh = rng.random(n).astype(dtype)
        bh = rng.random(n).astype(dtype)
        if np.issubdtype(np.dtype(dtype), np.complexfloating):
            xh = xh + 1j * rng.random(n)
            bh = bh + 1j * rng.random(n)
        M = tps.Mat.from_scipy(comm8, A, dtype=dtype)
        x = tps.Vec.from_global(comm8, xh, dtype=dtype)
        b = tps.Vec.from_global(comm8, bh, dtype=dtype)
        p = str(tmp_path / "es.npz")
        checkpoint.save_solve_state(p, M, x, b, iteration=11)
        for target in (comm1, comm, comm8):
            M2, x2, b2, it0 = checkpoint.load_solve_state(p, target)
            assert it0 == 11
            assert np.dtype(str(M2.dtype)) == np.dtype(dtype)
            assert (M2.to_scipy() != A).nnz == 0
            np.testing.assert_array_equal(x2.to_numpy(), xh)
            np.testing.assert_array_equal(b2.to_numpy(), bh)

    def test_resume_converges_in_fewer_iterations(self, comm8, tmp_path):
        """A restored solve finishes in fewer iterations than a cold
        start — the checkpoint actually carries the crashed progress."""
        A = poisson2d_csr(16)
        n = A.shape[0]
        M = tps.Mat.from_scipy(comm8, A)
        x, bv = M.get_vecs()
        bv.set_global(A @ np.ones(n))
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_tolerances(rtol=1e-9)
        cold = ksp.solve(bv, x).iterations
        # redo, interrupted at 3/4 of the cold iteration count
        x.zero()
        ksp.set_tolerances(max_it=max(2, cold * 3 // 4))
        ksp.solve(bv, x)
        p = str(tmp_path / "partial.npz")
        checkpoint.save_solve_state(p, M, x, bv)
        M2, x2, b2, _ = checkpoint.load_solve_state(p, comm8)
        ksp2 = tps.KSP().create(comm8)
        ksp2.set_operators(M2)
        ksp2.set_type("cg")
        ksp2.set_tolerances(rtol=1e-9)
        ksp2.set_initial_guess_nonzero(True)
        res = ksp2.solve(b2, x2)
        assert res.converged
        assert res.iterations < cold


class TestCheckpointHardening:
    """Atomic writes + validated loads (a crash mid-checkpoint can never
    leave a truncated file a later resume trusts)."""

    def test_no_tmp_file_left_behind(self, comm8, tmp_path):
        v = tps.Vec.from_global(comm8, np.arange(9.0))
        p = str(tmp_path / "v.npz")
        checkpoint.save_vec(p, v)
        assert [f.name for f in tmp_path.iterdir()] == ["v.npz"]

    def test_npz_suffix_normalized(self, comm8, tmp_path):
        """A path without .npz saves and loads through the same
        normalization numpy's savez applies."""
        v = tps.Vec.from_global(comm8, np.arange(5.0))
        p = str(tmp_path / "bare")
        checkpoint.save_vec(p, v)
        assert (tmp_path / "bare.npz").exists()
        np.testing.assert_array_equal(
            checkpoint.load_vec(p, comm8).to_numpy(), v.to_numpy())

    def test_truncated_file_rejected(self, comm8, tmp_path):
        """The torn write a non-atomic checkpoint could have produced."""
        v = tps.Vec.from_global(comm8, np.arange(64.0))
        p = tmp_path / "t.npz"
        checkpoint.save_vec(str(p), v)
        p.write_bytes(p.read_bytes()[:40])       # tear it
        with pytest.raises(ValueError, match="unreadable or truncated"):
            checkpoint.load_vec(str(p), comm8)

    def test_wrong_kind_rejected(self, comm8, tmp_path):
        v = tps.Vec.from_global(comm8, np.arange(4.0))
        p = str(tmp_path / "v.npz")
        checkpoint.save_vec(p, v)
        with pytest.raises(ValueError, match="expected 'mat'"):
            checkpoint.load_mat(p, comm8)

    def test_not_a_checkpoint_rejected(self, comm8, tmp_path):
        p = str(tmp_path / "other.npz")
        np.savez(p, something=np.ones(3))
        with pytest.raises(ValueError, match="no 'kind'"):
            checkpoint.load_vec(p, comm8)

    def test_inconsistent_csr_rejected(self, comm8, tmp_path):
        """Tampered/corrupted structure fails validation, not a resume."""
        A = poisson2d_csr(5).tocsr()
        p = str(tmp_path / "bad.npz")
        np.savez(p, kind="mat", shape=np.asarray([25, 25]),
                 indptr=A.indptr[:-3],           # truncated
                 indices=A.indices, data=A.data, dtype="float64")
        with pytest.raises(ValueError, match="indptr"):
            checkpoint.load_mat(p, comm8)

    def test_bad_dtype_rejected(self, comm8, tmp_path):
        A = poisson2d_csr(5).tocsr()
        p = str(tmp_path / "baddt.npz")
        np.savez(p, kind="mat", shape=np.asarray([25, 25]),
                 indptr=A.indptr, indices=A.indices, data=A.data,
                 dtype="not-a-dtype")
        with pytest.raises(ValueError, match="unknown dtype"):
            checkpoint.load_mat(p, comm8)

    def test_solve_state_shape_mismatch_rejected(self, comm8, tmp_path):
        A = poisson2d_csr(5).tocsr()
        p = str(tmp_path / "badx.npz")
        np.savez(p, kind="solve_state", shape=np.asarray([25, 25]),
                 indptr=A.indptr, indices=A.indices, data=A.data,
                 dtype="float64", x=np.ones(7), b=np.ones(25),
                 iteration=0)
        with pytest.raises(ValueError, match="iterate length"):
            checkpoint.load_solve_state(p, comm8)

    def test_validation_survives_optimized_mode(self, comm8, tmp_path):
        """The loaders raise ValueError, never bare assert (asserts
        vanish under python -O)."""
        import subprocess
        import sys
        v = tps.Vec.from_global(comm8, np.arange(4.0))
        p = str(tmp_path / "v.npz")
        checkpoint.save_vec(p, v)
        code = (
            "import numpy as np\n"
            "from mpi_petsc4py_example_tpu.utils import checkpoint\n"
            "import mpi_petsc4py_example_tpu as tps\n"
            "try:\n"
            f"    checkpoint.load_mat({p!r}, tps.DeviceComm())\n"
            "except ValueError:\n"
            "    print('VALUEERROR')\n")
        out = subprocess.run(
            [sys.executable, "-O", "-c", code], capture_output=True,
            text=True, check=True,
            env={**__import__('os').environ, "JAX_PLATFORMS": "cpu"})
        assert "VALUEERROR" in out.stdout


class TestLogView:
    def test_events_recorded_and_printed(self, comm8):
        profiling.clear_events()
        A = poisson2d_csr(6)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(36))
        ksp.solve(b, x)
        evs = profiling.events()
        assert any(e.what.startswith("KSPSolve(cg") for e in evs)
        buf = io.StringIO()
        profiling.log_view(file=buf)
        out = buf.getvalue()
        assert "KSPSolve(cg+none)" in out
        assert "solve(s), total wall" in out

    def test_event_log_is_bounded(self):
        """A process that solves forever keeps the newest RESERVOIR_LEN
        solve events, not all of them."""
        from mpi_petsc4py_example_tpu.telemetry.metrics import RESERVOIR_LEN
        profiling.clear_events()
        try:
            for i in range(RESERVOIR_LEN + 3):
                profiling.record_event("KSPSolve(cg+none)", 4, i, 1e-3, 2)
            evs = profiling.events()
            assert len(evs) == RESERVOIR_LEN
            assert evs[0].iterations == 3
            assert evs[-1].iterations == RESERVOIR_LEN + 2
        finally:
            profiling.clear_events()

    def test_convergence_history(self, comm8):
        """KSPSetResidualHistory analog: per-iteration residual norms."""
        A = poisson2d_csr(8)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_tolerances(rtol=1e-10)
        ksp.set_convergence_history()
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(64))
        res = ksp.solve(b, x)
        h = ksp.get_convergence_history()
        # petsc4py semantics: the iteration-0 initial residual is included
        assert len(h) == res.iterations + 1
        assert h[-1] < h[0]                   # monotone-ish decrease
        np.testing.assert_allclose(h[0], np.linalg.norm(A @ np.ones(64)),
                                   rtol=1e-6)
        np.testing.assert_allclose(h[-1], res.residual_norm, rtol=1e-6)
        # reset=False (petsc4py default): second solve accumulates
        x.zero()
        res2 = ksp.solve(b, x)
        assert len(ksp.get_convergence_history()) == (res.iterations
                                                      + res2.iterations + 2)
        # calling again REPLACES (no stacked recorders); reset=True clears
        # per solve; length truncates
        ksp.set_convergence_history(length=3, reset=True)
        x.zero()
        res3 = ksp.solve(b, x)
        assert len(ksp.get_convergence_history()) == 3
        x.zero()
        ksp.solve(b, x)
        assert len(ksp.get_convergence_history()) == 3   # cleared, refilled

    def test_history_does_not_suppress_monitor_flag(self, comm8, capsys):
        """-ksp_monitor's default printout and the history recorder are
        independent (as in PETSc)."""
        tps.init(["prog", "-ksp_monitor"])
        A = poisson2d_csr(6)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_from_options()
        ksp.set_convergence_history()
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(36))
        res = ksp.solve(b, x)
        out = capsys.readouterr().out
        assert "KSP Residual norm" in out
        assert "   0 KSP Residual norm" in out    # iteration-0 line, as PETSc
        assert len(ksp.get_convergence_history()) == res.iterations + 1

    def test_converged_reason_flag(self, comm8, capsys):
        """-ksp_converged_reason prints PETSc's post-solve line."""
        tps.init(["prog", "-ksp_converged_reason"])
        A = poisson2d_csr(6)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_from_options()
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(36))
        ksp.solve(b, x)
        out = capsys.readouterr().out
        assert "Linear solve converged due to CONVERGED_RTOL" in out

    def test_sync_points_counted(self, comm8):
        """log_view reports host-device sync counts: one KSP result fetch
        per solve; a HEP eigensolve is O(1) — the fused whole-solve program
        keeps every restart's projected eigh on device, so only the final H
        and basis fetches touch the host (VERDICT r2 #4)."""
        profiling.clear_events()
        A = poisson2d_csr(6)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(36))
        ksp.solve(b, x)
        ksp.solve(b, x)
        eps = tps.EPS().create(comm8)
        eps.set_operators(M)
        eps.set_problem_type("hep")
        eps.solve()
        sc = profiling.sync_counts()
        assert sc.get("KSP result fetch/solve") == 2
        assert eps._its >= 1
        assert sc.get("EPS H fetch/solve") == 1        # O(1), not per-restart
        assert sc.get("EPS H fetch/restart", 0) == 0
        assert sc.get("EPS basis fetch/solve") == 1
        buf = io.StringIO()
        profiling.log_view(file=buf)
        assert "host-device sync points" in buf.getvalue()

    def test_sync_points_nhep_per_restart(self, comm8):
        """The NHEP path (host Schur ordering) still counts one projected-
        matrix fetch per restart — the honest accounting for that route."""
        profiling.clear_events()
        rng = np.random.default_rng(5)
        A = poisson2d_csr(6).toarray() + 0.2 * rng.standard_normal((36, 36))
        import scipy.sparse as sp
        M = tps.Mat.from_scipy(comm8, sp.csr_matrix(A))
        eps = tps.EPS().create(comm8)
        eps.set_operators(M)
        eps.set_problem_type("nhep")
        eps.solve()
        sc = profiling.sync_counts()
        assert sc.get("EPS H fetch/restart", 0) == eps._its


class TestOptionsParsing:
    def test_negative_numeric_values(self):
        o = Options()
        o.parse_argv(["prog", "-ksp_atol", "-1e-12", "-shift", "-3"])
        assert o.get_real("ksp_atol") == -1e-12
        assert o.get_int("shift") == -3

    def test_boolean_flags(self):
        o = Options()
        o.parse_argv(["prog", "-ksp_monitor", "-ksp_type", "cg"])
        assert o.get_bool("ksp_monitor") is True
        assert o.get_string("ksp_type") == "cg"

    def test_env_seeding(self, monkeypatch):
        monkeypatch.setenv("TPU_SOLVE_KSP_TYPE", "bcgs")
        o = Options()
        assert o.get_string("ksp_type") == "bcgs"


class TestGetters:
    def test_ksp_tolerances_operators(self, comm8):
        A = sp.eye(10, format="csr")
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_tolerances(rtol=1e-7, atol=1e-40, divtol=1e4, max_it=77)
        assert ksp.get_tolerances() == (1e-7, 1e-40, 1e4, 77)
        Aop, Pop = ksp.get_operators()
        assert Aop is M and Pop is M

    def test_eps_dimensions_tolerances(self, comm8):
        eps = tps.EPS().create(comm8)
        eps.set_dimensions(nev=3, ncv=12)
        eps.set_tolerances(tol=1e-6, max_it=55)
        assert eps.get_dimensions() == (3, 12)
        assert eps.get_tolerances() == (1e-6, 55)

    def test_ksp_operators_unset_raises(self, comm8):
        with pytest.raises(RuntimeError, match="no operators"):
            tps.KSP().create(comm8).get_operators()

    def test_eps_auto_ncv_resolved(self, comm8):
        eps = tps.EPS().create(comm8)
        eps.set_dimensions(nev=2)
        assert eps.get_dimensions() == (2, 17)     # max(4, 17) unsized
        eps.set_operators(tps.Mat.from_scipy(comm8, sp.eye(10, format="csr")))
        assert eps.get_dimensions() == (2, 10)     # capped at n

    def test_ksp_view_flag(self, comm8, capsys):
        """-ksp_view prints the solver configuration after the solve."""
        A = poisson2d_csr(6)
        tps.global_options().parse_argv(["prog", "-ksp_view"])
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_from_options()
        x, bv = M.get_vecs()
        bv.set_global(np.ones(36))
        ksp.solve(bv, x)
        out = capsys.readouterr().out
        assert "KSP Object: type=cg" in out
        assert "norm type:" in out and "divtol=" in out


class TestPhaseStamps:
    def test_concurrent_stamps_keep_valid_json(self, tmp_path, monkeypatch):
        """utils/phases.py: tpurun's virtual ranks stamp from threads; the
        lock + atomic replace must keep the log parseable at all times and
        lose no stamps (the cfg2 artifact itemization depends on it)."""
        import json
        import threading

        from mpi_petsc4py_example_tpu.utils import phases
        log = tmp_path / "phases.json"
        monkeypatch.setenv("TPU_SOLVE_PHASE_LOG", str(log))
        monkeypatch.setattr(phases, "_STAMPS", [])

        def worker(rank):
            for k in range(25):
                phases.stamp(f"r{rank}_k{k}")

        threads = [threading.Thread(target=worker, args=(r,))
                   for r in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        data = json.load(open(log))          # must parse
        assert len(data) == 100              # no stamp lost
        names = {n for n, _ in data}
        assert names == {f"r{r}_k{k}" for r in range(4) for k in range(25)}

    def test_stamp_noop_without_env(self, monkeypatch):
        from mpi_petsc4py_example_tpu.utils import phases
        monkeypatch.delenv("TPU_SOLVE_PHASE_LOG", raising=False)
        before = list(phases._STAMPS)
        phases.stamp("ignored")
        assert phases._STAMPS == before
