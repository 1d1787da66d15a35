"""AOT export/deserialize of solve programs (utils/aot).

A fresh process pays tracing + lowering for every program it builds;
utils/aot serializes each program's StableHLO once (jax.export) and later
processes deserialize it instead of re-tracing. These tests pin, for the
fixed-shape EPS programs and the single-RHS KSP solve programs, the disk
round trip (bit-identical results, no trace of the solve body when
loaded), the key discipline (mesh and package-source fingerprints, no
blob for a key that holds a process-local identity), the silent fallback
on corrupt blobs, and the TPU_SOLVE_AOT=0 kill switch.
"""

import os

import numpy as np
import pytest

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.models import tridiag_family
from mpi_petsc4py_example_tpu.solvers import eps as eps_mod
from mpi_petsc4py_example_tpu.utils import aot


@pytest.fixture()
def aot_dir(tmp_path, monkeypatch):
    d = str(tmp_path / "aot")
    monkeypatch.setenv("TPU_SOLVE_AOT_DIR", d)
    monkeypatch.setenv("TPU_SOLVE_AOT", "1")
    # the facto programs are cached per (mesh, ncv, op) — drop them so
    # every test goes through the aot.wrap build path
    eps_mod._PROGRAM_CACHE.clear()
    yield d
    eps_mod._PROGRAM_CACHE.clear()


def _blobs(d):
    return sorted(f for f in os.listdir(d)) if os.path.isdir(d) else []


def _build_and_run(comm, ncv=16, seed=3):
    M = tps.Mat.from_scipy(comm, tridiag_family(100))
    prog = eps_mod._build_seed_facto_program(comm, M, ncv)
    v0 = comm.put_rows(np.random.default_rng(seed).random(100))
    V, H = prog(M.device_arrays(), (), v0)
    return np.asarray(V), np.asarray(H)


class TestAotRoundTrip:
    def test_export_then_load(self, comm8, aot_dir, monkeypatch):
        V1, H1 = _build_and_run(comm8)
        blobs = _blobs(aot_dir)
        assert len(blobs) == 1 and blobs[0].endswith(".jaxexport")

        # a second process (simulated: fresh program cache) must LOAD the
        # blob — an AOT-loaded program never re-exports, so exporting
        # again is the retrace we are eliminating
        eps_mod._PROGRAM_CACHE.clear()
        import jax

        def no_export(*a, **k):
            raise AssertionError("AOT cache hit must not re-export")
        monkeypatch.setattr(jax.export, "export", no_export)
        loads = []
        real_load = aot._load
        monkeypatch.setattr(aot, "_load",
                            lambda p: loads.append(p) or real_load(p))
        V2, H2 = _build_and_run(comm8)
        assert len(loads) == 1
        np.testing.assert_array_equal(H1, H2)
        np.testing.assert_array_equal(V1, V2)

    def test_full_eigensolve_parity(self, comm8, aot_dir, monkeypatch):
        """End-to-end krylovschur via the HOST-loop flow (the cfg2/TPU
        small-n path AOT targets — the CPU mesh would default to the
        fused whole-solve program) populates the facto blobs; a
        fresh-cache solve from the blobs returns the identical
        eigenvalue."""
        monkeypatch.setenv("TPU_SOLVE_EPS_FUSED", "0")
        CSR = tridiag_family(100)

        def eig_once():
            M = tps.Mat.from_scipy(comm8, CSR)
            e = tps.EPS().create(comm8)
            e.set_operators(M)
            e.set_problem_type("hep")
            e.solve()
            assert e.get_converged() >= 1
            return float(e.get_eigenvalue(0).real)

        lam1 = eig_once()
        assert len(_blobs(aot_dir)) >= 1      # seed-facto at minimum
        eps_mod._PROGRAM_CACHE.clear()
        lam2 = eig_once()
        assert lam1 == lam2
        lam_np = np.linalg.eigvalsh(CSR.toarray())
        lam_np = lam_np[np.argmax(np.abs(lam_np))]
        assert abs(lam1 - lam_np) / abs(lam_np) <= 1e-10

    def test_corrupt_blob_falls_back(self, comm8, aot_dir):
        V1, H1 = _build_and_run(comm8)
        (blob,) = _blobs(aot_dir)
        with open(os.path.join(aot_dir, blob), "wb") as fh:
            fh.write(b"not a jax export")
        eps_mod._PROGRAM_CACHE.clear()
        V2, H2 = _build_and_run(comm8)        # silent re-trace
        np.testing.assert_array_equal(H1, H2)

    def test_stale_blob_shape_mismatch_falls_back(self, comm8, aot_dir):
        """A blob whose key_parts failed to pin some operand geometry must
        never crash the caller: the loaded program's shape rejection falls
        back to the traced program, and says so."""
        import jax
        import jax.numpy as jnp
        f1 = jax.jit(lambda x: x * 2.0)
        w1 = aot.wrap("collide", comm8, ("unpinned",), f1)
        w1(jnp.arange(8.0))                   # export specialized to (8,)
        assert len(_blobs(aot_dir)) == 1
        f2 = jax.jit(lambda x: x * 2.0)
        w2 = aot.wrap("collide", comm8, ("unpinned",), f2)  # loads blob
        assert w2.aot == "hit"
        out = w2(jnp.arange(4.0))             # (4,) != (8,): must not raise
        np.testing.assert_allclose(np.asarray(out), np.arange(4.0) * 2.0)
        assert w2.aot == "fallback"

    def test_key_pins_operand_geometry(self, comm8, aot_dir):
        """Two same-n, same-layout-kind operators with different ELL
        widths must key to DIFFERENT blobs (the exported program is
        shape-specialized, unlike the shape-polymorphic jitted builder)."""
        import scipy.sparse as sp
        rng = np.random.default_rng(0)
        for density in (0.03, 0.2):
            A = sp.random(100, 100, density=density, random_state=rng,
                          format="csr") + sp.eye(100) * 10
            M = tps.Mat.from_scipy(comm8, A.tocsr())
            assert M.dia_vals is None
            prog = eps_mod._build_seed_facto_program(comm8, M, 16)
            v0 = comm8.put_rows(np.random.default_rng(1).random(100))
            prog(M.device_arrays(), (), v0)
            eps_mod._PROGRAM_CACHE.clear()
        assert len(_blobs(aot_dir)) == 2

    def test_key_pins_ncv_and_code(self, comm8, aot_dir):
        _build_and_run(comm8, ncv=16)
        _build_and_run(comm8, ncv=12)
        assert len(_blobs(aot_dir)) == 2      # distinct program keys
        d1 = aot._digest("seedfacto", comm8, (16,), code="a")
        d2 = aot._digest("seedfacto", comm8, (16,), code="b")
        assert d1 != d2                       # code fingerprint in the key


class TestAotGates:
    def test_disabled_env(self, comm8, aot_dir, monkeypatch):
        monkeypatch.setenv("TPU_SOLVE_AOT", "0")
        sentinel = object()
        assert aot.wrap("k", comm8, (), sentinel) is sentinel
        _build_and_run(comm8)
        assert _blobs(aot_dir) == []          # nothing written

    def test_atomic_store_layout(self, comm8, aot_dir):
        _build_and_run(comm8)
        # no .tmp residue from the atomic publish
        assert all(not f.endswith(".tmp") for f in _blobs(aot_dir))

    def test_source_fingerprint(self):
        """Every blob key holds the package-wide source fingerprint:
        computed once per process, the same on every call."""
        fp = aot.package_fingerprint()
        assert len(fp) == 64
        assert fp == aot.package_fingerprint()              # cached
        assert fp == aot.package_fingerprint(aot.PACKAGE_DIR)


# ---------------------------------------------------------------------------
# single-RHS KSP solve programs (krylov.build_ksp_program, kind "ksp")
# ---------------------------------------------------------------------------

def _walk(tree, out):
    out.append(tree)
    for c in tree.get("children", ()):
        _walk(c, out)
    return out


@pytest.fixture()
def spans():
    """Telemetry on for the test; returns a reader of the spans so far."""
    from mpi_petsc4py_example_tpu import telemetry
    telemetry.flight_recorder.clear()
    telemetry.enable(flight_len=1 << 16)

    def read():
        return [s for t in telemetry.flight_recorder.spans()
                for s in _walk(t, [])]
    yield read
    telemetry.disable()
    telemetry.flight_recorder.clear()


def _ksp_case(case, monkeypatch):
    """``(operator, ksp_type, pc_type)`` of one cell-like solve, small."""
    from mpi_petsc4py_example_tpu.models import StencilPoisson3D, convdiff2d
    comm = tps.DeviceComm(n_devices=1)
    if case.startswith("bcgs_bjacobi"):
        from mpi_petsc4py_example_tpu.solvers import bcgs_dd, bjilu
        from mpi_petsc4py_example_tpu.solvers import pc as pcmod
        # past a lowered dense cap: 16 ILU(0) blocks of 2 lines, the XLA
        # sweeps (the TPU kernel's layout is the chip's); "dd": the TPU
        # kernel's stack and the double-f32 loop, in interpret mode
        dd = case == "bcgs_bjacobi_dd"
        monkeypatch.setattr(pcmod, "_DENSE_CAP", 64)
        monkeypatch.setattr(bjilu, "BLOCK_ROWS", 64)
        monkeypatch.setattr(bjilu, "use_kernel", lambda platform, dtype: dd)
        if dd:
            monkeypatch.setattr(bcgs_dd, "PLATFORMS", ("tpu", "cpu"))
        return tps.Mat.from_scipy(comm, convdiff2d(32).tocsr()), "bcgs", \
            "bjacobi"
    return StencilPoisson3D(comm, 16, 16, 16), "cg", case.split("_")[1]


def _ksp_solve(op, ksp_type, pc_type, guess):
    """One gated solve from x0 = 0 or a nonzero guess: ``(x,
    iterations, the PC's kind)``."""
    ksp = tps.KSP().create(op.comm)
    ksp.set_operators(op)
    ksp.set_type(ksp_type)
    ksp.get_pc().set_type(pc_type)
    ksp.set_tolerances(rtol=1e-8, max_it=2000)
    ksp.set_true_residual_check(True)
    n = op.shape[0]
    x, b = op.get_vecs()
    b.set_global(np.random.default_rng(3).random(n))
    if guess == "nonzero":
        x.set_global(np.full(n, 0.5))
    ksp.set_initial_guess_nonzero(guess == "nonzero")
    res = ksp.solve(b, x)
    assert res.converged, res
    return x.to_numpy(), int(res.iterations), ksp.get_pc().kind


def _builds(spans, attr="aot"):
    return [s["attrs"][attr] for s in spans
            if s["name"] == "ksp.setup" and "aot" in s["attrs"]]


def _cold_then_warm(case, guess, spans, monkeypatch):
    """One solve with no blob, then one in a fresh program cache: ``(x,
    iterations, PC kind)`` of each, and each one's spans."""
    from mpi_petsc4py_example_tpu.solvers import krylov
    op, ksp_type, pc_type = _ksp_case(case, monkeypatch)
    krylov._PROGRAM_CACHE.clear()
    n0 = len(spans())
    cold_run = _ksp_solve(op, ksp_type, pc_type, guess)
    cold = spans()[n0:]
    krylov._PROGRAM_CACHE.clear()
    warm_run = _ksp_solve(op, ksp_type, pc_type, guess)
    warm = spans()[n0 + len(cold):]
    krylov._PROGRAM_CACHE.clear()
    return cold_run, cold, warm_run, warm


def _traced(spans):
    return {s["attrs"].get("fun_name", "") for s in spans
            if s["name"] == "compile.trace"}


class TestKspProgram:
    @pytest.mark.parametrize("guess", ["zero", "nonzero"])
    @pytest.mark.parametrize("case", ["cg_jacobi", "cg_mg",
                                      "bcgs_bjacobi_ilu0"])
    def test_export_then_load(self, case, guess, aot_dir, spans,
                              monkeypatch):
        """The first solve exports its program; a second process (a
        cleared program cache) loads it: the same x, bit for bit, the
        same iterations, and no trace of the solve body."""
        from mpi_petsc4py_example_tpu.solvers import krylov
        op, ksp_type, pc_type = _ksp_case(case, monkeypatch)
        krylov._PROGRAM_CACHE.clear()
        x1, it1, kind = _ksp_solve(op, ksp_type, pc_type, guess)
        assert kind == {"cg_jacobi": "jacobi", "cg_mg": "mg",
                        "bcgs_bjacobi_ilu0": "bjacobi_ilu0"}[case]
        cold = spans()
        assert _builds(cold) and set(_builds(cold)) == {"miss"}
        assert any(s["name"] == "compile.trace"
                   and "local_fn" in s["attrs"].get("fun_name", "")
                   for s in cold)
        assert len(_blobs(aot_dir)) == len(_builds(cold))

        krylov._PROGRAM_CACHE.clear()
        n_cold = len(cold)
        x2, it2, _ = _ksp_solve(op, ksp_type, pc_type, guess)
        warm = spans()[n_cold:]
        assert set(_builds(warm)) == {"hit"}
        assert it2 == it1
        np.testing.assert_array_equal(x2, x1)
        traced = [s["attrs"].get("fun_name", "") for s in warm
                  if s["name"] == "compile.trace"]
        assert not [f for f in traced if "local_fn" in f], traced
        krylov._PROGRAM_CACHE.clear()

    @pytest.mark.parametrize("guess", ["zero", "nonzero"])
    def test_double_f32_bcgs_export_then_load(self, guess, aot_dir, spans,
                                              monkeypatch):
        """fp64 BCGS's double-f32 loop (solvers/bcgs_dd.py) exports and
        loads like the XLA loop: the warm process loads, gives the same x
        bit for bit and the same iterations, traces no solve body and no
        function the XLA loop's warm process does not, and its program
        build says which loop it holds."""
        (x1, it1, kind), cold, (x2, it2, _), warm = _cold_then_warm(
            "bcgs_bjacobi_dd", guess, spans, monkeypatch)
        assert kind == "bjacobi_ilu0"
        assert set(_builds(cold)) == {"miss"}
        assert set(_builds(warm)) == {"hit"}
        assert set(_builds(cold, "loop")) == set(_builds(warm, "loop")) \
            == {"dd_pallas"}
        assert any("local_fn" in f for f in _traced(cold))
        assert it2 == it1
        np.testing.assert_array_equal(x2, x1)
        assert not [f for f in _traced(warm) if "local_fn" in f]
        *_, xla_warm = _cold_then_warm("bcgs_bjacobi_ilu0", guess, spans,
                                       monkeypatch)
        assert set(_builds(xla_warm, "loop")) == {"xla"}
        assert _traced(warm) <= _traced(xla_warm), \
            _traced(warm) - _traced(xla_warm)

    @pytest.mark.parametrize("local", ["shell_pc", "live_monitor",
                                       "fault_plan"])
    def test_process_local_key_writes_no_blob(self, local, aot_dir, spans):
        """A program whose key holds something valid in this process
        only is never exported, nor loaded: it keeps the traced jit."""
        from mpi_petsc4py_example_tpu.models import poisson2d_csr
        from mpi_petsc4py_example_tpu.resilience import faults
        from mpi_petsc4py_example_tpu.solvers import krylov
        comm = tps.DeviceComm(n_devices=1)
        A = poisson2d_csr(8)
        M = tps.Mat.from_scipy(comm, A)
        ksp = tps.KSP().create(comm)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_tolerances(rtol=1e-8, max_it=200)
        plan = "spmv.result=bitflip:at=100000"      # armed, never fires
        if local == "shell_pc":
            pc = ksp.get_pc()
            pc.set_type("shell")
            pc.set_shell_apply(lambda r: r)
        elif local == "live_monitor":
            assert krylov.live_monitor_supported(comm)
            ksp.set_monitor(lambda *a: None)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        krylov._PROGRAM_CACHE.clear()
        if local == "fault_plan":
            with faults.inject_faults(plan):
                res = ksp.solve(b, x)
        else:
            res = ksp.solve(b, x)
        assert res.converged
        assert _blobs(aot_dir) == []
        assert set(_builds(spans())) == {"off"}
        krylov._PROGRAM_CACHE.clear()

    def test_process_local_keys(self, comm8, aot_dir):
        """Shell uids are found at any depth of a key (a composite PC's
        children, a spectral transform's operator), and no program kind
        keyed on one goes through the export cache."""
        sentinel = object()
        for kind in ("ksp", "ksp_many", "megasolve", "seedfacto"):
            assert aot.wrap(kind, comm8, ("cg", ("shellmat", 1)),
                            sentinel) is sentinel
        _process_local = aot._process_local
        assert _process_local(("cg", ("shell", 3)))
        assert _process_local(("cg", ("composite", "additive", (),
                                      ("jacobi",), ("shell", 1))))
        assert _process_local((("st", "shift", False, ("shellmat", 2),
                                None),))
        assert not _process_local(("cg", ("jacobi",), ("dia", (-1, 0, 1)),
                                   None, "shell"))


@pytest.mark.parametrize("module", [
    "solvers/krylov.py", "solvers/cg_plans.py", "solvers/pc.py",
    "solvers/bjilu.py", "solvers/mg.py", "ops/pallas_stencil.py",
    "core/mat.py", "models/stencil.py", "solvers/bcgs_dd.py",
    "ops/double_f32.py"])
def test_package_fingerprint_sees_every_module(module, tmp_path):
    """One byte changed in any module a solve program traces changes the
    fingerprint every blob key holds: the edit misses the cache."""
    import shutil
    root = tmp_path / "pkg"
    shutil.copytree(aot.PACKAGE_DIR, root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = aot.package_fingerprint(str(root))
    assert before == aot.package_fingerprint(aot.PACKAGE_DIR)
    with open(root / module, "ab") as fh:
        fh.write(b"\n")
    aot.package_fingerprint.cache_clear()
    assert aot.package_fingerprint(str(root)) != before
