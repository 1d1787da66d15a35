"""The ``convdiff5`` operator kind, its reference, its bytes model and
its cell, on the CPU: ``b = A x_true`` and ``relres`` agree with scipy's
``A @ x``; the cell's path runs through ILU(0) blocks once the dense cap
is lowered; the control (the reference BiCGStab one precision below
fp64) comes out ``correct: false`` where the fp64 reference passes; the
bytes model counts the method as its docstring does."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import control
import run as bench_run
from bench_util import run_tiny, tiny_spec

CELL = "cd2d2048-bcgs-bjacobi"


def _convdiff(cfg):
    from mpi_petsc4py_example_tpu.models import convdiff2d
    return convdiff2d(int(cfg["nx"]), int(cfg["ny"]),
                      beta=float(cfg["beta"])).tocsr()


@pytest.mark.parametrize("edge", [16, 24])
def test_rhs_is_a_times_seeded_noise(edge):
    import mpi_petsc4py_example_tpu as tps
    cfg = tiny_spec(CELL, edge)["config"]
    comm = tps.DeviceComm(devices=bench_run.cell_devices(1, allow_cpu=True))
    make = bench_run.load_module("operators", "convdiff5").rhs_maker(
        cfg, comm)
    key = bench_run.seed_key(2 ** 33 + 3)
    b = np.asarray(make(key, jnp.int32(2)))
    u = np.asarray(jax.random.uniform(jax.random.fold_in(key, 2),
                                      (edge, edge), jnp.float64))
    assert b.dtype == np.float64
    np.testing.assert_allclose(b, _convdiff(cfg) @ u.reshape(-1),
                               rtol=1e-14, atol=1e-14)


@pytest.mark.parametrize("edge", [16, 300])
def test_relres_is_scipy_residual(edge):
    cfg = tiny_spec(CELL, edge)["config"]
    ref = bench_run.load_module("references", "convdiff5")
    rng = np.random.default_rng(edge)
    x = rng.random(edge * edge)
    b = rng.random(edge * edge)
    want = np.linalg.norm(b - _convdiff(cfg) @ x) / np.linalg.norm(b)
    assert ref.relres(x, b, cfg) == pytest.approx(want, rel=1e-12)


def test_cell_path_through_ilu0_blocks(monkeypatch):
    """At 16^2 the cell's operator is under the dense cap; lowered, the
    run takes ILU(0) blocks, and is still correct."""
    from mpi_petsc4py_example_tpu.solvers import bjilu
    from mpi_petsc4py_example_tpu.solvers import pc as pcmod
    monkeypatch.setattr(pcmod, "_DENSE_CAP", 64)
    monkeypatch.setattr(bjilu, "BLOCK_ROWS", 64)
    seen = []
    orig = bjilu.build

    def build(comm, mat, target=None):
        out = orig(comm, mat, target)
        seen.append(out[1])
        return out

    monkeypatch.setattr(bjilu, "build", build)
    res = run_tiny(CELL, seconds=0.3)
    assert res["correct"] is True and res["failed"] == 0
    assert seen and seen[0]["blocks"] == 4
    c = res["checks"]["relres_over_rtol"]
    assert 0 < c["value"] <= c["limit"]


def test_assembly_span_read():
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu import telemetry
    spec = tiny_spec(CELL)
    comm = tps.DeviceComm(devices=bench_run.cell_devices(1, allow_cpu=True))
    telemetry.enable()
    try:
        telemetry.flight_recorder.clear()
        bench_run.load_module("operators", "convdiff5").build(
            spec["config"], comm)
        spans = telemetry.flight_recorder.spans()
    finally:
        telemetry.disable()
    roots = [s for s in spans if s["name"] == "mat.assemble"]
    assert len(roots) == 1 and roots[0]["attrs"]["format"] == "dia"
    assert roots[0]["attrs"]["rows"] == 256
    run = bench_run.Run(setup_spans=spans)
    got = bench_run.load_module("metrics", "assembly_s").read(run)
    assert got == pytest.approx(roots[0]["t1"] - roots[0]["t0"])
    assert bench_run.load_module("metrics", "assembly_s").read(
        bench_run.Run(setup_spans=[])) is None


def test_fp32_reference_fails_fp64_passes():
    """The control fails by the relres limit (its own recurrence claims
    convergence at this size). The fp64 reference is judged
    here with its own x: control.py hands ``judge`` an fp32 copy of x,
    which alone reads above the limit at rtol 1e-8."""
    import mpi_petsc4py_example_tpu as tps
    spec = tiny_spec(CELL)
    devices = bench_run.cell_devices(1, allow_cpu=True)
    low = control.read_control(spec, 2 ** 33 + 1, "float32", devices,
                               max_it=400, count=2)
    c = low["checks"]["relres_over_rtol"]
    assert low["correct"] is False and c["value"] > 3 * c["limit"]
    cfg, traffic = spec["config"], spec["traffic"]
    ref = bench_run.load_module("references", "convdiff5")
    make = bench_run.load_module("operators", "convdiff5").rhs_maker(
        cfg, tps.DeviceComm(devices=devices))
    checked, converged = [], []
    for i in range(2):
        b = make(bench_run.seed_key(2 ** 33 + 1), jnp.int32(i))
        x, k = ref.solve(b, cfg, float(traffic["rtol"]), 400, "float64")
        checked.append((np.asarray(x), np.asarray(b)))
        converged.append(k < 400)
    correct, checks, _ = bench_run.judge(checked, converged, cfg, traffic,
                                         ref)
    assert correct is True, checks


def _bytes_model():
    path = os.path.join(bench_run.HERE, "bytes", "bcgs-bjacobi.py")
    spec = importlib.util.spec_from_file_location("bytes_bcgs_bj", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


V = 16 * 16 * 8            # one fp64 vector of the 16^2 grid


def _info(m=5 * V, f=3 * V, chips=1):
    return {"n": 256, "itemsize": 8, "chips": chips,
            "matrix_bytes_per_apply": m, "diagonal_bytes": 0,
            "pc_factor_bytes": f}


def test_bytes_model_counts():
    bb = _bytes_model()
    # S1 7 V + F + M, S2 4 V + F + M + t, S3 7 V + t; t stored (2 V)
    # where A costs more than two vectors
    assert bb.per_iteration(_info(), 0) == 18 * V + 6 * V + 10 * V + 2 * V
    # a cheap A is applied again in S3 in place of storing t
    assert bb.per_iteration(_info(m=V), 0) == 18 * V + 6 * V + 2 * V + V
    # prologue 2 V, the first S1 skips p and v, the check 2 V + M
    assert bb.per_solve(_info(), 0) == 2 * V + 5 * V
    # three sweeps an iteration, two a solve, 2 C each per chip
    assert bb.per_iteration(_info(), 1024) == 36 * V - 6 * 1024
    assert bb.per_iteration(_info(chips=4), 1024) == 36 * V - 24 * 1024
    assert bb.per_solve(_info(), 1024) == 7 * V - 4 * 1024
    assert bb.per_iteration(_info(), 10 * V) == 0


def test_bytes_model_matches_operator_info():
    cfg = bench_run.cell_spec(CELL)["config"]
    info = bench_run.load_module("operators", "convdiff5").info(cfg)
    v = 2048 * 2048 * 8
    assert info["n"] * info["itemsize"] == v
    assert info["matrix_bytes_per_apply"] == 5 * v
    assert info["pc_factor_bytes"] == 3 * v
    vmem = 128 << 20
    assert _bytes_model().per_iteration(info, vmem) == 36 * v - 6 * vmem
