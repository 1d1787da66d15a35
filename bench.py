#!/usr/bin/env python
"""Benchmark harness for the headline stencil-CG metric (TPU only).

Headline metric (BASELINE.json): KSP iterations/second and time-to-rtol=1e-6
for CG on the 3D 7-point Poisson operator, with residual parity vs a CPU
oracle. The TPU path runs the matrix-free stencil operator (fp32, Jacobi-CG,
one jit-compiled program, fused Pallas stencil+dot kernel); the baseline is
scipy.sparse.linalg.cg (fp64 CPU) on the identical problem and tolerance —
the stand-in for 8-rank PETSc KSPCG (petsc4py is not installable here; scipy
is the only CPU oracle, SURVEY.md §4).

Measurement methodology (two numbers, both reported):

- **end-to-end wall**: median ± spread over ``--reps`` timed solves,
  launch and result-fetch latency included — the conservative number used
  for ``vs_baseline``.
- **on-chip iteration rate**: the latency-free rate, measured by the delta
  method — two fixed-iteration solves (norm type 'none') whose wall
  difference isolates pure loop time: ``per_iter = (w_hi - w_lo)/(it_hi -
  it_lo)``, median over ``--reps``. From it the achieved HBM traffic
  (11 vector passes/iteration on the fused CG path) and the fraction of the
  device's HBM peak (:data:`HBM_PEAK_GBPS`) are derived — the
  "bandwidth-bound" claim is measured, not asserted.

Runs on a TPU only: without one it exits non-zero before measuring.

Prints ONE JSON line:
  {"metric": ..., "value": on_chip_iters_per_sec, "unit": "iters/s",
   "vs_baseline": cpu_wall / tpu_e2e_wall, "extra": {...}}

Usage: python bench.py [--quick] [--n NX] [--rtol R] [--reps K]
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

import numpy as np

# importing the package first applies the x64 and compile-cache config
# before any jax backend initialization
import mpi_petsc4py_example_tpu  # noqa: F401

# HBM peak per chip, keyed by jax's device_kind. v5e ("TPU v5 lite"):
# 819 GB/s — Google Cloud documentation, "TPU v5e". A device missing here
# is an error, never a default.
HBM_PEAK_GBPS = {"TPU v5 lite": 819.0}


def hbm_peak_gbps(device_kind: str) -> float:
    """The HBM peak of ``device_kind`` from :data:`HBM_PEAK_GBPS`."""
    try:
        return HBM_PEAK_GBPS[device_kind]
    except KeyError:
        raise ValueError(f"no HBM peak known for device_kind "
                         f"{device_kind!r}; add it to bench.HBM_PEAK_GBPS "
                         "with its source") from None


def tpu_device():
    """The first TPU device; exits non-zero when JAX finds none (a
    measurement never falls back to the CPU)."""
    import jax
    d = jax.devices()[0]
    if d.platform != "tpu":
        sys.exit(f"no TPU found (jax platform {d.platform!r}): this "
                 "measures the chip")
    return d

# fused CG+Jacobi step traffic (krylov.cg_stencil_kernel): Adot reads p /
# writes Ap (2), the x/r update fusion reads x,p,r,Ap and writes x,r (6),
# the p-update reads r,p and writes p (3) -> 11 vector passes per iteration
PASSES_PER_ITER = 11


def make_problem(nx, pc_type="jacobi"):
    import jax.numpy as jnp

    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import StencilPoisson3D

    comm = tps.DeviceComm()
    op = StencilPoisson3D(comm, nx, dtype=jnp.float32)
    n = nx ** 3
    rng = np.random.default_rng(7)
    x_true = rng.random(n).astype(np.float32)
    b = np.asarray(op.mult(tps.Vec.from_global(comm, x_true)).to_numpy())

    ksp = tps.KSP().create(comm)
    ksp.set_operators(op)
    ksp.set_type("cg")
    ksp.get_pc().set_type(pc_type)
    return comm, op, ksp, b


def tpu_solve(nx, rtol, pc_type="jacobi", reps=3):
    """Converged CG; returns (iters, e2e walls list, x, b)."""
    comm, op, ksp, b = make_problem(nx, pc_type)
    ksp.set_tolerances(rtol=rtol, atol=0.0, max_it=20000)
    x, bv = op.get_vecs()
    bv.set_global(b)
    ksp.solve(bv, x)          # warm-up: compiles the program
    walls = []
    for _ in range(reps):
        x.zero()
        t0 = time.perf_counter()
        res = ksp.solve(bv, x)
        walls.append(time.perf_counter() - t0)
    return res.iterations, walls, x.to_numpy(), b, res


def _fixed_iter_solver(nx, max_it):
    comm, op, ksp, b = make_problem(nx, "jacobi")
    ksp.set_norm_type("none")
    ksp.set_tolerances(rtol=0.0, atol=0.0, max_it=max_it)
    x, bv = op.get_vecs()
    bv.set_global(b)
    ksp.solve(bv, x)          # warm-up
    return ksp, x, bv


def _delta_protocol(make_solver, run_one, reps, lo, hi, autoscale):
    """The ONE delta-method measurement protocol (single- and multi-RHS
    callers share it): two fixed-iteration solves whose wall difference
    isolates pure loop time, with the iteration delta auto-scaled so the
    measured loop time sits well above the run-to-run launch-latency
    noise (~tens of ms) — a pilot delta estimates the rate, then ``hi``
    is re-chosen for ~0.75 s of loop work, backing off under early
    recurrence blow-up. ``run_one(solver) -> (wall_s, iterations)`` is
    the only thing that differs between callers.
    """
    solvers = {m: make_solver(m) for m in (lo, hi)}

    def one_delta(a, b_):
        ws, its = {}, {}
        for max_it in (a, b_):
            # actual iterations, not max_it: a tol=0 fp32 run eventually
            # overflows its recurrence to inf and exits early — dividing
            # by the requested count would fake an arbitrarily fast rate
            ws[max_it], its[max_it] = run_one(solvers[max_it])
        return (ws[b_] - ws[a]) / max(its[b_] - its[a], 1), its[b_]

    pilot, _ = one_delta(lo, hi)
    target = int(0.75 / max(pilot, 1e-7))
    if autoscale and target > 2 * (hi - lo):  # delta too small for the noise
        hi2 = lo + min(target, 200000)
        solvers[hi2] = make_solver(hi2)
        _, actual = one_delta(lo, hi2)
        if actual < hi2:              # recurrence blow-up: stay under it
            hi2 = max(int(actual * 0.9), hi)
            if hi2 not in solvers:
                solvers[hi2] = make_solver(hi2)
            # the delta stayed shorter than intended — compensate with
            # extra samples beyond the user's --reps
            reps = max(reps, 5)
        hi = hi2
    return [one_delta(lo, hi)[0] for _ in range(reps)]


def delta_rate(make_solver, reps=3, lo=20, hi=520, autoscale=True):
    """Delta-method on-chip per-iteration time (see module docstring);
    returns a per_iter_seconds list.

    ``make_solver(max_it) -> (ksp, x, bv)`` builds a warmed fixed-iteration
    solver (norm type 'none'). The one measurement protocol shared by
    bench.py and benchmarks/run_all.py (configs 5 and 7) lives in
    :func:`_delta_protocol`.
    """
    def run_one(solver):
        ksp, x, bv = solver
        x.zero()
        t0 = time.perf_counter()
        r = ksp.solve(bv, x)
        return time.perf_counter() - t0, r.iterations

    return _delta_protocol(make_solver, run_one, reps, lo, hi, autoscale)


def on_chip_rate(nx, reps=3, lo=20, hi=520):
    """Delta-method per-iteration time for CG+Jacobi at nx^3."""
    return delta_rate(lambda m: _fixed_iter_solver(nx, m),
                      reps=reps, lo=lo, hi=hi)


def delta_rate_many(make_solver, B, reps=3, lo=20, hi=220,
                    autoscale=True):
    """Delta-method per-iteration time for a BATCHED fixed-iteration
    solver: the :func:`_delta_protocol` discipline over
    ``KSP.solve_many`` launches (one iteration advances ALL k columns;
    a launch's iteration count is its slowest column's). Shared by
    bench.py and benchmarks/run_all.py (config 7).

    ``make_solver(max_it) -> ksp`` builds a warmed fixed-iteration
    (norm 'none') solver.
    """
    def run_one(kf):
        t0 = time.perf_counter()
        r = kf.solve_many(B.copy())
        return time.perf_counter() - t0, max(r.iterations)

    return _delta_protocol(make_solver, run_one, reps, lo, hi, autoscale)


def batched_delta(nx, k=8, reps=3, lo=20, hi=220):
    """Delta-method per-iteration time of the BATCHED (k-RHS) stencil CG
    kernel (the multi-RHS Pallas pipeline + one-psum-per-phase fused
    reductions) on the headline problem."""
    comm, op, ksp, b = make_problem(nx, "jacobi")
    n = nx ** 3
    rng = np.random.default_rng(11)
    B = np.stack([b] + [np.asarray(
        op.mult(mpi_petsc4py_example_tpu.Vec.from_global(
            comm, rng.random(n).astype(np.float32))).to_numpy())
        for _ in range(k - 1)], axis=1)

    def fixed(max_it):
        kf = mpi_petsc4py_example_tpu.KSP().create(comm)
        kf.set_operators(op)
        kf.set_type("cg")
        kf.get_pc().set_type("jacobi")
        kf.set_norm_type("none")
        kf.set_tolerances(rtol=0.0, atol=0.0, max_it=max_it)
        kf.solve_many(B.copy())            # warm-up / compile
        return kf

    return delta_rate_many(fixed, B, reps=reps, lo=lo, hi=hi)


def serving_episode(nx, requests=64, max_k=16, window=0.003, rtol=1e-6):
    """Coalesced-serving episode (--serving): the SAME request set
    through a SolveServer session (block-CG dispatch, donated buffers)
    and through sequential per-request ``ksp.solve`` launches, on the
    headline stencil operator. Prints one extra JSON line; the ratio
    measures dispatch amortization + block-kernel throughput (cfg9 in
    benchmarks/run_all.py is the full Poisson-arrival protocol with the
    injected-fault recovery)."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.serving import SolveServer

    comm, op, ksp, b = make_problem(nx, "jacobi")
    n = nx ** 3
    rng = np.random.default_rng(13)
    B = np.stack([np.asarray(op.mult(tps.Vec.from_global(
        comm, rng.random(n).astype(np.float32))).to_numpy())
        for _ in range(requests)], axis=1)
    ksp.set_tolerances(rtol=rtol, atol=0.0, max_it=20000)
    x, bv = op.get_vecs()
    bv.set_global(B[:, 0])
    ksp.solve(bv, x)                  # warm the k=1 program
    t0 = time.perf_counter()
    for j in range(requests):
        x, bv = op.get_vecs()
        bv.set_global(B[:, j])
        ksp.solve(bv, x)
    seq_wall = time.perf_counter() - t0

    srv = SolveServer(comm, window=window, max_k=max_k)
    srv.register_operator("stencil", op, pc_type="jacobi", rtol=rtol,
                          warm_widths=(max_k,))
    t0 = time.perf_counter()
    futs = [srv.submit("stencil", B[:, j]) for j in range(requests)]
    res = [f.result(600) for f in futs]
    wall = time.perf_counter() - t0
    stats = srv.stats()
    srv.shutdown()
    assert all(r.converged for r in res)
    line = {
        "metric": f"serving: {requests} coalesced solves ({nx}^3 "
                  f"stencil, max_k={max_k}) vs sequential dispatch",
        "value": round(requests / wall, 2) if wall > 0 else 0.0,
        "unit": "solves/s",
        "vs_baseline": round(seq_wall / wall, 3) if wall > 0 else 0.0,
        "extra": {
            "seq_solves_per_s": round(requests / seq_wall, 2)
            if seq_wall > 0 else 0.0,
            "mean_batch_width": round(stats["mean_width"], 2),
            "batches": stats["batches"],
            "queue_wait_p50_ms": round(
                stats.get("queue_wait_p50_s", 0.0) * 1e3, 2),
        },
    }
    print(json.dumps(line))


def cpu_baseline(nx, b: np.ndarray, rtol: float):
    """scipy fp64 CG on the identical operator/tolerance."""
    import scipy.sparse.linalg as spla

    from mpi_petsc4py_example_tpu.models import poisson3d_csr

    A = poisson3d_csr(nx).astype(np.float64)
    bb = b.astype(np.float64)
    iters = [0]

    def cb(_):
        iters[0] += 1

    # Jacobi preconditioning to match the TPU configuration (diag = 6)
    M = spla.LinearOperator(A.shape, matvec=lambda v: v / 6.0)
    t0 = time.perf_counter()
    x, info = spla.cg(A, bb, rtol=rtol, atol=0.0, maxiter=20000,
                      M=M, callback=cb)
    wall = time.perf_counter() - t0
    return iters[0], wall, x, A


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small problem for smoke testing")
    ap.add_argument("--n", type=int, default=None,
                    help="grid points per dimension (default 128; quick 32)")
    ap.add_argument("--rtol", type=float, default=1e-6)
    ap.add_argument("--reps", type=int, default=3,
                    help="timed repetitions (median + spread reported)")
    ap.add_argument("--log-view", action="store_true",
                    help="print the -log_view solve/kernel-traffic "
                         "summary after the JSON line")
    ap.add_argument("--serving", action="store_true",
                    help="additionally run the coalesced-serving "
                         "episode (SolveServer vs sequential dispatch) "
                         "and print its JSON line")
    opts = ap.parse_args()
    nx = opts.n or (32 if opts.quick else 128)

    import jax

    hbm_roof = hbm_peak_gbps(tpu_device().device_kind)
    ndev = len(jax.devices())
    # stencil sharding needs nz % ndev == 0
    if nx % ndev != 0:
        nx = ((nx + ndev - 1) // ndev) * ndev
    n = nx ** 3

    iters, walls, x_tpu, b, res = tpu_solve(nx, opts.rtol, "jacobi",
                                            reps=opts.reps)
    mg_iters, mg_walls, x_mg, _, _ = tpu_solve(nx, opts.rtol, "mg",
                                               reps=opts.reps)
    hi = 520 if not opts.quick else 220
    pers = on_chip_rate(nx, reps=opts.reps, hi=hi)

    # batched multi-RHS kernel: k=8 delta-method episode — one iteration
    # serves 8 columns, so per-RHS-iteration cost should undercut k=1
    k_batch = 8
    pers_b = batched_delta(nx, k=k_batch, reps=opts.reps,
                           hi=220 if opts.quick else 320)
    per_b = statistics.median(pers_b)

    cpu_iters, cpu_wall, x_cpu, A = cpu_baseline(nx, b, opts.rtol)

    # residual parity check in fp64 on host
    bnorm = np.linalg.norm(b.astype(np.float64))
    r_tpu = np.linalg.norm(b.astype(np.float64) - A @ x_tpu.astype(np.float64))
    r_mg = np.linalg.norm(b.astype(np.float64) - A @ x_mg.astype(np.float64))
    r_cpu = np.linalg.norm(b.astype(np.float64) - A @ x_cpu)
    parity = bool(max(r_tpu, r_mg) <= 10 * max(r_cpu, opts.rtol * bnorm))

    wall = statistics.median(walls)
    mg_wall = statistics.median(mg_walls)
    per = statistics.median(pers)
    onchip = 1.0 / per if per > 0 else 0.0
    gbps = PASSES_PER_ITER * n * 4 / per / 1e9 if per > 0 else 0.0
    # per-kernel achieved-GB/s recording (utils/profiling): the composed
    # CG step's model traffic over its measured delta-method time — shows
    # up in the -log_view kernel-traffic table alongside the
    # decompose_stencil pieces
    from mpi_petsc4py_example_tpu.utils.profiling import (
        record_kernel_traffic)
    record_kernel_traffic(f"cg_step[{nx}^3]", PASSES_PER_ITER * n * 4, per)
    # the batched kernel's achieved-GB/s row: same 11-pass model per
    # column, k columns per batched iteration — this is the line the
    # -log_view kernel-traffic table shows for the multi-RHS pipeline
    gbps_b = (PASSES_PER_ITER * n * 4 * k_batch / per_b / 1e9
              if per_b > 0 else 0.0)
    record_kernel_traffic(f"cg_many_step[k={k_batch},{nx}^3]",
                          PASSES_PER_ITER * n * 4 * k_batch, per_b)
    # headline: best time-to-rtol config (CG+MG) vs the CPU oracle
    best_wall = min(wall, mg_wall)
    line = {
        "metric": f"CG 3D Poisson {nx}^3 ({n:,} DoF) fp32: on-chip CG+Jacobi "
                  f"iteration rate (delta method, fixed launch "
                  f"latency excluded); vs_baseline is end-to-end "
                  f"time-to-rtol={opts.rtol:g} incl. launch latency, best "
                  f"config, vs scipy fp64 CPU",
        "value": round(onchip, 1),
        "unit": "iters/s",
        "vs_baseline": round(cpu_wall / best_wall, 3) if best_wall > 0 else 0.0,
        "extra": {
            "onchip_per_iter_us": round(1e6 * per, 1),
            "onchip_spread_us": [round(1e6 * min(pers), 1),
                                 round(1e6 * max(pers), 1)],
            "achieved_gbps": round(gbps, 1),
            "hbm_roof_frac": round(gbps / hbm_roof, 3),
            # apparent traffic above the HBM roof means the CG state stayed
            # VMEM-resident across loop iterations (possible up to ~16 MB
            # vectors) — the 11-pass HBM model doesn't apply at that size
            "vmem_resident": bool(gbps > hbm_roof),
            "batched_k8_onchip_per_iter_us": round(1e6 * per_b, 1),
            "batched_k8_per_rhs_iter_us": round(1e6 * per_b / k_batch, 1),
            "batched_k8_achieved_gbps": round(gbps_b, 1),
            "e2e_jacobi_wall_s": round(wall, 4),
            "e2e_jacobi_spread_s": [round(min(walls), 4),
                                    round(max(walls), 4)],
            "e2e_jacobi_iters": iters,
            "e2e_mg_wall_s": round(mg_wall, 4),
            "e2e_mg_iters": mg_iters,
            "e2e_iters_per_s": round(iters / wall, 1) if wall > 0 else 0.0,
            "cpu_wall_s": round(cpu_wall, 4), "cpu_iters": cpu_iters,
            "rel_residual_tpu": float(r_tpu / bnorm),
            "rel_residual_mg": float(r_mg / bnorm),
            "rel_residual_cpu": float(r_cpu / bnorm),
            "residual_parity": parity,
            "devices": len(jax.devices()),
            "platform": jax.devices()[0].platform,
        },
    }
    print(json.dumps(line))
    if opts.serving:
        serving_episode(nx if opts.quick else min(nx, 64),
                        requests=32 if opts.quick else 64,
                        rtol=opts.rtol)
    if opts.log_view:
        from mpi_petsc4py_example_tpu.utils import profiling
        profiling.log_view()
    return 0


if __name__ == "__main__":
    sys.exit(main())
