#!/usr/bin/env python
"""Run the BASELINE benchmark configs and emit JSON results.

Usage: python benchmarks/run_all.py [--quick] [--out results.json]

Configs (BASELINE.json `configs` + the round-6 reference-precision row):
  1. AIJ Laplacian assembly + KSPCG/PCNONE solve (the test.py-shaped flow)
  2. multi-rank scatter + distributed solve (test2.py-shaped, tpurun -n 4)
  3. KSPGMRES + PCJACOBI on 2D 5-point Poisson
  4. KSPBCGS + block-Jacobi on unsymmetric convection-diffusion
  5. 3D 7-point Poisson, row-sharded stencil across the device mesh
     (CG+jacobi raced against CG+MG; the metric is time-to-rtol)
  6. fp32 inner CG + fp64 iterative refinement to rtol 1e-10 — the
     reference-precision (fp64-class) headline (solvers/refine.py)
  7. batched multi-RHS throughput: k=8 RHS via KSP.solve_many (block-CG,
     one gather + fused reductions per iteration for ALL columns) vs 8
     sequential single-RHS solves on the 64^3 Poisson case — aggregate
     RHS/s, per-RHS residual parity, delta-method on-chip cost
  8. ABFT overhead: the silent-corruption guard (-ksp_abft) ON vs OFF on
     the 64^3 Poisson CG solve — e2e walls + delta-method per-iteration
     itemization, guarded to stay under 10% overhead
  9. serving throughput: a SolveServer session under Poisson-arrival
     load (coalesced block-CG dispatch, donated buffers, one injected
     mid-load worker crash recovered in place) vs the same request set
     through sequential per-request dispatch — sustained solves/s,
     p50/p99 latency, per-request residual parity; the ROADMAP item-1
     target is >=100x the sequential rate where per-request dispatch
     latency dominates (a local CPU mesh has microsecond dispatch, so
     the ratio there measures only the block-kernel amortization)
 10. elastic recovery: sustained serving load with ONE injected
     PERMANENT device loss (device.lost — sticky, same-mesh retries
     futile) — healthy vs degraded solves/s, the recovery wall-clock
     (reshard + rebuild + mesh adoption), the resumed iteration, and
     the strict per-request fp64 residual-parity gate applied ACROSS
     the shrink boundary (requests in flight when the hardware died
     included); needs a multi-device mesh, so a 1-device parent
     re-runs itself on the 8-virtual-device CPU host platform
 11. mixed precision: bf16/f32/f64 storage channels under fp64
     refinement to rtol 1e-10 — per-variant walls, refine steps,
     bytes-per-iterate, strict fp64 parity gate per variant
 12. telemetry overhead: the repeated CG solve workload with the
     telemetry layer (spans + metrics registry + flight recorder) OFF
     vs ON — best-of batch walls, <2% overhead guard folded into the
     parity gate, per-iteration latency histogram (the -log_view row)
 13. megasolve: whole-solve fusion cold/warm walls fused vs unfused,
     one-dispatch-per-solve assertion, fused serving rerun
 15. s-step CA-CG: per-method fixed-iteration walls {cg, pipecg,
     sstep s=2/4/8} with per-method crossover latency (the per-site
     latency above which each 1-site plan beats classic CG), the
     measured-latency auto-selector's choice reported honestly, the
     1-site-per-s-block schedule gate, and the f32-inner-sstep
     refined-to-rtol-1e-10 parity gate
 14. fleet serving: a SolveRouter sharding sessions across replicas —
     sustained solves/s vs replica count (scaling reported honestly:
     process-local replicas SHARE the CPU mesh, so near-linear scaling
     is a real-hardware claim like cfg9's 100x), interactive-vs-bulk
     completion p99 under overload (the QoS gate: interactive p99 <
     bulk p99 IS folded into parity — it is structural, not a hardware
     property), and one injected device loss AND one heal mid-load
     with the strict per-request fp64 residual-parity gate applied
     across BOTH the shrink and re-grow boundaries
 17. persistent serving: sustained Poisson-arrival load where every
     request carries a UNIQUE rtol (the coalescer can never group two),
     served by a persistent device-resident session (per-slot
     tolerances, cross-batch staging) vs the per-batch megasolve
     session — sustained solves/s, p50/p99 latency, and the measured
     ``dispatch.programs`` per request: the per-batch tier pays one
     launch per request on this workload, the persistent tier
     amortizes to < 1 (the ISSUE-18 acceptance gate), with the strict
     per-request fp64 residual-parity gate against each request's OWN
     rtol
 18. fleet transport: the multi-host RPC tier — the same request set
     served through the in-process loopback transport vs real
     localhost sockets (solves/s, p50/p99 latency: the framing+pickle
     cost of host separation), then ONE injected host loss mid-load
     with the failover wall-clock (kill -> first re-homed answer), the
     checkpoint-carried resumed iteration (> 0: never a cold restart),
     and the strict per-request fp64 residual-parity gate applied
     ACROSS the failover boundary

CPU baselines use scipy (fp64) where a matching algorithm exists; scipy is
the only CPU oracle available (SURVEY.md §4).

Every iterative config runs with -ksp_true_residual_check on, so
``rel_residual`` (the TRUE ||b - A x||/||b||, recomputed in fp64 on host)
meets rtol and the per-config ``residual_parity`` field is a strict gate,
not an eyeball (round-3 VERDICT item 5).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.models import (
    StencilPoisson3D, convdiff2d, poisson2d_csr, poisson3d_csr,
    tridiag_family)

RTOL = 1e-6


def solve(comm, op, b, ksp_type, pc_type, rtol=RTOL, max_it=20000,
          restart=30, true_check=True, margin=0.5):
    ksp = tps.KSP().create(comm)
    ksp.set_operators(op)
    ksp.set_type(ksp_type)
    ksp.get_pc().set_type(pc_type)
    ksp.set_tolerances(rtol=rtol, atol=0.0, max_it=max_it)
    ksp.set_true_residual_check(true_check)
    # drift guard band (-ksp_true_residual_margin): converge the compiled
    # program to margin*rtol so the strict true-residual gate rarely
    # re-enters — a few extra microsecond iterations instead of a ~100 ms
    # re-entry dispatch. Default 0.5 (measured: margin 1.0 paid one
    # re-entry in cfg1 AND cfg4; 0.7 still one in cfg4 — BCGS's
    # recurrence drifts hardest); cfg3 overrides to 1.0 (GMRES's Arnoldi
    # norm doesn't drift, and the tighter target costs it ~23% more
    # iterations for nothing)
    ksp.true_residual_margin = margin
    ksp.restart = restart
    x, bv = op.get_vecs()
    bv.set_global(b)
    t0 = time.perf_counter()
    ksp.set_up()              # PC build + device_put, measured separately
    pc_setup = time.perf_counter() - t0
    ksp.solve(bv, x)          # warm-up / compile
    x.zero()
    t0 = time.perf_counter()
    res = ksp.solve(bv, x)
    wall = time.perf_counter() - t0
    extra = dict(
        pc_setup_s=round(pc_setup, 4),
        safeguard_reentries=int(getattr(ksp, "_last_reentries", 0)))
    mode = getattr(ksp.get_pc(), "setup_mode", None)
    if mode is not None:      # where block inversions ran (-pc_setup_device)
        extra["pc_setup_mode"] = mode
    brk = getattr(ksp.get_pc(), "setup_breakdown", None)
    if brk is not None:
        extra["pc_setup_breakdown"] = brk
    return x.to_numpy(), res, wall, extra


def true_relres(A, x, b):
    """fp64 host recomputation of ||b - A x|| / ||b||."""
    b64 = np.asarray(b, dtype=np.float64)
    r = b64 - A @ np.asarray(x, dtype=np.float64)
    return float(np.linalg.norm(r) / np.linalg.norm(b64))


def parity_fields(res, rres, cpu_iters=None, cpu_rres=None, rtol=RTOL):
    """The per-config residual-parity block (round-3 VERDICT item 5).

    ``residual_parity`` is strict: the TRUE relative residual meets rtol
    (1.05 slack only for fp32 device-vs-fp64 host norm rounding), and the
    CPU oracle — when one ran — met it too.
    """
    out = dict(iters=res.iterations,
               rnorm_recurrence=float(res.residual_norm),
               rel_residual=rres)
    ok = rres <= rtol * 1.05
    if cpu_iters is not None:
        out["cpu_iters"] = int(cpu_iters)
    if cpu_rres is not None:
        out["cpu_rel_residual"] = float(cpu_rres)
        ok = ok and cpu_rres <= rtol * 1.05
    out["residual_parity"] = bool(ok and res.converged)
    return out


def _counting(fn, A, b, rtol=RTOL, **kw):
    """Run a scipy iterative solver with an iteration counter."""
    iters = [0]
    t0 = time.perf_counter()
    x, info = fn(A, b.astype(np.float64), rtol=rtol, atol=0.0,
                 callback=lambda *_: iters.__setitem__(0, iters[0] + 1),
                 **kw)
    return x, iters[0], time.perf_counter() - t0


def onchip_breakdown(comm, op, b, ksp_type, pc_type):
    """Delta-method on-chip per-iteration time + fixed per-solve latency.

    Separates kernel cost from the dispatch+fetch floor (the dominant e2e
    term for small problems): slope between two fixed-iteration solves = pure loop time;
    a 1-iteration solve = the fixed latency.
    """
    import bench

    def make_solver(max_it):
        ksp = tps.KSP().create(comm)
        ksp.set_operators(op)
        ksp.set_type(ksp_type)
        ksp.get_pc().set_type(pc_type)
        if ksp_type not in tps.KSP._CYCLE_GRANULAR:
            ksp.set_norm_type("none")
        # cycle-granular kernels (gmres) reject norm 'none' AT SOLVE TIME
        # (fixed-iteration contract can't hold); rtol=atol=0 already runs
        # a fixed max_it worth of cycles, and delta_rate divides by ACTUAL
        # iterations so the cycle rounding cancels
        ksp.set_tolerances(rtol=0.0, atol=0.0, max_it=max_it)
        x, bv = op.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, x)
        return ksp, x, bv
    rates = bench.delta_rate(make_solver)
    per_iter = float(np.median(rates))
    ksp, x, bv = make_solver(1)
    fixed = []
    for _ in range(3):
        x.zero()
        t0 = time.perf_counter()
        ksp.solve(bv, x)
        fixed.append(time.perf_counter() - t0)
    return dict(onchip_per_iter_us=round(per_iter * 1e6, 2),
                fixed_latency_ms=round(min(fixed) * 1e3, 1))


def floor_fields(out, iters):
    """Reconcile the e2e wall against its own measured floor (round-5
    VERDICT items 3/6): floor = fixed dispatch latency + iters x on-chip
    per-iteration time; the remainder is what the artifact must explain."""
    if "onchip_per_iter_us" in out and "fixed_latency_ms" in out:
        floor = (out["fixed_latency_ms"] / 1e3
                 + iters * out["onchip_per_iter_us"] / 1e6)
        out["floor_s"] = round(floor, 4)
        out["unaccounted_s"] = round(out["wall_s"] - floor, 4)
    return out


# every config must carry the shared floor-accounting schema so future
# rounds can't silently regress the instrumentation (VERDICT r4 item 6);
# checked in main() before the artifact is written
_REQUIRED_FIELDS = {
    "cfg1_aij_assembly_cg_none": (
        "wall_s", "assembly_s", "assembly_breakdown", "onchip_per_iter_us",
        "fixed_latency_ms", "floor_s", "unaccounted_s", "safeguard_reentries",
        "residual_parity"),
    "cfg2_multirank_scatter_eigensolve_n4": (
        "wall_s", "warm_s", "phases_s", "residual_parity"),
    "cfg3_gmres_jacobi_poisson2d": (
        "wall_s", "onchip_per_iter_us", "fixed_latency_ms", "floor_s",
        "unaccounted_s", "safeguard_reentries", "residual_parity"),
    "cfg4_bcgs_bjacobi_convdiff": (
        "wall_s", "assembly_s", "assembly_breakdown",
        "speedup_incl_overheads", "pc_setup_s", "pc_setup_mode",
        "onchip_per_iter_us", "fixed_latency_ms", "floor_s",
        "unaccounted_s", "safeguard_reentries", "residual_parity"),
    "cfg5_poisson3d_sharded_stencil": (
        "wall_s", "mg_solve_s", "mg_verify_s", "onchip_per_iter_ms",
        "residual_parity"),
    "cfg6_fp32_refined_rtol1e10": (
        "wall_s", "refine_steps", "inner_iters", "rel_residual",
        "cpu_rel_residual", "residual_parity"),
    "cfg7_batched_k8": (
        "wall_s", "seq_wall_s", "rhs_per_s", "seq_rhs_per_s",
        "speedup_vs_sequential", "onchip_per_iter_us",
        "onchip_per_rhs_iter_us", "max_batched_seq_rres_diff",
        "residual_parity"),
    "cfg8_abft_overhead": (
        "wall_off_s", "wall_on_s", "e2e_overhead_pct", "abft_checks",
        "sdc_detections", "onchip_per_iter_us_off",
        "onchip_per_iter_us_on", "onchip_overhead_pct",
        "abft_overhead_ok", "residual_parity"),
    "cfg9_serving": (
        "wall_s", "seq_wall_s", "solves_per_s", "seq_solves_per_s",
        "speedup_vs_sequential", "p50_latency_ms", "p99_latency_ms",
        "mean_batch_width", "max_batch_width", "queue_wait_p50_ms",
        "injected_fault_recovered", "target_100x", "residual_parity"),
    "cfg10_elastic": (
        "wall_s", "healthy_solves_per_s", "degraded_solves_per_s",
        "degraded_capacity_ratio", "recovery_wall_s", "reshard_s",
        "adopt_s", "old_devices", "new_devices", "resumed_iteration",
        "residual_parity"),
    "cfg11_mixed_precision": (
        "wall_s", "variants", "speedup_bf16_vs_f64_per_iter",
        "bytes_per_iter_ratio_f64_over_bf16", "bandwidth_win",
        "resident_zdepth_f32", "resident_zdepth_bf16",
        "resident_doubling", "cpu_rel_residual", "residual_parity"),
    "cfg12_telemetry_overhead": (
        "wall_off_s", "wall_on_s", "overhead_pct",
        "telemetry_overhead_ok", "spans_per_solve", "per_iter_p50_us",
        "per_iter_p99_us", "residual_parity"),
    "cfg13_megasolve": (
        "wall_s", "variants", "serving", "fused_dispatches_per_solve",
        "dispatch_count_ok", "fused_cold_win", "fused_warm_win",
        "residual_parity"),
    "cfg14_fleet": (
        "wall_s", "scaling", "solves_per_s", "speedup_max_replicas",
        "near_linear_scaling", "interactive_p99_ms", "bulk_p99_ms",
        "qos_p99_ok", "shed", "old_devices", "new_devices",
        "regrown_devices", "resumed_iteration", "residual_parity"),
    "cfg15_sstep": (
        "wall_s", "methods", "psum_per_site_us", "crossover_us",
        "autoselect", "schedule_gate_ok", "refined_rel_residual",
        "demote_events", "residual_parity"),
    "cfg16_multisplit": (
        "wall_s", "sync", "sync_modeled_wall_s", "async_measured",
        "jitter_grid_us", "straggler_model", "cpu_mesh_caveat",
        "jitter_crossover_us", "async_wins_at_jitter",
        "refined_rel_residual", "residual_parity"),
    "cfg17_persistent": (
        "wall_s", "requests", "slots", "persistent", "per_batch",
        "dispatches_per_request_persistent",
        "dispatches_per_request_batch", "amortization_ok",
        "solves_per_s_ratio", "cpu_mesh_caveat", "residual_parity"),
    "cfg18_transport": (
        "wall_s", "requests", "loopback", "socket",
        "socket_vs_loopback_ratio", "failover_wall_s",
        "failover_event_wall_s", "resumed_iteration",
        "failover_parity_ok", "cpu_mesh_caveat", "residual_parity"),
}


def check_schema(results, quick=False):
    if quick:       # --quick skips the slow delta-method fields by design
        return
    for c in results["configs"]:
        need = _REQUIRED_FIELDS.get(c.get("config"), ())
        missing = [k for k in need if k not in c]
        assert not missing, (c.get("config"), missing)


def manufactured(A, seed=0, dtype=np.float64):
    rng = np.random.default_rng(seed)
    x = rng.random(A.shape[0]).astype(dtype)
    return x, (A @ x).astype(dtype)


def config1(comm, quick):
    """AIJ Laplacian assembly + KSPCG, PCNONE."""
    import scipy.sparse.linalg as spla

    nx = 24 if quick else 64
    t0 = time.perf_counter()
    A = poisson3d_csr(nx)                     # model build: scipy kron —
    model_build = time.perf_counter() - t0    # not a framework cost
    t0 = time.perf_counter()
    M = tps.Mat.from_scipy(comm, A, dtype=np.float32)
    assembly = time.perf_counter() - t0       # framework MatAssembly analog
    x_true, b = manufactured(A, dtype=np.float32)
    x, res, wall, extra = solve(comm, M, b, "cg", "none")
    x_cpu, cpu_iters, cpu = _counting(spla.cg, A, b, maxiter=20000)
    out = dict(config="cfg1_aij_assembly_cg_none", n=nx ** 3,
               model_build_s=round(model_build, 4),
               assembly_s=round(assembly, 4),
               assembly_breakdown=M.assembly_breakdown,
               wall_s=round(wall, 4), cpu_wall_s=round(cpu, 4),
               speedup=round(cpu / wall, 2),
               speedup_incl_assembly=round(cpu / (wall + assembly), 2),
               **extra)
    out.update(parity_fields(res, true_relres(A, x, b),
                             cpu_iters, true_relres(A, x_cpu, b)))
    if not quick:
        out.update(onchip_breakdown(comm, M, b, "cg", "none"))
        floor_fields(out, res.iterations)
    return out


def _cfg2_phases(t0: float, wall: float, stamps: dict):
    """Itemize one in-process cfg2 driver run from its phase stamps
    (utils/phases.py): tpurun setup, scatter+assembly, eigensolve,
    teardown. Values are seconds; 'unstamped' covers anything a missing
    stamp leaves behind, so the parts always sum to the wall."""
    out = {}
    marks = [("tpurun_setup", t0, stamps.get("driver_exec")),
             ("scatter_assembly", stamps.get("driver_exec"),
              stamps.get("mat_assembled")),
             ("eigensolve", stamps.get("mat_assembled"),
              stamps.get("eps_solved")),
             ("teardown", stamps.get("eps_solved"), t0 + wall)]
    acc = 0.0
    for name, a, b in marks:
        if a is not None and b is not None and b >= a:
            out[name] = round(b - a, 4)
            acc += b - a
    out["unstamped"] = round(max(wall - acc, 0.0), 4)
    return out


def config2(comm, quick):
    """Multi-rank scatter + distributed solve: eigensolve driver, -n 4.

    Runs ``tools/tpurun.py``'s entry IN this process (its ranks are
    threads): this process already holds the devices through ``comm``, and
    a child process would need the same chip. Reports the first driver
    run (``cold_s``, compiles included), the median of the repeats
    (``wall_s``, phase-itemized) and the warm solver time ``warm_s``. A
    failed driver run raises."""
    import contextlib
    import io
    import tempfile

    sys.path.insert(0, os.path.join(REPO, "tools"))
    import tpurun
    argv = ["-n", "4", os.path.join(REPO, "examples", "eigensolve.py")]
    walls, phase_runs = [], []
    for _ in range(2 if quick else 4):
        with tempfile.TemporaryDirectory() as td:
            log = os.path.join(td, "phases.json")
            os.environ["TPU_SOLVE_PHASE_LOG"] = log
            out = io.StringIO()
            t0 = time.time()
            try:
                with contextlib.redirect_stdout(out):
                    rc = tpurun.main(argv)
            finally:
                os.environ.pop("TPU_SOLVE_PHASE_LOG", None)
            wall_i = time.time() - t0
            if rc != 0 or "Eigenvalue:" not in out.getvalue():
                raise RuntimeError(f"cfg2 driver failed (rc={rc}): "
                                   f"{out.getvalue()[-2000:]}")
            stamps = {}
            if os.path.exists(log):
                # keep THIS run's first occurrence of each stamp: the log
                # also holds earlier runs of this process, and the 4
                # virtual ranks re-stamp collective points
                for name, ts in json.load(open(log)):
                    if ts >= t0:
                        stamps.setdefault(name, ts)
        walls.append(wall_i)
        phase_runs.append(_cfg2_phases(t0, wall_i, stamps))
    tps.global_options().clear()      # the driver's options DB
    cold, warm_walls = walls[0], walls[1:]
    order = sorted(range(len(warm_walls)), key=warm_walls.__getitem__)
    mid = order[len(warm_walls) // 2]
    wall, phases = warm_walls[mid], phase_runs[1 + mid]

    # warm-process flow: the same tridiagonal HEP solve (largest magnitude,
    # nev=1 — reference test2.py defaults), timed on its second run
    CSR = tridiag_family(100)

    def eig_once():
        M = tps.Mat.from_scipy(comm, CSR)
        eps = tps.EPS().create(comm)
        eps.set_operators(M)
        eps.set_problem_type("hep")
        eps.solve()
        assert eps.get_converged() >= 1
        return float(eps.get_eigenvalue(0).real)

    lam = eig_once()                          # warm-up / compile
    t0 = time.perf_counter()
    lam = eig_once()
    warm = time.perf_counter() - t0
    lam_np = np.linalg.eigvalsh(CSR.toarray())
    lam_np = lam_np[np.argmax(np.abs(lam_np))]
    eig_err = abs(lam - lam_np) / abs(lam_np)
    return dict(config="cfg2_multirank_scatter_eigensolve_n4", n=100,
                cold_s=round(cold, 4), wall_s=round(wall, 4),
                wall_spread_s=[round(min(warm_walls), 4),
                               round(max(warm_walls), 4)],
                phases_s=phases,
                warm_s=round(warm, 4),
                eigenvalue_rel_err=float(eig_err),
                residual_parity=bool(eig_err <= 1e-8),
                ok=True)


def config3(comm, quick):
    """KSPGMRES + PCJACOBI on 2D 5-point Poisson."""
    import scipy.sparse.linalg as spla

    nx = 48 if quick else 512
    A = poisson2d_csr(nx)
    x_true, b = manufactured(A, dtype=np.float32)
    M = tps.Mat.from_scipy(comm, A, dtype=np.float32)
    x, res, wall, extra = solve(comm, M, b, "gmres", "jacobi",
                                max_it=40000, margin=1.0)
    Mj = spla.LinearOperator(A.shape, matvec=lambda v: v / A.diagonal())
    x_cpu, cpu_iters, cpu = _counting(spla.gmres, A, b, restart=30, M=Mj,
                                      callback_type="pr_norm")
    out = dict(config="cfg3_gmres_jacobi_poisson2d", n=nx * nx,
               wall_s=round(wall, 4), cpu_wall_s=round(cpu, 4),
               speedup=round(cpu / wall, 2), **extra)
    out.update(parity_fields(res, true_relres(A, x, b),
                             cpu_iters, true_relres(A, x_cpu, b)))
    if not quick:
        out.update(onchip_breakdown(comm, M, b, "gmres", "jacobi"))
        floor_fields(out, res.iterations)
    return out


def config4(comm, quick):
    """KSPBCGS + block-Jacobi on unsymmetric convection-diffusion."""
    import scipy.sparse.linalg as spla

    nx = 40 if quick else 256
    A = convdiff2d(nx, beta=0.4)
    x_true, b = manufactured(A, dtype=np.float32)
    t0 = time.perf_counter()
    M = tps.Mat.from_scipy(comm, A, dtype=np.float32)
    assembly = time.perf_counter() - t0
    x, res, wall, extra = solve(comm, M, b, "bcgs", "bjacobi")
    t0 = time.perf_counter()
    ilu = spla.spilu(A.tocsc())          # the CPU oracle's pc_setup analog
    cpu_pc_setup = time.perf_counter() - t0
    Mi = spla.LinearOperator(A.shape, matvec=ilu.solve)
    x_cpu, cpu_iters, cpu = _counting(spla.bicgstab, A, b, M=Mi)
    out = dict(config="cfg4_bcgs_bjacobi_convdiff", n=nx * nx,
               assembly_s=round(assembly, 4),
               # round-6 VERDICT item 1: the sweep's biggest unexplained
               # number gets the cfg1 treatment — itemized parts that sum
               # to assembly_s (placement is synced inside from_csr, so
               # async dispatch can no longer masquerade as assembly)
               assembly_breakdown=M.assembly_breakdown,
               wall_s=round(wall, 4), cpu_wall_s=round(cpu, 4),
               cpu_pc_setup_s=round(cpu_pc_setup, 4),
               speedup=round(cpu / wall, 2), **extra)
    out["speedup_incl_overheads"] = round(
        (cpu + cpu_pc_setup)
        / (wall + assembly + extra["pc_setup_s"]), 3)
    out.update(parity_fields(res, true_relres(A, x, b),
                             cpu_iters, true_relres(A, x_cpu, b)))
    if not quick:
        out.update(onchip_breakdown(comm, M, b, "bcgs", "bjacobi"))
        floor_fields(out, res.iterations)
    return out


def config5(comm, quick):
    """3D 7-point Poisson at the BASELINE 100M-DoF target, row-sharded
    stencil across the mesh.

    Default 512^3 = 134M DoF (>= the 100M target; a 128-multiple so the
    fused Pallas stencil-CG fast path applies). fp32 matrix-free. The
    metric is time-to-rtol, so CG+jacobi is RACED against CG+MG (the slab
    V-cycle, ~10 iterations) and the best wall is the config's number —
    the round-3 VERDICT's top demand. Reports the end-to-end walls
    (launch and fetch latency included) and the on-chip
    per-iteration time of the jacobi loop via the delta method."""
    import jax.numpy as jnp

    nx = 32 if quick else 512
    ndev = comm.size
    if nx % ndev:
        nx = ((nx + ndev - 1) // ndev) * ndev
    op = StencilPoisson3D(comm, nx, dtype=jnp.float32)
    n = nx ** 3
    rng = np.random.default_rng(5)
    x_true = rng.random(n).astype(np.float32)
    b = np.asarray(op.mult(tps.Vec.from_global(comm, x_true)).to_numpy())

    def op_relres(x):
        r = b - np.asarray(
            op.mult(tps.Vec.from_global(comm, np.asarray(x))).to_numpy())
        return float(np.linalg.norm(r) / np.linalg.norm(b))

    x_j, res_j, wall_j, _ = solve(comm, op, b, "cg", "jacobi")
    rres_j = op_relres(x_j)
    x_m, res_m, wall_m, extra_m = solve(comm, op, b, "cg", "mg")
    rres_m = op_relres(x_m)
    # verification split (round-5 VERDICT item 6): the same MG solve
    # without the true-residual epilogue isolates what the gate's fused
    # verification mult adds. Dispatch noise can exceed the
    # epilogue's one stencil pass, so BOTH sides are best-of-3 (min
    # suppresses the noise; the difference can still read slightly
    # negative within residual jitter — reported as measured)
    def best_of(true_check, reps=3):
        walls = [solve(comm, op, b, "cg", "mg", true_check=true_check)[2]
                 for _ in range(reps)]
        return min(walls)
    if quick:            # quick mode discards the split (check_schema)
        mg_gate_s = mg_solve_s = wall_m
    else:
        mg_gate_s = best_of(True)
        mg_solve_s = best_of(False)
    best = min(wall_j, wall_m)

    # on-chip rate: the shared delta-method protocol (bench.delta_rate)
    from bench import delta_rate

    def make_fixed(max_it):
        ksp = tps.KSP().create(comm)
        ksp.set_operators(op)
        ksp.set_type("cg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_norm_type("none")
        ksp.set_tolerances(rtol=0.0, atol=0.0, max_it=max_it)
        xv, bv = op.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, xv)     # warm (program cache shared with solve())
        return ksp, xv, bv

    pers = delta_rate(make_fixed, reps=3, lo=20,
                      hi=120 if quick else 320, autoscale=not quick)
    per = float(np.median(pers))
    res_best, rres_best = ((res_m, rres_m) if wall_m <= wall_j
                           else (res_j, rres_j))
    out = dict(config="cfg5_poisson3d_sharded_stencil", n=n,
               devices=ndev, wall_s=round(best, 4),
               e2e_jacobi_wall_s=round(wall_j, 4),
               e2e_jacobi_iters=res_j.iterations,
               rel_residual_jacobi=rres_j,
               e2e_mg_wall_s=round(wall_m, 4),
               e2e_mg_iters=res_m.iterations,
               rel_residual_mg=rres_m,
               mg_solve_s=round(mg_solve_s, 4),
               mg_verify_s=round(mg_gate_s - mg_solve_s, 4),
               safeguard_reentries=extra_m["safeguard_reentries"],
               iters_per_s=round(res_j.iterations / wall_j, 1),
               onchip_per_iter_ms=round(1e3 * per, 3),
               onchip_iters_per_s=round(1.0 / per, 1) if per > 0 else 0.0)
    out.update(parity_fields(res_best, rres_best))
    return out


def config6(comm, quick):
    """Reference-precision iterative config (round 6, VERDICT 'next' #2):
    fp32 inner CG+Jacobi inside fp64 iterative refinement
    (solvers/refine.RefinedKSP, the Wilkinson scheme) to rtol 1e-10 on the
    cfg1 Poisson operator — the reference's PETSc stack is fp64 end to end
    (test.py:14 np.double), while every prior headline was fp32/1e-6. The
    CPU oracle is scipy fp64 CG at the SAME 1e-10 tolerance, so the
    speedup compares equal-accuracy solves.
    """
    import scipy.sparse.linalg as spla

    from mpi_petsc4py_example_tpu.solvers.refine import RefinedKSP

    rtol = 1e-10
    nx = 24 if quick else 64
    A = poisson3d_csr(nx)
    x_true, b = manufactured(A, dtype=np.float64)
    rk = RefinedKSP().create(comm)
    rk.set_operators(A)
    rk.set_type("cg")
    rk.get_pc().set_type("jacobi")
    rk.set_tolerances(rtol=rtol, inner_rtol=1e-6)
    rk.solve(b)                          # warm-up: compiles the inner KSP
    t0 = time.perf_counter()
    x, res = rk.solve(b)
    wall = time.perf_counter() - t0
    rres = true_relres(A, x, b)
    Mj = spla.LinearOperator(A.shape, matvec=lambda v: v / A.diagonal())
    x_cpu, cpu_iters, cpu = _counting(spla.cg, A, b, rtol=rtol, M=Mj,
                                      maxiter=40000)
    cpu_rres = true_relres(A, x_cpu, b)
    out = dict(config="cfg6_fp32_refined_rtol1e10", n=nx ** 3,
               rtol=rtol,
               wall_s=round(wall, 4),
               refine_steps=int(rk.refine_steps),
               inner_iters=int(res.iterations),
               cpu_wall_s=round(cpu, 4), cpu_iters=int(cpu_iters),
               speedup=round(cpu / wall, 2) if wall > 0 else 0.0,
               rnorm_recurrence=float(res.residual_norm),
               rel_residual=rres,
               cpu_rel_residual=cpu_rres,
               # strict gate AT REFERENCE PRECISION: both sides meet the
               # 1e-10 target (1.05 slack for norm rounding, as elsewhere)
               residual_parity=bool(rres <= rtol * 1.05
                                    and cpu_rres <= rtol * 1.05))
    return out


def config7(comm, quick):
    """Batched multi-RHS throughput (round 7): k=8 RHS through ONE
    ``KSP.solve_many`` block-CG launch vs 8 sequential cfg1-style solves
    on the 64^3 Poisson operator.

    The batched program pays ONE all_gather and one fused reduction per
    phase for all 8 columns (tests/test_collective_volume.py pins the op
    count), so its aggregate RHS/s should beat 8 sequential launches by
    roughly the amortized collective+dispatch share. Reported: both
    walls, both aggregate rates, per-RHS residual parity (every batched
    column meets rtol AND agrees with its sequential twin), and the
    delta-method on-chip per-iteration cost of the batched kernel (also
    per RHS-iteration, the number comparable to cfg1's per-iter cost).
    """
    import bench

    k = 8
    nx = 24 if quick else 64
    A = poisson3d_csr(nx)
    n = nx ** 3
    M = tps.Mat.from_scipy(comm, A, dtype=np.float32)
    rng = np.random.default_rng(7)
    Xt = rng.random((n, k)).astype(np.float32)
    B = np.asarray(A @ Xt).astype(np.float32)

    def make_ksp():
        ksp = tps.KSP().create(comm)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.get_pc().set_type("jacobi")
        # the batched program has no true-residual gate (solve_many routes
        # gated solves through the sequential fallback), so the fp32
        # recurrence-drift guard band is applied directly: converge the
        # recurrence to margin*rtol (the cfg-suite margin=0.5 discipline)
        # and verify the TRUE fp64 residual against rtol itself below.
        # Both the batched and the sequential side use the same target,
        # so the iteration counts stay comparable.
        ksp.set_tolerances(rtol=RTOL * 0.5, atol=0.0, max_it=20000)
        return ksp

    ksp = make_ksp()
    ksp.solve_many(B.copy())                # warm-up / compile
    t0 = time.perf_counter()
    res = ksp.solve_many(B.copy())
    wall = time.perf_counter() - t0

    # 8 sequential single-RHS solves, same compiled-program discipline
    x, bv = M.get_vecs()
    bv.set_global(B[:, 0])
    ksp.solve(bv, x)                        # warm-up the k=1 program
    seq_iters, seq_rres = [], []
    t0 = time.perf_counter()
    for j in range(k):
        x, bv = M.get_vecs()
        bv.set_global(B[:, j])
        r = ksp.solve(bv, x)
        seq_iters.append(r.iterations)
        seq_rres.append(true_relres(A, x.to_numpy(), B[:, j]))
    seq_wall = time.perf_counter() - t0

    bat_rres = [true_relres(A, res.X[:, j], B[:, j]) for j in range(k)]
    # strict parity: every batched column meets rtol, and matches its
    # sequential twin's residual at the solve tolerance scale
    max_diff = max(abs(b - s) for b, s in zip(bat_rres, seq_rres))
    parity = bool(res.converged
                  and all(r <= RTOL * 1.05 for r in bat_rres)
                  and all(r <= RTOL * 1.05 for r in seq_rres)
                  and max_diff <= RTOL)
    out = dict(config="cfg7_batched_k8", n=n, nrhs=k,
               wall_s=round(wall, 4),
               seq_wall_s=round(seq_wall, 4),
               rhs_per_s=round(k / wall, 2) if wall > 0 else 0.0,
               seq_rhs_per_s=round(k / seq_wall, 2) if seq_wall > 0
               else 0.0,
               speedup_vs_sequential=round(seq_wall / wall, 3)
               if wall > 0 else 0.0,
               batched_iters=res.iterations,
               seq_iters=seq_iters,
               rel_residuals=[float(r) for r in bat_rres],
               max_batched_seq_rres_diff=float(max_diff),
               residual_parity=parity)

    if not quick:
        # delta-method on-chip cost of the BATCHED kernel via the shared
        # batched protocol (bench.delta_rate_many — autoscaled deltas,
        # same discipline as every other config); per-RHS-iteration cost
        # is the cfg1-comparable number (one batched iteration advances
        # all k columns)
        def batched_fixed(max_it):
            kf = make_ksp()
            kf.set_norm_type("none")
            kf.set_tolerances(rtol=0.0, atol=0.0, max_it=max_it)
            kf.solve_many(B.copy())          # warm-up
            return kf

        pers = bench.delta_rate_many(batched_fixed, B, reps=3, lo=20,
                                     hi=320)
        per = float(np.median(pers))
        out["onchip_per_iter_us"] = round(per * 1e6, 2)
        out["onchip_per_rhs_iter_us"] = round(per * 1e6 / k, 2)
        # the batched kernel's achieved-GB/s row for -log_view artifacts
        # (model: the 11-pass fused-CG step per column — bench.py's
        # PASSES_PER_ITER — times k columns per batched iteration)
        from mpi_petsc4py_example_tpu.utils.profiling import (
            record_kernel_traffic)
        record_kernel_traffic(f"cg_many_step[k={k},{nx}^3]",
                              bench.PASSES_PER_ITER * n * 4 * k, per)
    return out


def config8(comm, quick):
    """ABFT overhead (round 8): the cfg1-shaped 64^3 Poisson CG solve
    with the silent-corruption guard ON vs OFF.

    The guard folds every checksum partial into the existing reduction
    phases (tests/test_collective_volume.py::TestAbftGuardVolume pins the
    psum-site count), so the only cost is the extra elementwise
    sums/abs-sums over arrays the step already touches. Reported:
    ABFT-on/off end-to-end walls AND the delta-method on-chip
    per-iteration costs (the e2e wall folds in fixed dispatch latency and
    host noise, so the GUARD — overhead < 10% — is judged on the
    delta-method number, itemized per iteration). The guarded solve must
    also stay false-positive-free (detections == 0) and meet rtol.
    """
    import bench

    nx = 24 if quick else 64
    A = poisson3d_csr(nx)
    n = nx ** 3
    M = tps.Mat.from_scipy(comm, A, dtype=np.float32)
    x_true, b = manufactured(A, dtype=np.float32)

    def make_ksp(abft, norm_none=False, max_it=20000):
        ksp = tps.KSP().create(comm)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.get_pc().set_type("none")
        # the cfg-suite margin-0.5 discipline: converge the recurrence to
        # margin*rtol, verify the fp64 TRUE residual against rtol below
        ksp.set_tolerances(rtol=RTOL * 0.5, atol=0.0, max_it=max_it)
        ksp.abft = bool(abft)
        if norm_none:
            ksp.set_norm_type("none")
            ksp.set_tolerances(rtol=0.0, atol=0.0, max_it=max_it)
        return ksp

    def timed_solve(abft, reps=3):
        # best-of-reps: single e2e walls on a shared CPU jitter by tens
        # of percent (the cfg5 best_of discipline); min suppresses noise
        ksp = make_ksp(abft)
        x, bv = M.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, x)          # warm-up / compile
        walls = []
        for _ in range(1 if quick else reps):
            x.zero()
            t0 = time.perf_counter()
            res = ksp.solve(bv, x)
            walls.append(time.perf_counter() - t0)
        return x.to_numpy(), res, min(walls)

    x_off, res_off, wall_off = timed_solve(False)
    x_on, res_on, wall_on = timed_solve(True)
    rres_on = true_relres(A, x_on, b)
    rres_off = true_relres(A, x_off, b)

    out = dict(config="cfg8_abft_overhead", n=n,
               wall_off_s=round(wall_off, 4),
               wall_on_s=round(wall_on, 4),
               e2e_overhead_pct=round(100.0 * (wall_on - wall_off)
                                      / wall_off, 2) if wall_off > 0
               else 0.0,
               iters_off=res_off.iterations, iters_on=res_on.iterations,
               abft_checks=res_on.abft_checks,
               sdc_detections=res_on.sdc_detections,
               rel_residual=rres_on)
    overhead_ok = True
    if not quick:
        # delta-method itemization (the shared protocol): pure on-chip
        # per-iteration cost with and without the folded ABFT partials —
        # fixed-iteration solves (norm none), slope between two lengths
        def make_fixed(abft):
            def make_solver(max_it):
                ksp = make_ksp(abft, norm_none=True, max_it=max_it)
                x, bv = M.get_vecs()
                bv.set_global(b)
                ksp.solve(bv, x)
                return ksp, x, bv
            return make_solver

        # ALTERNATE the on/off measurements and keep each side's best:
        # back-to-back delta_rate calls on a shared CPU see different
        # background load, which otherwise swamps the (near-zero) ABFT
        # delta with tens of percent of noise
        offs, ons = [], []
        for _ in range(2):
            offs.append(float(np.median(bench.delta_rate(
                make_fixed(False)))))
            ons.append(float(np.median(bench.delta_rate(
                make_fixed(True)))))
        per_off, per_on = min(offs), min(ons)
        overhead = (per_on - per_off) / per_off if per_off > 0 else 0.0
        # the acceptance guard: folded ABFT stays under 10% per-iteration
        overhead_ok = overhead < 0.10
        out.update(onchip_per_iter_us_off=round(per_off * 1e6, 2),
                   onchip_per_iter_us_on=round(per_on * 1e6, 2),
                   onchip_overhead_pct=round(100.0 * overhead, 2),
                   abft_overhead_ok=bool(overhead_ok))
    # strict parity: both solves meet rtol in the fp64 true residual,
    # identical iteration counts (pure ABFT never changes the
    # recurrence), zero false positives, and the overhead guard held
    out.update(parity_fields(res_on, rres_on))
    out["residual_parity"] = bool(
        out["residual_parity"] and rres_off <= RTOL * 1.05
        and res_on.iterations == res_off.iterations
        and res_on.sdc_detections == 0 and overhead_ok)
    return out


def config9(comm, quick):
    """Serving throughput (round 9, ROADMAP item 1): a SolveServer
    session under Poisson-arrival load vs sequential per-request
    dispatch of the SAME request set.

    The server registers the Poisson operator once (operands + PC +
    compiled/AOT-cached block programs resident), coalesces concurrent
    arrivals into up to max_k-wide block-CG launches with donated
    iterate blocks, and recovers ONE injected mid-load worker crash
    (``ksp.program=unavailable``) through the per-dispatch resilient
    path — its batch-mates' answers still pass the parity gate.
    Reported: sustained solves/s both ways, per-request completion
    latency p50/p99 (arrival -> future resolution, the number a client
    feels), coalescing stats, and the strict per-request residual gate.
    The >=100x acceptance target is a DISPATCH-LATENCY claim: where a
    launch costs ~100 ms, a k=64 block at ~1x launch cost serves 64
    requests, and the batching window admits
    more than one block per sequential-solve interval; a local CPU mesh
    (microsecond dispatch) measures only the block-kernel amortization,
    so ``target_100x`` is reported alongside the honest measured ratio
    rather than folded into ``residual_parity``.
    """
    from mpi_petsc4py_example_tpu.resilience import RetryPolicy
    from mpi_petsc4py_example_tpu.serving import SolveServer

    R = 48 if quick else 192
    nx = 16 if quick else 32
    max_k = 16 if quick else 64
    A = poisson3d_csr(nx)
    n = nx ** 3
    M = tps.Mat.from_scipy(comm, A, dtype=np.float32)
    rng = np.random.default_rng(9)
    Xt = rng.random((n, R)).astype(np.float32)
    B = np.asarray(A @ Xt).astype(np.float32)
    # the cfg-suite margin-0.5 discipline: converge the fp32 recurrence
    # to 0.5*rtol, verify the fp64 TRUE residual against rtol below
    rtol_inner = RTOL * 0.5

    # ---- sequential-dispatch baseline: one program launch per request
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type("cg")
    ksp.get_pc().set_type("jacobi")
    ksp.set_tolerances(rtol=rtol_inner, atol=0.0, max_it=20000)
    x, bv = M.get_vecs()
    bv.set_global(B[:, 0])
    ksp.solve(bv, x)                       # warm-up / compile
    seq_rres = []
    t0 = time.perf_counter()
    for j in range(R):
        x, bv = M.get_vecs()
        bv.set_global(B[:, j])
        ksp.solve(bv, x)
        seq_rres.append(true_relres(A, x.to_numpy(), B[:, j]))
    seq_wall = time.perf_counter() - t0
    seq_rate = R / seq_wall if seq_wall > 0 else 0.0

    # ---- serving: coalesced dispatch under Poisson arrivals
    srv = SolveServer(comm, window=0.003, max_k=max_k, pad_pow2=True,
                      resilient=True,
                      retry_policy=RetryPolicy(base_delay=0.01,
                                               max_delay=0.1))
    # pre-compile every pow2 block width the padding policy can
    # dispatch, plus the guess-nonzero resume program the injected
    # crash's recovery path needs — compiles must not pollute the
    # sustained-rate measurement
    widths = [1 << p for p in range(max_k.bit_length())
              if (1 << p) <= max_k]
    sess = srv.register_operator("poisson", M, pc_type="jacobi",
                                 rtol=rtol_inner, warm_widths=widths)
    sess.ksp.set_initial_guess_nonzero(True)
    sess.ksp.solve_many(np.zeros((n, max_k), np.float32))
    sess.ksp.set_initial_guess_nonzero(False)

    # offered load: Poisson arrivals at ~50x the sequential service
    # rate, so the queue is persistently backlogged and the coalescer
    # must batch (the sustained-throughput regime, not a latency idle)
    lam = max(50.0 * seq_rate, 100.0)
    gaps = rng.exponential(1.0 / lam, R)
    t_submit = np.empty(R)
    t_done = np.empty(R)
    futs = []

    def _mark_done(j):
        def cb(_f):
            t_done[j] = time.monotonic()
        return cb

    # ONE injected worker crash mid-load (3rd dispatched block), with
    # real partial state (iter=8) — the serving retry path checkpoints,
    # rebuilds, resumes; all futures must still resolve with parity
    with tps.inject_faults("ksp.program=unavailable:at=3:iter=8"):
        t_start = time.monotonic()
        next_arrival = t_start
        for j in range(R):
            next_arrival += gaps[j]
            delay = next_arrival - time.monotonic()
            if delay > 0:
                time.sleep(delay)
            t_submit[j] = time.monotonic()
            f = srv.submit("poisson", B[:, j])
            f.add_done_callback(_mark_done(j))
            futs.append(f)
        res = [f.result(600) for f in futs]
        t_end = time.monotonic()
    stats = srv.stats()
    srv.shutdown()

    wall = t_end - t_start
    rate = R / wall if wall > 0 else 0.0
    lat_ms = np.sort((t_done - t_submit) * 1e3)
    srv_rres = [true_relres(A, res[j].x, B[:, j]) for j in range(R)]
    fault_recovered = any(r.attempts > 1 for r in res)
    parity = bool(all(r.converged for r in res)
                  and all(rr <= RTOL * 1.05 for rr in srv_rres)
                  and all(rr <= RTOL * 1.05 for rr in seq_rres)
                  and fault_recovered)
    speedup = rate / seq_rate if seq_rate > 0 else 0.0
    return dict(config="cfg9_serving", n=n, requests=R,
                max_k=max_k, batching_window_s=srv.window,
                offered_rate_per_s=round(lam, 1),
                wall_s=round(wall, 4),
                seq_wall_s=round(seq_wall, 4),
                solves_per_s=round(rate, 2),
                seq_solves_per_s=round(seq_rate, 2),
                speedup_vs_sequential=round(speedup, 3),
                p50_latency_ms=round(float(np.percentile(lat_ms, 50)), 2),
                p99_latency_ms=round(float(np.percentile(lat_ms, 99)), 2),
                mean_batch_width=round(stats["mean_width"], 2),
                max_batch_width=max(stats["width_hist"], default=0),
                batches=stats["batches"],
                queue_wait_p50_ms=round(
                    stats.get("queue_wait_p50_s", 0.0) * 1e3, 2),
                padded_cols=stats["padded_cols"],
                injected_fault_recovered=bool(fault_recovered),
                max_rel_residual=float(max(srv_rres)),
                target_100x=bool(speedup >= 100.0),
                residual_parity=parity)


def config10(comm, quick):
    """Elastic degraded-mesh recovery under sustained serving load
    (round 11, ISSUE 8): a SolveServer session survives ONE injected
    PERMANENT device loss (``device.lost`` — sticky per-device, so
    same-mesh retries are futile by construction) by resharding the
    in-flight block onto the largest viable smaller mesh, resuming it
    from the checkpointed iterate, and adopting the degraded mesh
    server-wide.

    Three phases over the same operator/session: HEALTHY load on the
    full mesh (baseline solves/s), the LOSS phase (the fault fires at
    the 2nd dispatched block with real partial state, every pending
    future must still resolve), and DEGRADED load on the shrunk mesh
    (the capacity number an operator plans around). Reported: both
    sustained rates and their ratio, the recovery wall-clock split into
    reshard (checkpoint reload + operand/PC/program rebuild on the new
    geometry) and adoption (re-registering other residents), the
    old/new device counts, the iteration the resumed solve continued
    from (must be > 0 — progress survived the hardware), and the
    strict per-request fp64 residual-parity gate applied ACROSS the
    shrink boundary: every request of every phase, batch-mates of the
    dying block included, must converge with a true fp64 relative
    residual at rtol. A 1-device parent cannot shrink, so it re-runs
    this config in a subprocess on the 8-virtual-device CPU host
    platform (XLA_FLAGS must precede the jax import) and adopts that
    row, marked ``virtual_mesh``.
    """
    if comm.size < 2:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
        env.setdefault("JAX_PLATFORMS", "cpu")
        cmd = [sys.executable, os.path.abspath(__file__),
               "--configs", "cfg10"]
        if quick:
            cmd.append("--quick")
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=1800)
        for line in proc.stdout.splitlines():
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict) and row.get("config") == "cfg10_elastic":
                row["virtual_mesh"] = True
                return row
        raise RuntimeError(
            f"cfg10 subprocess produced no row (rc={proc.returncode}): "
            f"{proc.stderr[-500:]}")

    from mpi_petsc4py_example_tpu.resilience import RetryPolicy
    from mpi_petsc4py_example_tpu.resilience import faults as _faults
    from mpi_petsc4py_example_tpu.serving import SolveServer
    from mpi_petsc4py_example_tpu.utils import profiling

    R = 12 if quick else 48          # requests PER PHASE
    nx = 10 if quick else 16
    max_k = 4 if quick else 8
    A = poisson3d_csr(nx)
    n = nx ** 3
    rng = np.random.default_rng(10)
    Xt = rng.random((n, 3 * R)).astype(np.float32)
    B = np.asarray(A @ Xt).astype(np.float32)
    rtol_inner = RTOL * 0.5          # the cfg-suite margin discipline

    srv = SolveServer(comm, window=0.002, max_k=max_k, pad_pow2=True,
                      resilient=True,
                      retry_policy=RetryPolicy(sleep=lambda _d: None))
    widths = [1 << p for p in range(max_k.bit_length())
              if (1 << p) <= max_k]
    srv.register_operator("poisson", A, pc_type="jacobi",
                          rtol=rtol_inner, warm_widths=widths)
    rres = {}

    def phase(lo, hi):
        t0 = time.perf_counter()
        futs = {j: srv.submit("poisson", B[:, j]) for j in range(lo, hi)}
        results = {j: f.result(600) for j, f in futs.items()}
        wall = time.perf_counter() - t0
        for j, r in results.items():
            rres[j] = true_relres(A, r.x, B[:, j])
        ok = all(r.converged for r in results.values())
        return wall, ok

    try:
        # ---- phase 1: healthy load on the full mesh
        healthy_wall, healthy_ok = phase(0, R)
        healthy_rate = R / healthy_wall if healthy_wall > 0 else 0.0

        # ---- phase 2: permanent loss mid-load — fires at the 2nd
        # dispatched block boundary with 6 iterations of real partial
        # state; the shrink must resume it, not restart it
        victim = comm.device_ids[-1]
        with tps.inject_faults(
                f"device.lost=unavailable:device={victim}:at=2:iter=6"):
            loss_wall, loss_ok = phase(R, 2 * R)
        stats = srv.stats()
        shrinks = stats["mesh_shrinks"]
        reshard_s = (profiling.mesh_shrinks()[-1]["rebuild_s"]
                     if profiling.mesh_shrinks() else 0.0)
        adopt_s = shrinks[-1]["adopt_wall_s"] if shrinks else 0.0
        resumed = shrinks[-1]["resumed_iteration"] if shrinks else 0
        old_n, new_n = comm.size, srv.comm.size

        # ---- phase 3: degraded load on the shrunk mesh
        degraded_wall, degraded_ok = phase(2 * R, 3 * R)
        degraded_rate = (R / degraded_wall if degraded_wall > 0 else 0.0)
    finally:
        srv.shutdown(wait=False)
        _faults.heal()

    parity = bool(healthy_ok and loss_ok and degraded_ok
                  and all(r <= RTOL * 1.05 for r in rres.values())
                  and len(shrinks) == 1 and new_n < old_n
                  and resumed > 0)
    return dict(config="cfg10_elastic", n=n, requests_per_phase=R,
                max_k=max_k, devices=old_n,
                wall_s=round(loss_wall, 4),
                healthy_wall_s=round(healthy_wall, 4),
                degraded_wall_s=round(degraded_wall, 4),
                healthy_solves_per_s=round(healthy_rate, 2),
                degraded_solves_per_s=round(degraded_rate, 2),
                degraded_capacity_ratio=round(
                    degraded_rate / healthy_rate, 3)
                    if healthy_rate > 0 else 0.0,
                recovery_wall_s=round(reshard_s + adopt_s, 4),
                reshard_s=round(reshard_s, 4),
                adopt_s=round(adopt_s, 4),
                old_devices=old_n, new_devices=new_n,
                resumed_iteration=int(resumed),
                max_rel_residual=float(max(rres.values())),
                residual_parity=parity)


def config11(comm, quick):
    """Mixed-precision compute plans (round 10, ROADMAP item 4): 128³
    Poisson CG at bf16/f32/f64 inner precision under fp64 iterative
    refinement (solvers/refine.RefinedKSP + the cg_plans precision
    plans), all three variants gated at the SAME strict fp64 rtol 1e-10
    residual parity against the scipy CPU oracle (the cfg6 gate, per
    precision).

    Per variant: e2e refined wall, refine-step count, delta-method
    per-INNER-iteration cost, and a modeled bytes-per-iterate —
    published as an achieved-GB/s row in ``-log_view``
    (utils/profiling.record_kernel_traffic). The headline is the
    bandwidth ratio: bf16 storage moves 1/4 the bytes per iterate of
    f64 (1/2 of f32), which on a memory-bandwidth-bound VMEM-resident
    pipeline is the per-iteration speedup ceiling; on
    hosts where f64 is native (this CPU mesh) the wall-clock ratio
    understates it, so the gate accepts EITHER a >=1.5x measured
    per-iteration speedup OR the >=1.8x modeled byte reduction the
    GB/s table prices. A resident-size probe
    (ops/pallas_stencil.resident_zdepth) shows the VMEM-resident
    z-depth — the largest grid that stays resident — exactly doubling
    under bf16 storage.
    """
    import scipy.sparse.linalg as spla

    from mpi_petsc4py_example_tpu.ops.pallas_stencil import resident_zdepth
    from mpi_petsc4py_example_tpu.solvers.refine import RefinedKSP
    from mpi_petsc4py_example_tpu.utils.profiling import (
        record_kernel_traffic)

    rtol = 1e-10
    nx = 20 if quick else 128
    n = nx ** 3
    A = poisson3d_csr(nx)
    x_true, b = manufactured(A, dtype=np.float64)

    # scipy fp64 CG at the SAME tolerance — the equal-accuracy oracle
    Mj = spla.LinearOperator(A.shape, matvec=lambda v: v / A.diagonal())
    x_cpu, cpu_iters, cpu = _counting(spla.cg, A, b, rtol=rtol, M=Mj,
                                      maxiter=40000)
    cpu_rres = true_relres(A, x_cpu, b)

    variants = {}
    parity = cpu_rres <= rtol * 1.05
    for prec in ("bf16", "f32", "f64"):
        rk = RefinedKSP().create(comm)
        rk.set_inner_precision(prec)
        rk.set_operators(A)
        rk.set_type("cg")
        rk.get_pc().set_type("jacobi")
        rk.set_tolerances(rtol=rtol)
        rk.solve(b)                          # warm-up / compile
        t0 = time.perf_counter()
        x, res = rk.solve(b)
        wall = time.perf_counter() - t0
        rres = true_relres(A, x, b)
        ok = bool(res.converged and rres <= rtol * 1.05)
        parity = parity and ok
        itemsize = np.dtype(rk.inner_dtype).itemsize
        # bytes/iterate model of the inner CG+jacobi step on the 7-diag
        # DIA operator: 7 diagonal rows + ~10 vector passes (SpMV
        # read/write + the fused x/r/p update chain), all at the
        # STORAGE width — the quantity the precision plan halves
        bytes_per_iter = float(n * itemsize * (7 + 10))
        row = dict(refined_wall_s=round(wall, 4),
                   refine_steps=int(rk.refine_steps),
                   inner_iters=int(res.iterations),
                   rel_residual=rres,
                   residual_parity=ok,
                   itemsize=itemsize,
                   model_bytes_per_iter=bytes_per_iter)
        if not quick:
            ob = onchip_breakdown(comm, rk._inner_op, b, "cg", "jacobi")
            row.update(ob)
            per_s = ob["onchip_per_iter_us"] / 1e6
            # the -log_view achieved-GB/s row for this precision variant
            record_kernel_traffic(f"cfg11_inner_cg[{prec},{nx}^3]",
                                  bytes_per_iter, per_s)
            row["achieved_gbps"] = round(
                bytes_per_iter / per_s / 1e9, 2) if per_s > 0 else 0.0
        variants[prec] = row

    bytes_ratio = (variants["f64"]["model_bytes_per_iter"]
                   / variants["bf16"]["model_bytes_per_iter"])
    speedup = 0.0
    if not quick:
        speedup = (variants["f64"]["onchip_per_iter_us"]
                   / max(variants["bf16"]["onchip_per_iter_us"], 1e-9))
    # the acceptance gate: measured per-iteration speedup where f64 is
    # emulated, or the modeled byte reduction where it is native
    bandwidth_win = bool(speedup >= 1.5 or bytes_ratio >= 1.8)
    # resident-size probe at the production 512^2 plane geometry
    rz32 = resident_zdepth(512, 512, np.float32)
    rz16 = resident_zdepth(512, 512, np.dtype("bfloat16"))
    return dict(config="cfg11_mixed_precision", n=n, rtol=rtol,
                wall_s=variants["bf16"]["refined_wall_s"],
                cpu_wall_s=round(cpu, 4), cpu_iters=int(cpu_iters),
                cpu_rel_residual=cpu_rres,
                variants=variants,
                speedup_bf16_vs_f64_per_iter=round(speedup, 3),
                bytes_per_iter_ratio_f64_over_bf16=round(bytes_ratio, 2),
                bandwidth_win=bandwidth_win,
                resident_zdepth_f32=int(rz32),
                resident_zdepth_bf16=int(rz16),
                # at least doubles: halved planes double the resident
                # count exactly; the fixed 2*nbuf halo-plane overhead
                # amortizes better on top
                resident_doubling=bool(rz16 >= 2 * rz32),
                # residual_parity means ACCURACY parity, like every other
                # config; the bandwidth gate is its own field (the cfg11
                # CI smoke asserts both independently)
                residual_parity=bool(parity))


def config12(comm, quick):
    """Telemetry overhead (round 13, ISSUE 11): the cfg2-class repeated
    CG solve workload with the telemetry layer OFF vs ON — spans +
    metrics registry + flight recorder all armed on the ON side.

    Spans are pure host work (a dict, two clock reads, a ring append per
    span; no XLA programs, no device dispatches — the zero-program proof
    is tests/test_telemetry.py's live-arrays check), so the guard is
    strict: <2% end-to-end wall overhead, measured best-of over batches
    of solves so timer/scheduler noise amortizes (the cfg5/cfg8 best-of
    discipline), and folded into ``residual_parity`` so a telemetry
    regression fails the parity gate like any numerics regression.
    Also reports the per-iteration latency histogram the registry now
    feeds (-log_view's new row): p50/p99 across the run's solves.
    """
    from mpi_petsc4py_example_tpu import telemetry

    nx = 16 if quick else 32
    nsolve = 3 if quick else 10
    reps = 1 if quick else 3
    A = poisson3d_csr(nx)
    n = nx ** 3
    M = tps.Mat.from_scipy(comm, A, dtype=np.float32)
    x_true, b = manufactured(A, dtype=np.float32)

    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type("cg")
    ksp.get_pc().set_type("jacobi")
    ksp.set_tolerances(rtol=RTOL * 0.5, atol=0.0, max_it=20000)
    x, bv = M.get_vecs()
    bv.set_global(b)
    ksp.solve(bv, x)              # warm-up / compile (shared both sides)

    def batch_wall():
        t0 = time.perf_counter()
        for _ in range(nsolve):
            x.zero()
            res = ksp.solve(bv, x)
        return time.perf_counter() - t0, res

    telemetry.disable()
    wall_off = res_off = None
    for _ in range(reps):
        w, res_off = batch_wall()
        wall_off = w if wall_off is None else min(wall_off, w)

    telemetry.enable(flight_len=512)
    try:
        wall_on = res_on = None
        for _ in range(reps):
            w, res_on = batch_wall()
            wall_on = w if wall_on is None else min(wall_on, w)
        spans = telemetry.flight_recorder.spans()
        n_spans = len([s for s in spans if s["name"] == "ksp.solve"])
    finally:
        telemetry.disable()

    rres = true_relres(A, x.to_numpy(), b)
    overhead = (wall_on - wall_off) / wall_off if wall_off > 0 else 0.0
    # <2% wall — the ISSUE-11 acceptance guard (spans are host-side
    # microseconds against a multi-ms solve; a miss means a dispatch or
    # allocation leaked into the armed path)
    overhead_ok = overhead < 0.02
    hist = telemetry.registry.histogram("solve.per_iter_seconds")
    s = hist.summary((50, 99))
    out = dict(config="cfg12_telemetry_overhead", n=n, nsolve=nsolve,
               wall_off_s=round(wall_off, 4),
               wall_on_s=round(wall_on, 4),
               overhead_pct=round(100.0 * overhead, 2),
               telemetry_overhead_ok=bool(overhead_ok),
               spans_per_solve=round(n_spans / max(nsolve * reps, 1), 2),
               per_iter_p50_us=round(s["p50"] * 1e6, 3),
               per_iter_p99_us=round(s["p99"] * 1e6, 3),
               iters_off=res_off.iterations, iters_on=res_on.iterations,
               rel_residual=rres)
    out.update(parity_fields(res_on, rres))
    # telemetry must never change the numerics (identical iteration
    # counts) and must hold the overhead guard
    out["residual_parity"] = bool(
        out["residual_parity"] and overhead_ok
        and res_on.iterations == res_off.iterations and n_spans > 0)
    return out


def config13(comm, quick):
    """Megasolve whole-solve fusion (round 14, ROADMAP item 3 first
    half): the fused one-dispatch RefinedKSP program
    (solvers/megasolve.py, ``-ksp_megasolve``) vs the unfused
    host-driven refinement loop on 128³ Poisson, across inner
    precisions {bf16, f32}.

    Per precision: COLD single-solve e2e wall (fresh program caches —
    trace + compile + the solve itself, the full first-request cost a
    fresh process pays) and warm wall, both ways; the compiled-program
    launch count per solve read from the telemetry ``dispatch.programs``
    counter — the fused path must measure EXACTLY 1 where the unfused
    path pays one launch per outer step (the ``dispatch_count_ok``
    assertion, the tentpole's acceptance gate); and the parity gate per
    variant: f32 must reach the strict fp64 rtol 1e-10 target BOTH ways
    (the fused program's exit gate is that very check, in-program), and
    every variant's fused outcome must MATCH the unfused refinement —
    bf16 at 128^3 is conditioning-limited (cond(A)*eps_bf16 ~ 13 >> 1:
    the Wilkinson recurrence stagnates at ~1e-3 IDENTICALLY fused and
    unfused — measured byte-equal final residuals), so its gate is
    agreement, not an accuracy bf16 cannot deliver. Measured at 128^3
    (8-device CPU mesh, aggregated across the two variants — per-variant
    walls swing +-30% run to run on this contended host): fused warm
    aggregate 40.1 s vs unfused 52.2 s (1.30x), cold aggregate also
    below in every measured run; the CI quick smoke gates the warm
    aggregate. Each removed launch additionally buys its full dispatch
    latency.

    ``serving`` is the cfg9-style rerun with a megasolve session: a
    burst of requests through a SolveServer whose operator session
    routes coalesced blocks through the fused batched program — one
    launch per dispatched block (asserted from the counter), p50/p99
    completion latency reported. On the CPU mesh (µs dispatch) the
    fused wall win comes from removing the per-outer-step host
    round-trips (placements, fetches, and the host-side fp64 residual
    SpMV); each removed launch is worth its full dispatch latency —
    2 + steps launches to 1.
    """
    from mpi_petsc4py_example_tpu.serving import SolveServer
    from mpi_petsc4py_example_tpu.solvers import megasolve as mega_mod
    from mpi_petsc4py_example_tpu.solvers.krylov import (
        _PROGRAM_CACHE, _PROGRAM_CACHE_MANY)
    from mpi_petsc4py_example_tpu.solvers.refine import RefinedKSP
    from mpi_petsc4py_example_tpu.utils.profiling import dispatch_counts

    rtol = 1e-10
    nx = 20 if quick else 128
    n = nx ** 3
    A = poisson3d_csr(nx)
    x_true, b = manufactured(A, dtype=np.float64)
    bn = float(np.linalg.norm(b))

    def cold_caches():
        # a COLD solve must pay trace+compile: evict this process's
        # program caches (the AOT disk cache is also bypassed so the
        # measured cold wall is the honest fresh-machine cost)
        _PROGRAM_CACHE.clear()
        _PROGRAM_CACHE_MANY.clear()
        mega_mod._MEGASOLVE_CACHE.clear()
        mega_mod._MEGASOLVE_CACHE_MANY.clear()

    def counted_solve(rk):
        before = dispatch_counts()
        t0 = time.perf_counter()
        x, res = rk.solve(b)
        wall = time.perf_counter() - t0
        after = dispatch_counts()
        launches = int(sum(after.values()) - sum(before.values()))
        return x, res, wall, launches

    old_aot = os.environ.get("TPU_SOLVE_AOT")
    os.environ["TPU_SOLVE_AOT"] = "0"
    try:
        variants = {}
        parity = True
        dispatch_ok = True
        for prec in ("bf16", "f32"):
            row = {}
            for fused in (False, True):
                rk = RefinedKSP().create(comm)
                rk.set_inner_precision(prec)
                rk.set_operators(A)
                rk.set_type("cg")
                rk.get_pc().set_type("jacobi")
                rk.set_tolerances(rtol=rtol)
                rk.megasolve = fused
                cold_caches()
                x, res, cold, launches = counted_solve(rk)
                # warm wall: best of 3 (the cfg8 discipline — single
                # warm walls on this contended mesh carry ~20% noise,
                # which at quick scale swamps the fused win)
                warm = float("inf")
                for _ in range(3):
                    _, res2, w, launches2 = counted_solve(rk)
                    warm = min(warm, w)
                rres = true_relres(A, x, b)
                key = "fused" if fused else "unfused"
                row[key] = dict(cold_wall_s=round(cold, 4),
                                warm_wall_s=round(warm, 4),
                                refine_steps=int(rk.refine_steps),
                                inner_iters=int(res.iterations),
                                launches_cold=launches,
                                launches_warm=launches2,
                                rel_residual=rres,
                                reason=int(res.reason),
                                reaches_rtol=bool(res.converged
                                                  and rres <= rtol * 1.05))
                if fused:
                    # the tentpole's measured fact: ONE compiled-program
                    # launch per fused request, cold or warm
                    dispatch_ok = (dispatch_ok and launches == 1
                                   and launches2 == 1)
                else:
                    dispatch_ok = dispatch_ok and launches > 1
            # the parity CLAIM of the fusion: the fused program must
            # reproduce the unfused refinement's outcome — both reach
            # the strict rtol, or (where the storage precision is
            # conditioning-limited, e.g. bf16 at 128^3 where
            # cond(A)*eps_bf16 >> 1 stagnates the Wilkinson recurrence
            # identically both ways) both stop for the same reason at
            # residuals agreeing to 10%. f32 must ALWAYS reach rtol —
            # the representative strict-accuracy variant.
            uf, fu = row["unfused"], row["fused"]
            agree = (uf["reaches_rtol"] and fu["reaches_rtol"]) or (
                not uf["reaches_rtol"] and not fu["reaches_rtol"]
                and uf["reason"] == fu["reason"]
                and abs(uf["rel_residual"] - fu["rel_residual"])
                <= 0.1 * max(uf["rel_residual"], 1e-300))
            row["fused_matches_unfused"] = bool(agree)
            ok = agree and (fu["reaches_rtol"] if prec == "f32"
                            else True)
            parity = parity and ok
            row["cold_speedup"] = round(
                row["unfused"]["cold_wall_s"]
                / max(row["fused"]["cold_wall_s"], 1e-9), 3)
            row["warm_speedup"] = round(
                row["unfused"]["warm_wall_s"]
                / max(row["fused"]["warm_wall_s"], 1e-9), 3)
            variants[prec] = row

        # the wall-clock win gates compare AGGREGATES across the
        # precision variants: per-variant walls on this contended CPU
        # mesh swing +-30% run to run (the unfused path's own
        # cold-vs-warm spread reaches ~18%), while the summed fused
        # wall beat the summed unfused wall in every measured full and
        # quick run (128^3: 40.1 s vs 52.2 s warm). Cold additionally
        # pays the nested program's larger trace, so --quick runs gate
        # on the WARM aggregate (the CI smoke asserts it) and report
        # cold honestly.
        def _total(which, key):
            return sum(v[which][key] for v in variants.values())
        fused_cold_win = bool(_total("fused", "cold_wall_s")
                              < _total("unfused", "cold_wall_s"))
        fused_warm_win = bool(_total("fused", "warm_wall_s")
                              < _total("unfused", "warm_wall_s"))

        # ---- cfg9-style serving rerun: fused one-launch dispatches ----
        R = 24 if quick else 96
        nxs = 16 if quick else 32
        As = poisson3d_csr(nxs)
        Ms = tps.Mat.from_scipy(comm, As, dtype=np.float32)
        rng = np.random.default_rng(13)
        rhs = rng.standard_normal((R, nxs ** 3)).astype(np.float32)
        before = dispatch_counts()
        t0 = time.perf_counter()
        with SolveServer(comm, window=0.002, max_k=16,
                         autostart=True) as srv:
            srv.register_operator("p", Ms, pc_type="jacobi", rtol=1e-6,
                                  megasolve=True)
            futs = []
            t_done = {}
            for i in range(R):
                t_sub = time.perf_counter()
                fut = srv.submit("p", rhs[i])
                # per-request completion stamp at RESOLUTION time (the
                # cfg9 done-callback discipline) — stamping after the
                # whole burst would report burst-end minus submit for
                # every request
                fut.add_done_callback(
                    lambda _f, j=i: t_done.__setitem__(
                        j, time.perf_counter()))
                futs.append((t_sub, fut))
            served = [f.result(600) for _, f in futs]
            lat = sorted(t_done[j] - t_sub
                         for j, (t_sub, _f) in enumerate(futs))
            stats = srv.stats()
        serve_wall = time.perf_counter() - t0
        after = dispatch_counts()
        mega_launches = int(after.get("megasolve_many", 0)
                            - before.get("megasolve_many", 0))
        serve_parity = True
        for i, r in enumerate(served):
            rres = float(np.linalg.norm(rhs[i] - As @ np.asarray(
                r.x, dtype=np.float64))
                / max(np.linalg.norm(rhs[i]), 1e-300))
            serve_parity = serve_parity and rres <= 1e-6 * 1.5
        # every coalesced block dispatched as exactly ONE fused launch
        serving_dispatch_ok = mega_launches == int(stats["batches"])
        dispatch_ok = dispatch_ok and serving_dispatch_ok
        serving = dict(
            requests=R, wall_s=round(serve_wall, 4),
            solves_per_s=round(R / serve_wall, 1),
            p50_latency_ms=round(lat[len(lat) // 2] * 1e3, 2),
            p99_latency_ms=round(lat[min(len(lat) - 1,
                                         int(len(lat) * 0.99))] * 1e3,
                                 2),
            batches=int(stats["batches"]),
            mean_batch_width=round(stats["mean_width"], 2),
            fused_launches=mega_launches,
            one_launch_per_batch=bool(serving_dispatch_ok),
            residual_parity=bool(serve_parity))
        parity = parity and serve_parity
    finally:
        if old_aot is None:
            os.environ.pop("TPU_SOLVE_AOT", None)
        else:
            os.environ["TPU_SOLVE_AOT"] = old_aot

    return dict(config="cfg13_megasolve", n=n, rtol=rtol,
                wall_s=variants["f32"]["fused"]["cold_wall_s"],
                variants=variants, serving=serving,
                fused_dispatches_per_solve=1 if dispatch_ok else -1,
                dispatch_count_ok=bool(dispatch_ok),
                fused_cold_win=bool(fused_cold_win),
                fused_warm_win=bool(fused_warm_win),
                residual_parity=bool(parity))


def config14(comm, quick):
    """Fleet serving (round 15, ROADMAP item 2 phase 2): a SolveRouter
    sharding sessions across N SolveServer replicas with consistent-hash
    placement, QoS-aware scheduling, and the elastic shrink/RE-GROW
    round trip under load.

    Three phases:

    1. **Scaling** — the same mixed-session request set through fleets
       of 1..max replica count: sustained solves/s per fleet size.
       Reported HONESTLY (the cfg9 discipline): process-local replicas
       share one CPU mesh and one GIL'd submitting process, so
       ``near_linear_scaling`` is the real-hardware claim — separate
       hosts per replica — not a local gate; it is reported, never
       folded into parity.
    2. **Overload QoS** — a bulk burst followed by interactive arrivals
       against a deliberately backlogged fleet: per-class completion
       p99. The gate ``interactive_p99 < bulk_p99`` IS folded into
       parity: deadline-weighted preemption is structural scheduling
       behavior, not a hardware property.
    3. **Elastic round trip** — one injected PERMANENT device loss
       mid-load (shrink, resumed past iteration 0), one ``heal()``
       mid-load (re-grow back to the provisioned mesh), with the strict
       per-request fp64 residual-parity gate applied across BOTH
       boundaries and every future required to resolve.

    A 1-device parent re-runs itself on the 8-virtual-device CPU host
    platform (the cfg10 pattern).
    """
    if comm.size < 2:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8"
                            ).strip()
        env.setdefault("JAX_PLATFORMS", "cpu")
        cmd = [sys.executable, os.path.abspath(__file__),
               "--configs", "cfg14"]
        if quick:
            cmd.append("--quick")
        proc = subprocess.run(cmd, env=env, capture_output=True,
                              text=True, timeout=1800)
        for line in proc.stdout.splitlines():
            try:
                row = json.loads(line)
            except json.JSONDecodeError:
                continue
            if isinstance(row, dict) and row.get("config") == "cfg14_fleet":
                row["virtual_mesh"] = True
                return row
        raise RuntimeError(
            f"cfg14 subprocess produced no row (rc={proc.returncode}): "
            f"{proc.stderr[-500:]}")

    from mpi_petsc4py_example_tpu.resilience import RetryPolicy
    from mpi_petsc4py_example_tpu.resilience import faults as _faults
    from mpi_petsc4py_example_tpu.serving import SolveRouter

    nx = 10 if quick else 16
    R = 24 if quick else 96            # requests per scaling fleet
    max_rep = 2 if quick else 4
    n_ops = 4
    A = poisson3d_csr(nx)
    n = nx ** 3
    rng = np.random.default_rng(14)
    rtol_inner = RTOL * 0.5            # the cfg-suite margin discipline
    nosleep = RetryPolicy(sleep=lambda _d: None)
    rres_all = []

    def check(j, r, Bcol):
        rres = true_relres(A, r.x, Bcol)
        rres_all.append(rres)
        return r.converged

    # ---- phase 1: sustained solves/s vs replica count ------------------
    Xt = rng.random((n, R)).astype(np.float32)
    B = np.asarray(A @ Xt).astype(np.float32)
    scaling = []
    reps = [r for r in (1, 2, 4) if r <= max_rep]
    for nrep in reps:
        rt = SolveRouter(nrep, comm, window=0.002, max_k=8,
                         retry_policy=nosleep)
        try:
            for i in range(n_ops):
                rt.register_operator(f"op{i}", A, pc_type="jacobi",
                                     rtol=rtol_inner,
                                     warm_widths=(1, 8))
            # warm pass: compiles must not pollute the measured rate
            [rt.solve(f"op{i}", B[:, 0], timeout=600)
             for i in range(n_ops)]
            t0 = time.perf_counter()
            futs = [rt.submit(f"op{j % n_ops}", B[:, j])
                    for j in range(R)]
            res = [f.result(600) for f in futs]
            wall = time.perf_counter() - t0
        finally:
            rt.shutdown(wait=False)
        ok = all(check(j, r, B[:, j]) for j, r in enumerate(res))
        scaling.append({"replicas": nrep,
                        "solves_per_s": round(R / wall, 2),
                        "wall_s": round(wall, 4),
                        "all_converged": bool(ok)})
    rate1 = scaling[0]["solves_per_s"]
    rateN = scaling[-1]["solves_per_s"]
    speedup = rateN / rate1 if rate1 > 0 else 0.0
    near_linear = bool(speedup >= 0.7 * reps[-1])

    # ---- phase 2: overload QoS — interactive p99 < bulk p99 ------------
    import threading

    n_bulk = 24 if quick else 64
    n_int = 8 if quick else 16
    K = n_bulk + n_int
    Xt2 = rng.random((n, K)).astype(np.float32)
    B2 = np.asarray(A @ Xt2).astype(np.float32)
    done_at = {}
    t_sub = {}
    # Future.set_result wakes result() waiters BEFORE running done
    # callbacks, so the main thread can read the latency map while the
    # last mark() has not fired yet — count callbacks and wait for all
    all_marked = threading.Event()
    left = [K]
    mark_lock = threading.Lock()

    def mark(j):
        def cb(_f):
            done_at[j] = time.monotonic()
            with mark_lock:
                left[0] -= 1
                if left[0] == 0:
                    all_marked.set()
        return cb

    rt = SolveRouter(2, comm, window=0.002, max_k=8, max_queue=K + 8,
                     retry_policy=nosleep)
    try:
        rt.register_operator("p", A, pc_type="jacobi", rtol=rtol_inner,
                             warm_widths=(1, 8))
        rt.solve("p", B2[:, 0], timeout=600)          # warm
        futs = {}
        # the bulk burst lands first — a backlog the interactive
        # arrivals must preempt through, not wait behind
        for j in range(n_bulk):
            t_sub[j] = time.monotonic()
            futs[j] = rt.submit("p", B2[:, j], qos="bulk")
            futs[j].add_done_callback(mark(j))
        for j in range(n_bulk, K):
            t_sub[j] = time.monotonic()
            futs[j] = rt.submit("p", B2[:, j], qos="interactive")
            futs[j].add_done_callback(mark(j))
        res2 = {j: f.result(600) for j, f in futs.items()}
        assert all_marked.wait(60), "done-callbacks did not all run"
        qos_stats = rt.stats()
    finally:
        rt.shutdown(wait=False)
    ok2 = all(check(j, r, B2[:, j]) for j, r in res2.items())
    lat = {j: (done_at[j] - t_sub[j]) * 1e3 for j in range(K)}
    bulk_p99 = float(np.percentile([lat[j] for j in range(n_bulk)], 99))
    int_p99 = float(np.percentile([lat[j] for j in range(n_bulk, K)], 99))
    qos_ok = bool(int_p99 < bulk_p99)
    shed = qos_stats["shed"]

    # ---- phase 3: loss -> shrink -> heal -> re-grow under load ---------
    E = 12 if quick else 32
    Xt3 = rng.random((n, 2 * E)).astype(np.float32)
    B3 = np.asarray(A @ Xt3).astype(np.float32)
    victim = comm.device_ids[-1]
    rt = SolveRouter(1, comm, window=0.002, max_k=4,
                     retry_policy=nosleep)
    try:
        rt.register_operator("p", A, pc_type="jacobi", rtol=rtol_inner,
                             warm_widths=(1, 4))
        rt.solve("p", B3[:, 0], timeout=600)          # warm
        with tps.inject_faults(
                f"device.lost=unavailable:device={victim}:at=1:iter=6"):
            futs = [rt.submit("p", B3[:, j]) for j in range(E)]
            res_loss = [f.result(600) for f in futs]
        st = rt.stats()
        per = list(st["per_replica"].values())[0]
        shrinks = per["mesh_shrinks"]
        resumed = shrinks[-1]["resumed_iteration"] if shrinks else 0
        old_n = comm.size
        new_n = per["devices"]
        _faults.heal()
        regrown_replicas = rt.heal_check()
        futs = [rt.submit("p", B3[:, E + j]) for j in range(E)]
        res_heal = [f.result(600) for f in futs]
        st = rt.stats()
        per = list(st["per_replica"].values())[0]
        regrows = per["mesh_regrows"]
        regrown_n = per["devices"]
    finally:
        rt.shutdown(wait=False)
        _faults.heal()
    ok3 = (all(check(j, r, B3[:, j])
               for j, r in enumerate(res_loss))
           and all(check(E + j, r, B3[:, E + j])
                   for j, r in enumerate(res_heal)))

    parity = bool(ok2 and ok3
                  and all(s["all_converged"] for s in scaling)
                  and all(r <= RTOL * 1.05 for r in rres_all)
                  and qos_ok
                  and len(shrinks) == 1 and new_n < old_n
                  and resumed > 0
                  and regrown_replicas >= 1 and len(regrows) >= 1
                  and regrown_n == old_n)
    return dict(config="cfg14_fleet", n=n, requests=R,
                sessions=n_ops,
                wall_s=scaling[-1]["wall_s"],
                scaling=scaling,
                solves_per_s=rateN,
                speedup_max_replicas=round(speedup, 3),
                near_linear_scaling=near_linear,
                interactive_p99_ms=round(int_p99, 2),
                bulk_p99_ms=round(bulk_p99, 2),
                qos_p99_ok=qos_ok,
                shed=int(shed),
                old_devices=int(old_n), new_devices=int(new_n),
                regrown_devices=int(regrown_n),
                resumed_iteration=int(resumed),
                max_rel_residual=float(max(rres_all)),
                residual_parity=parity)


def config15(comm, quick):
    """cfg15_sstep: s-step communication-avoiding CG — refined
    rtol-1e-10 parity vs classic CG, fixed-iteration per-method walls
    with per-method crossover latency from the measured psum probe, the
    auto-selector's choice reported honestly (on the CPU mesh psum
    latency is µs-scale, so classic CG keeps winning and the report
    says so), and the 1-site-per-s-block schedule gate enforced before
    any timing is believed."""
    import time as _time
    from mpi_petsc4py_example_tpu.models import (StencilPoisson3D,
                                                 poisson2d_csr)
    from mpi_petsc4py_example_tpu.solvers.krylov import build_ksp_program
    from mpi_petsc4py_example_tpu.solvers.refine import RefinedKSP
    from mpi_petsc4py_example_tpu.utils.hlo import (
        solver_loop_reduce_sites)

    from benchmarks import multichip_weak_scaling as mws

    nx = 16 if quick else 48
    ndev = comm.size
    nz = ((nx + ndev - 1) // ndev) * ndev
    op = StencilPoisson3D(comm, nx, nx, nz)
    n = nx * nx * nz
    t_cfg = _time.perf_counter()

    # ---- schedule gate: ONE reduce site per s-block, pinned on HLO ----
    ksp0 = tps.KSP().create(comm)
    ksp0.set_operators(op)
    ksp0.set_type("sstep")
    ksp0.get_pc().set_type("jacobi")
    ksp0.set_up()
    pc = ksp0.get_pc()
    x0v, b0v = op.get_vecs()
    dt = np.dtype(np.float64)
    gates = {}
    for s in (2, 4, 8):
        prog = build_ksp_program(comm, "sstep", pc, op, sstep_s=s)
        txt = prog.lower(op.device_arrays(), pc.device_arrays(),
                         b0v.data, x0v.data, dt.type(1e-8), dt.type(0.0),
                         dt.type(0.0), np.int32(8)).as_text()
        gates[f"s{s}"] = solver_loop_reduce_sites(txt)
    schedule_gate_ok = all(v == 1 for v in gates.values())

    # ---- the weak-scaling bench's OWN ranking point (one definition of
    # the method table, sites, crossover model, and parity sweep) ----
    iters = 20 if quick else 60
    pt = mws.run_point(comm, nx, iters, repeats=1 if quick else 3,
                       dtype=np.float64, parity=True)
    method_rows = {lb: {"per_iter_us": pt[lb]["per_iter_us"],
                        "iters_per_s": pt[lb]["iters_per_s"],
                        "reduce_sites_per_iter":
                            pt[lb]["reduce_sites_per_iter"]}
                   for lb in mws.METHODS}
    psum_us = pt["psum_per_site_us"]
    crossover = pt["crossover_us"]
    fastest = pt["fastest_measured"]
    sel_dict = pt["autoselect"]
    parity_rel = pt["parity_rel_diff"]

    # ---- refined rtol-1e-10 gate: f32 inner SSTEP under fp64
    # refinement reaches the strict fp64 target (the acceptance bar) ----
    A2 = poisson2d_csr(16 if quick else 32)
    x_true, b2 = manufactured(A2, seed=15)
    rk = RefinedKSP(comm)
    rk.set_inner_precision("f32")
    rk.set_operators(A2)
    rk.set_type("sstep")
    rk.inner.sstep_s = 4
    rk.get_pc().set_type("jacobi")
    rk.set_tolerances(rtol=1e-10)
    xr, rres = rk.solve(b2)
    refined_rel = float(np.linalg.norm(b2 - A2 @ xr)
                        / np.linalg.norm(b2))
    demote_events = sum(1 for e in getattr(rres, "recovery_events", ())
                        if e.kind == "sstep_demote")

    parity = bool(schedule_gate_ok and parity_rel <= 1e-6
                  and refined_rel <= 1e-10 and rres.converged)
    return dict(config="cfg15_sstep", n=n, iters=iters,
                wall_s=_time.perf_counter() - t_cfg,
                methods=method_rows,
                psum_per_site_us=psum_us,
                crossover_us=crossover,
                fastest_measured=fastest,
                autoselect=sel_dict,
                schedule_gate=gates,
                schedule_gate_ok=schedule_gate_ok,
                parity_rel_diff=parity_rel,
                refined_rel_residual=refined_rel,
                demote_events=int(demote_events),
                residual_parity=parity)


def config16(comm, quick):
    """cfg16_multisplit: the asynchronous tier's weak-scaling jitter
    point — where bounded staleness beats every synchronous plan.

    The async claim is about STRAGGLERS, not collective latency:
    seeded exponential jitter (mean J per step, every device —
    resilience/faults ``comm.delay``) is injected into the multisplit
    solve and its wall MEASURED; each synchronous plan's jittered wall
    is MODELED as its measured fault-free wall plus, per iteration, the
    expected MAX of the per-device draws (a lockstep iteration cannot
    complete before its slowest device: E[max of d Exp(J)] = J*H_d).
    Communication-avoiding schedules amortize collective LATENCY, not
    straggler delay — s-step still gets a CLT credit (its s sequential
    inner iterations average the draws: charge J*(1+(H_d-1)/sqrt(s))),
    the most favorable defensible model for the competition. The async
    tier pays only the per-block MEAN, because staleness absorbs
    independent per-step fluctuations instead of propagating them
    through a barrier. ``jitter_crossover_us`` is the per-step jitter
    above which the measured async wall beats the BEST modeled
    synchronous plan; ``async_wins_at_jitter`` gates the top of the
    measured grid. Strict fp64 residual parity is enforced on every
    solve, jittered or not. CPU-mesh caveats in the committed JSON:
    sleeps cannot be injected INSIDE a compiled synchronous while_loop,
    hence the model; and the async tier's host-thread orchestration
    overhead (~0.3 s here) is being compared against µs-scale compiled
    sync walls, so the ZERO-jitter async column loses by design — the
    crossover is the honest headline, not the base wall."""
    import time as _time
    import scipy.sparse as sp
    from mpi_petsc4py_example_tpu.resilience import faults as _faults
    from mpi_petsc4py_example_tpu.solvers.krylov import build_ksp_program
    from mpi_petsc4py_example_tpu.solvers.multisplit import MultisplitSolver
    from mpi_petsc4py_example_tpu.utils.hlo import solver_loop_reduce_sites

    n = 1024 if quick else 4096
    nblocks = 4
    inner_rtol = 1e-4
    rtol = 1e-10
    grid_us = (0, 5_000, 20_000) if quick else (0, 5_000, 20_000, 50_000)
    ndev = comm.size
    h_d = float(sum(1.0 / k for k in range(1, ndev + 1)))
    t_cfg = _time.perf_counter()

    A = sp.diags([-1.0, 4.0, -1.0], [-1, 0, 1], shape=(n, n),
                 format="csr")
    x_true, b = manufactured(A, seed=16)
    bnorm = float(np.linalg.norm(b))

    # ---- synchronous baselines on the SAME operator: converged walls,
    # iteration counts, and the per-iteration reduce-site count pinned
    # on the lowered HLO (the latency-amortization story the straggler
    # model deliberately does NOT credit) ----
    M = tps.Mat.from_scipy(comm, A)
    dt = np.dtype(np.float64)
    sync = {}
    parity_ok = True
    for label, (tp, s) in (("cg", ("cg", None)),
                           ("pipecg", ("pipecg", None)),
                           ("sstep4", ("sstep", 4))):
        ksp = tps.KSP().create(comm)
        ksp.set_operators(M)
        ksp.set_type(tp)
        if s is not None:
            ksp.sstep_s = s
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=rtol)
        x, bv = M.get_vecs()
        bv.set_global(b)
        res = ksp.solve(bv, x)             # compile + warm
        best = float("inf")
        for _ in range(2):
            x.set_global(np.zeros(n))
            t0 = _time.perf_counter()
            res = ksp.solve(bv, x)
            best = min(best, _time.perf_counter() - t0)
        pkw = {} if s is None else {"sstep_s": s}
        txt = build_ksp_program(comm, tp, ksp.get_pc(), M, **pkw).lower(
            M.device_arrays(), ksp.get_pc().device_arrays(),
            bv.data, x.data, dt.type(rtol), dt.type(0.0), dt.type(0.0),
            np.int32(8)).as_text()
        sites = solver_loop_reduce_sites(txt) / (s or 1)
        # straggler charge per iteration: max-of-draws for a per-
        # iteration barrier; CLT credit for s-step's s-deep work chain
        factor = h_d if s is None else 1.0 + (h_d - 1.0) / float(s) ** 0.5
        parity_ok &= bool(res.converged
                          and res.residual_norm <= rtol * bnorm * 10)
        sync[label] = {"wall_s": best, "iters": int(res.iterations),
                       "per_iter_us": best / res.iterations * 1e6,
                       "reduce_sites_per_iter": sites,
                       "straggler_factor": factor}

    # ---- the async tier: fault-free parity gate, then the MEASURED
    # jitter sweep (real seeded sleeps in every block worker) ----
    ms = MultisplitSolver(nblocks=nblocks, rtol=rtol,
                          inner_rtol=inner_rtol)
    ms.set_operator(A)
    async_rows = {}
    refined_rel = float("inf")
    for j_us in grid_us:
        mean_s = j_us / 1e6
        spec = f"comm.delay=delay:times=*:mean={mean_s}:seed=16"
        try:
            if j_us:
                with tps.inject_faults(spec):
                    t0 = _time.perf_counter()
                    r = ms.solve(b)
                    wall = _time.perf_counter() - t0
            else:
                t0 = _time.perf_counter()
                r = ms.solve(b)
                wall = _time.perf_counter() - t0
        finally:
            _faults.heal()
        rres = float(np.linalg.norm(b - A @ r.x) / bnorm)
        parity_ok &= bool(r.converged and rres <= rtol)
        if j_us == 0:
            refined_rel = rres
        async_rows[str(j_us)] = {
            "wall_s": wall, "cut": int(r.cut_version),
            "outer_steps": list(r.block_steps),
            "resyncs": int(r.resyncs),
            "max_stale_seen": int(r.max_stale_seen),
            "rel_residual": rres}

    # ---- modeled synchronous walls over the same grid + crossover ----
    sync_modeled = {
        label: {str(j_us): row["wall_s"] + row["iters"]
                * row["straggler_factor"] * j_us / 1e6
                for j_us in grid_us}
        for label, row in sync.items()}
    diffs = []
    for j_us in grid_us:
        best_sync = min(m[str(j_us)] for m in sync_modeled.values())
        diffs.append((j_us, async_rows[str(j_us)]["wall_s"] - best_sync))
    crossover = None
    for (j0, d0), (j1, d1) in zip(diffs, diffs[1:]):
        if d0 > 0 >= d1:          # async overtakes between j0 and j1
            crossover = j0 + (j1 - j0) * d0 / (d0 - d1)
            break
    if crossover is None and diffs and diffs[0][1] <= 0:
        crossover = 0.0           # async already wins jitter-free
    async_wins = diffs[-1][1] <= 0 if diffs else False

    return dict(
        config="cfg16_multisplit", n=n, nblocks=nblocks, devices=ndev,
        inner_rtol=inner_rtol,
        wall_s=_time.perf_counter() - t_cfg,
        sync=sync, sync_modeled_wall_s=sync_modeled,
        async_measured=async_rows,
        jitter_grid_us=list(grid_us),
        straggler_model=(
            "sync jittered wall MODELED: fault-free wall + iters * "
            f"charge * J; charge = H({ndev}) = {h_d:.3f} (expected max "
            "of per-device Exp(J) draws at a lockstep barrier) for "
            "cg/pipecg, 1 + (H-1)/sqrt(s) for s-step (CLT credit: its "
            "s-deep sequential chain averages draws). Async wall "
            "MEASURED with the same seeded draws injected as real "
            "sleeps (comm.delay) — it pays the per-block MEAN because "
            "bounded staleness absorbs independent fluctuations."),
        cpu_mesh_caveat=(
            "single-host virtual mesh: sleeps cannot be injected inside "
            "a compiled synchronous while_loop, hence the modeled sync "
            "column; the async tier's host-thread orchestration "
            "overhead is compared against ms-scale compiled sync walls, "
            "so the zero-jitter async column loses by design and "
            "jitter_crossover_us is the honest headline. On a real "
            "multi-chip mesh the sync walls gain a per-site latency "
            "term the CPU mesh does not charge."),
        jitter_crossover_us=crossover,
        async_wins_at_jitter=bool(async_wins),
        refined_rel_residual=refined_rel,
        residual_parity=bool(parity_ok))


def config17(comm, quick):
    """cfg17_persistent: the device-resident request queue under
    sustained load — amortized dispatch vs the per-batch tier.

    The workload isolates exactly the structural difference ISSUE 18
    names: every request carries a UNIQUE rtol, so the coalescer's
    compatibility grouping can never put two of them in one block and
    the per-batch megasolve tier pays one ``megasolve_many`` launch per
    request. The persistent tier takes ``(Q,)``-shaped per-slot
    tolerance operands, so those same incompatible requests STAGE
    ACROSS batches into shared launches — the measured
    ``dispatch.programs`` per request drops below 1 (the acceptance
    gate; at full slot occupancy it approaches 1/Q). Arrivals are
    Poisson (seeded exponential gaps), identical in both modes; both
    modes run a warm pre-burst first so program compiles are mostly
    outside the measured window. Per-request strict parity: each
    answer's fp64 TRUE relative residual must meet that request's OWN
    rtol.

    CPU-mesh caveats (committed into the JSON): dispatch here costs
    microseconds, so the WALL-clock win from removing launches is
    noise on this host — ``dispatches_per_request_*`` is the honest
    headline, and the solves/s ratio is reported, not gated. Every
    launch the persistent tier removes is worth its full dispatch
    latency. Occasional mid-run
    retraces (a pow2 slot width first seen during the measured burst)
    add wall noise the warm pre-burst cannot fully remove."""
    from mpi_petsc4py_example_tpu.serving import SolveServer
    from mpi_petsc4py_example_tpu.utils.profiling import dispatch_counts

    rtol0 = 1e-8
    nx = 12 if quick else 24
    A = poisson3d_csr(nx)
    n = A.shape[0]
    R = 32 if quick else 96
    Q = 8
    rng = np.random.default_rng(17)
    Xt = rng.random((n, R))
    B = np.asarray(A @ Xt)
    bn = np.linalg.norm(B, axis=0)
    # every request a UNIQUE rtol: same tolerance CLASS, never the same
    # compatibility group (floats differ) — the per-batch tier cannot
    # coalesce, the persistent tier does not need to
    rtols = [rtol0 * (1.0 + j / (2.0 * R)) for j in range(R)]
    gaps = rng.exponential(0.0005, size=R)
    t_cfg = time.perf_counter()

    def run(persistent):
        parity = True
        with SolveServer(comm, window=0.002, max_k=Q,
                         autostart=True) as srv:
            srv.register_operator("p", A, ksp_type="cg",
                                  pc_type="jacobi", rtol=rtol0,
                                  megasolve=not persistent,
                                  persistent=persistent)
            # warm pre-burst: touch the pow2 slot widths (persistent)
            # / the width-1 block (per-batch) so compiles land before
            # the measured window
            for w in (Q, 3, 1):
                ws = [srv.submit("p", B[:, j % R], rtol=rtols[j % R])
                      for j in range(w)]
                [f.result(600) for f in ws]
                srv.drain(600)
            mid = dispatch_counts()
            t_sub, t_done, futs = {}, {}, []
            t0 = time.perf_counter()
            for j in range(R):
                time.sleep(gaps[j])
                t_sub[j] = time.perf_counter()
                f = srv.submit("p", B[:, j], rtol=rtols[j])
                f.add_done_callback(
                    lambda _f, i=j: t_done.__setitem__(
                        i, time.perf_counter()))
                futs.append(f)
            served = [f.result(600) for f in futs]
            srv.drain(600)
            wall = time.perf_counter() - t0
            stats = srv.stats()
            after = dispatch_counts()
        for j, r in enumerate(served):
            rres = float(np.linalg.norm(B[:, j] - A @ r.x)
                         / max(bn[j], 1e-300))
            parity = parity and bool(r.converged
                                     and rres <= rtols[j] * 1.05)
        # TOTAL compiled-program launches across the measured burst
        # (every kind): the denominator a per-request launch budget is
        # honestly charged against
        disp = int(sum(after.values()) - sum(mid.values()))
        lat = sorted(t_done[j] - t_sub[j] for j in range(R))
        row = dict(
            requests=R, wall_s=round(wall, 4),
            solves_per_s=round(R / wall, 1),
            p50_latency_ms=round(lat[len(lat) // 2] * 1e3, 2),
            p99_latency_ms=round(lat[min(len(lat) - 1,
                                         int(len(lat) * 0.99))] * 1e3,
                                 2),
            dispatches=disp,
            dispatches_per_request=round(disp / R, 4),
            batches=int(stats["batches"]),
            residual_parity=bool(parity))
        if persistent:
            pst = stats.get("persistent", {}).get("p", {})
            row.update(launches=int(pst.get("launches", 0)),
                       mean_requests_per_launch=round(
                           pst.get("requests", 0)
                           / max(pst.get("launches", 1), 1), 2),
                       padded_slots=int(pst.get("padded_slots", 0)),
                       turnovers=int(pst.get("turnovers", 0)),
                       fallbacks=int(pst.get("fallbacks", 0)))
        return row

    per_batch = run(persistent=False)
    pers = run(persistent=True)
    dpr_p = pers["dispatches_per_request"]
    dpr_b = per_batch["dispatches_per_request"]
    return dict(
        config="cfg17_persistent", n=n, devices=int(comm.size),
        requests=R, slots=Q,
        wall_s=round(time.perf_counter() - t_cfg, 4),
        persistent=pers, per_batch=per_batch,
        dispatches_per_request_persistent=dpr_p,
        dispatches_per_request_batch=dpr_b,
        amortization_ok=bool(dpr_p < 1.0 <= dpr_b),
        solves_per_s_ratio=round(pers["solves_per_s"]
                                 / max(per_batch["solves_per_s"],
                                       1e-12), 3),
        cpu_mesh_caveat=(
            "single-host virtual mesh: dispatch costs microseconds, so "
            "the wall/solves_per_s columns mostly measure host "
            "orchestration and occasional mid-burst retraces, not the "
            "launch amortization — dispatches_per_request_* is the "
            "honest headline (gated < 1 persistent, >= 1 per-batch on "
            "this unique-rtol workload). Each launch the persistent "
            "tier removes is worth its full dispatch latency."),
        residual_parity=bool(pers["residual_parity"]
                             and per_batch["residual_parity"]))


def config18(comm, quick):
    """cfg18_transport: the multi-host RPC tier under load — loopback
    vs localhost-socket throughput, then failover after one injected
    host loss.

    Phase 1 serves an identical request burst through BOTH transports
    on a two-host FleetManager: the in-process loopback (function-call
    delivery — the deterministic-CI floor) and real localhost TCP
    sockets (length-prefixed pickled frames, one connection per call —
    every marshalling cost a cross-host deployment pays except the
    network itself). The solves/s ratio is the honest price of host
    separation ON THIS BOX. Phase 2 kills the owning replica host
    after its elastic checkpoint was lease-pulled, then submits again:
    the measured failover wall-clock spans kill -> first re-homed
    answer (detection via the in-flight deadline, checkpoint ship,
    warm re-registration, re-solve), the FailoverEvent's
    ``resumed_iteration`` must be > 0 (the re-homed solve provably
    continued, never a cold restart), and EVERY request — before the
    kill, and after it on the survivor — is gated on its fp64 TRUE
    relative residual: the strict parity gate across the failover
    boundary.

    CPU-mesh caveats (committed into the JSON): both "hosts" are
    threads in one process and the sockets traverse loopback, so
    socket-vs-loopback measures framing + pickling + connection
    setup, not network latency, and the failover wall excludes any
    real failure-detection delay a WAN deployment would pay. The
    structural gates (resumed_iteration > 0, parity across the
    boundary, one truthful owner) are mesh-independent."""
    from mpi_petsc4py_example_tpu.serving.remote import FleetManager

    rtol = 1e-10
    nx = 10 if quick else 16
    A = poisson2d_csr(nx)
    n = A.shape[0]
    R = 12 if quick else 32
    rng = np.random.default_rng(18)
    Xt = rng.random((n, R))
    B = np.asarray(A @ Xt)
    bn = np.linalg.norm(B, axis=0)
    t_cfg = time.perf_counter()

    def _mgr(transport):
        return FleetManager(
            2, comm, transport=transport, window=0.0, max_k=4,
            retry_policy=tps.RetryPolicy(sleep=lambda _d: None),
            client_sleep=lambda _d: None)

    def _parity(j, r):
        rres = float(np.linalg.norm(B[:, j] - A @ r.x)
                     / max(bn[j], 1e-300))
        return bool(r.converged and rres <= rtol * 1.05)

    def run(transport):
        parity = True
        mgr = _mgr(transport)
        try:
            mgr.register_operator("a", A, ksp_type="cg",
                                  pc_type="jacobi", rtol=rtol)
            mgr.solve("a", B[:, 0], timeout=600)   # warm the program
            lat = []
            t0 = time.perf_counter()
            for j in range(R):
                t_sub = time.perf_counter()
                r = mgr.solve("a", B[:, j], timeout=600)
                lat.append(time.perf_counter() - t_sub)
                parity = parity and _parity(j, r)
            wall = time.perf_counter() - t0
        finally:
            mgr.shutdown(wait=False)
        lat.sort()
        return dict(
            transport=transport, requests=R, wall_s=round(wall, 4),
            solves_per_s=round(R / wall, 1),
            p50_latency_ms=round(lat[len(lat) // 2] * 1e3, 2),
            p99_latency_ms=round(lat[min(len(lat) - 1,
                                         int(len(lat) * 0.99))] * 1e3,
                                 2),
            residual_parity=bool(parity))

    loopback = run("loopback")
    sock = run("socket")

    # ---- failover: one injected host loss mid-load (loopback) -----------
    fo_parity = True
    mgr = _mgr("loopback")
    try:
        mgr.register_operator("a", A, ksp_type="cg", pc_type="jacobi",
                              rtol=rtol)
        half = R // 2
        for j in range(half):                  # pre-kill traffic
            fo_parity = fo_parity and _parity(j, mgr.solve(
                "a", B[:, j], timeout=600))
        mgr.lease_step()                       # pull the warm checkpoint
        owner = mgr.router.owner("a")
        t_kill = time.perf_counter()
        mgr.kill_host(owner)
        r = mgr.solve("a", B[:, half], timeout=600)
        failover_wall = time.perf_counter() - t_kill
        fo_parity = fo_parity and _parity(half, r)
        for j in range(half + 1, R):           # post-failover traffic
            fo_parity = fo_parity and _parity(j, mgr.solve(
                "a", B[:, j], timeout=600))
        ev = mgr.failovers[0] if mgr.failovers else None
        resumed = int(ev.resumed_iteration) if ev else 0
        ev_wall = round(float(ev.wall_s), 4) if ev else -1.0
        rehomed = bool(ev and mgr.router.owner("a") != owner)
    finally:
        mgr.shutdown(wait=False)

    return dict(
        config="cfg18_transport", n=n, devices=int(comm.size),
        requests=R, wall_s=round(time.perf_counter() - t_cfg, 4),
        loopback=loopback, socket=sock,
        socket_vs_loopback_ratio=round(
            sock["solves_per_s"]
            / max(loopback["solves_per_s"], 1e-12), 3),
        failover_wall_s=round(failover_wall, 4),
        failover_event_wall_s=ev_wall,
        resumed_iteration=resumed,
        failover_parity_ok=bool(fo_parity and rehomed and resumed > 0),
        cpu_mesh_caveat=(
            "single-process fleet: both hosts are threads and the "
            "sockets traverse loopback, so socket_vs_loopback_ratio "
            "prices framing + pickling + per-call connection setup, "
            "not network latency, and failover_wall_s excludes real "
            "WAN failure-detection delay. The structural gates "
            "(resumed_iteration > 0, rehome off the dead host, fp64 "
            "parity across the boundary) are mesh-independent."),
        residual_parity=bool(loopback["residual_parity"]
                             and sock["residual_parity"]
                             and fo_parity and resumed > 0))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default=None)
    ap.add_argument("--configs", default=None,
                    help="comma-separated subset, e.g. 'cfg1,cfg4' "
                         "(iteration aid; schema checks apply only to "
                         "full sweeps)")
    opts = ap.parse_args()

    import jax

    comm = tps.DeviceComm()
    results = {"platform": jax.devices()[0].platform,
               "devices": len(jax.devices()), "configs": []}
    all_cfgs = {"cfg1": config1, "cfg2": config2, "cfg3": config3,
                "cfg4": config4, "cfg5": config5, "cfg6": config6,
                "cfg7": config7, "cfg8": config8, "cfg9": config9,
                "cfg10": config10, "cfg11": config11, "cfg12": config12,
                "cfg13": config13, "cfg14": config14, "cfg15": config15,
                "cfg16": config16, "cfg17": config17,
                "cfg18": config18}
    if opts.configs:
        names = [s.strip() for s in opts.configs.split(",") if s.strip()]
        bad = [s for s in names if s not in all_cfgs]
        if bad:
            ap.error(f"unknown configs {bad}; choose from {list(all_cfgs)}")
        run_cfgs = {k: all_cfgs[k] for k in names}
    else:
        run_cfgs = all_cfgs
    full_sweep = set(run_cfgs) == set(all_cfgs)
    for fn in run_cfgs.values():
        try:
            r = fn(comm, opts.quick)
        except Exception as e:  # noqa: BLE001 — record per-config failures
            r = dict(config=fn.__name__, error=repr(e))
        results["configs"].append(r)
        print(json.dumps(r))
    parities = [c.get("residual_parity") for c in results["configs"]]
    # the all-configs parity claim only exists for a FULL sweep — a subset
    # run must not write an artifact indistinguishable from the real thing
    key = "residual_parity_all" if full_sweep else "residual_parity_selected"
    results[key] = bool(all(p is True for p in parities))
    print(json.dumps({key: results[key]}))
    if full_sweep:
        check_schema(results, quick=opts.quick)
    if opts.out:
        with open(opts.out, "w") as f:
            json.dump(results, f, indent=2)
    return 0


if __name__ == "__main__":
    sys.exit(main())
