#!/usr/bin/env python
"""Pass-level decomposition of the fused stencil-CG step (TPU only).

Methodology (round 3, now reproducible): each piece of the CG iteration is
timed as an in-device ``fori_loop`` microbenchmark — the loop body is the
piece under test, the program returns a scalar that depends on every carry
(no DCE), and timing differences between two iteration counts isolate pure
loop time (the delta method; D2H of the scalar forces completion).

Pieces:
  adot     — the fused Pallas stencil+<p,Ap> kernel alone
  chain    — the CG vector-update chain alone (x, r, ||r||², p)
  composed — the full cg_stencil_kernel step (fixed-iteration KSP solve)

Usage: python benchmarks/decompose_stencil.py [--n 512] [--iters 40]
Prints one JSON line per piece with ms/iter and HBM passes/iter
(one pass = n³·4 bytes at the device's HBM peak, bench.HBM_PEAK_GBPS).

With ``--vcycle`` the MG V-cycle is decomposed instead (a V-cycle
ablation): full cycle, smoothing-ablated cycle, and the isolated
restriction/prolongation costs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import hbm_peak_gbps, tpu_device  # noqa: E402

# a TPU only; unknown device kinds raise instead of assuming a peak
HBM_GBPS = None     # set in main() from the device's peak


def time_loop(prog, args, iters_lo, iters_hi, reps=3):
    """Delta-method ms/iter of ``prog(*args, iters)``; D2H-forced sync."""
    outs = []
    for iters in (iters_lo, iters_hi):
        prog(*args, iters)                    # warm/compile
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            np.asarray(prog(*args, iters))    # D2H forces completion
            best = min(best, time.perf_counter() - t0)
        outs.append(best)
    return (outs[1] - outs[0]) / (iters_hi - iters_lo)


def vcycle_decomposition(nx: int):
    """MG V-cycle ablation: full cycle,
    smoothing-ablated cycle, isolated transfers, and the round-6
    fused-restriction delta (residual_restrict_fused vs the separate
    residual+restrict passes it replaces)."""
    import jax
    import jax.numpy as jnp

    import mpi_petsc4py_example_tpu.solvers.mg as mg
    from mpi_petsc4py_example_tpu.utils.profiling import (
        record_kernel_traffic)

    r0 = jnp.full((nx, nx, nx), 1e-6, jnp.float32)
    e0 = jnp.full((nx // 2,) * 3, 1e-6, jnp.float32)
    passes_bytes = nx ** 3 * 4

    def report(name, per_s, model_passes=None):
        line = {"piece": name, "ms": round(per_s * 1e3, 3),
                "fine_passes": round(
                    per_s * HBM_GBPS * 1e9 / passes_bytes, 2)}
        if model_passes is not None:
            # achieved effective bandwidth over the piece's own traffic
            # model — the -log_view per-kernel GB/s line (utils/profiling)
            record_kernel_traffic(f"{name}[{nx}^3]",
                                  model_passes * passes_bytes, per_s)
            line["model_passes"] = model_passes
            line["achieved_gbps"] = round(
                model_passes * passes_bytes / per_s / 1e9, 1)
        print(json.dumps(line))

    def cycle_loop():
        cycle = mg.make_vcycle3d(nx, nx, nx)

        @jax.jit
        def loop(r, iters):
            def body(_, r):
                return cycle(r) * jnp.float32(1e-3)
            return jax.lax.fori_loop(0, iters, body, r)[0, 0, :8]
        return loop

    report("vcycle", time_loop(cycle_loop(), (r0,), 8, 24))
    # smoothing ablation: neutralize BOTH the per-sweep path and the
    # round-5 fused pair fast paths (_smooth/_smooth0 dispatch above
    # _sweep now)
    orig = (mg._sweep, mg._smooth, mg._smooth0)
    mg._sweep = lambda u, f, lo, hi, omega=mg._OMEGA, platform=None: u
    mg._smooth = lambda u, f, iters, exchange, omega=mg._OMEGA, \
        platform=None: u
    mg._smooth0 = lambda f, iters, exchange, omega=mg._OMEGA, \
        platform=None: (mg._OMEGA / 6.0) * f
    try:
        report("vcycle_no_smoothing", time_loop(cycle_loop(), (r0,), 8, 24))
    finally:
        mg._sweep, mg._smooth, mg._smooth0 = orig

    def xfer_loop(fn, x):
        @jax.jit
        def loop(v, iters):
            def body(_, c):
                out = fn(c)
                return c * jnp.float32(0.999) + \
                    0 * jnp.float32(jnp.sum(out[0, 0, :4]))
            return jax.lax.fori_loop(0, iters, body, v)[0, 0, :8]
        return loop

    report("restrict", time_loop(
        xfer_loop(lambda r: mg._restrict(r), r0), (r0,), 16, 64),
        model_passes=1.125)                    # read r + write coarse/8
    report("prolong", time_loop(
        xfer_loop(lambda e: mg._prolong(e), e0), (e0,), 16, 64),
        model_passes=1.125)                    # read coarse/8 + write fine
    # the round-6 fused-restriction lever, itemized: the fully-fused
    # kernel (residual + 3-axis restriction from VMEM-resident chunks)
    # vs the separate residual pass + restrict pass it replaces
    f0 = jnp.full((nx, nx, nx), 2e-6, jnp.float32)
    report("residual_restrict_fused", time_loop(
        xfer_loop(lambda u: mg._residual_restrict_fused(u, f0), r0),
        (r0,), 16, 64),
        model_passes=2.125)                    # read u + f, write coarse/8
    report("residual_then_restrict", time_loop(
        xfer_loop(lambda u: mg._restrict(
            mg._residual(u, f0, *mg._no_exchange(u))), r0),
        (r0,), 16, 64),
        model_passes=4.125)   # read u,f / write r / read r / write coarse
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--iters", type=int, default=40)
    ap.add_argument("--vcycle", action="store_true",
                    help="decompose the MG V-cycle instead of the CG step")
    ap.add_argument("--log-view", action="store_true",
                    help="print the per-kernel achieved-GB/s -log_view "
                         "table after the decomposition")
    opts = ap.parse_args()
    nx = opts.n
    global HBM_GBPS
    HBM_GBPS = hbm_peak_gbps(tpu_device().device_kind)
    from mpi_petsc4py_example_tpu.utils import profiling
    if opts.vcycle:
        rc = vcycle_decomposition(nx)
        if opts.log_view:
            profiling.log_view()
        return rc
    lo, hi = opts.iters // 4, opts.iters

    import jax
    import jax.numpy as jnp

    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        _pick_chunk, pallas_supported, stencil3d_dot_pallas)

    assert pallas_supported(nx, nx, jnp.float32), "needs the TPU kernel"
    shape = (nx, nx, nx)
    passes_bytes = nx ** 3 * 4
    chunk, nchunks = _pick_chunk(nx, 4, nx, nx, None)
    print(json.dumps({"n": nx, "chunk": chunk, "nchunks": nchunks}))

    # the per-piece traffic models (read+write vector passes) backing the
    # achieved-GB/s recording: adot reads p and writes Ap (+edge planes),
    # the chain's structural count is 9 passes, the composed CG step 11.25
    _MODEL_PASSES = {"adot": 2.25, "chain": 9.0, "composed": 11.25}

    def report(name, per_s, note=""):
        line = {"piece": name, "ms_per_iter": round(per_s * 1e3, 4),
                "hbm_passes": round(per_s * HBM_GBPS * 1e9 / passes_bytes, 2)}
        model = _MODEL_PASSES.get(name)
        if model is not None:
            profiling.record_kernel_traffic(f"{name}[{nx}^3]",
                                            model * passes_bytes, per_s)
            line["achieved_gbps"] = round(
                model * passes_bytes / per_s / 1e9, 1)
        if note:
            line["note"] = note
        print(json.dumps(line))

    z = jnp.zeros((1, nx, nx), jnp.float32)
    u0 = jnp.full(shape, 1e-20, jnp.float32)

    # ---- adot: the fused kernel alone (spectral radius < 12 keeps 1e-20
    # seed finite for ~40 unscaled iterations) -----------------------------
    @jax.jit
    def adot_loop(u, iters):
        def body(_, u):
            y, d = stencil3d_dot_pallas(u, z, z, nx, nx, nx)
            return y
        u = jax.lax.fori_loop(0, iters, body, u)
        return jnp.sum(u[0, 0, :8])

    report("adot", time_loop(adot_loop, (u0,), lo, hi))

    # ---- chain: the CG update chain alone (same arrays, fixed scalars;
    # beta depends on rr so the reduction is live) -------------------------
    @jax.jit
    def chain_loop(x, r, p, y, iters):
        def body(_, st):
            x, r, p = st
            alpha = jnp.float32(1e-3)
            x = x + alpha * p
            r = r - alpha * y
            rr = jnp.sum(r * r)
            beta = rr * jnp.float32(1e-30)
            p = r * jnp.float32(1.0 / 6.0) + beta * p
            return (x, r, p)
        x, r, p = jax.lax.fori_loop(0, iters, body, (x, r, p))
        return jnp.sum(x[0, 0, :8]) + jnp.sum(r[0, 0, :8]) + jnp.sum(p[0, 0, :8])

    v = jnp.full(shape, 1e-6, jnp.float32)
    report("chain", time_loop(chain_loop, (v, v, v, v), lo, hi))

    # ---- composed: the production fixed-iteration CG solve ---------------
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import StencilPoisson3D

    import bench

    comm = tps.DeviceComm()
    op = StencilPoisson3D(comm, nx, dtype=jnp.float32)
    rng = np.random.default_rng(5)
    b = rng.random(nx ** 3).astype(np.float32)

    def make_fixed(max_it):
        ksp = tps.KSP().create(comm)
        ksp.set_operators(op)
        ksp.set_type("cg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_norm_type("none")
        ksp.set_tolerances(rtol=0.0, atol=0.0, max_it=max_it)
        xv, bv = op.get_vecs()
        bv.set_global(b)
        ksp.solve(bv, xv)
        return ksp, xv, bv

    pers = bench.delta_rate(make_fixed, reps=3, lo=lo, hi=hi,
                            autoscale=False)
    report("composed", float(np.median(pers)),
           note="production cg_stencil_kernel via KSP")
    if opts.log_view:
        profiling.log_view()
    return 0


if __name__ == "__main__":
    sys.exit(main())
