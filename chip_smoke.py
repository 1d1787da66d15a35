#!/usr/bin/env python
"""Drive the solver stack's main path once on the TPU chip, and check it.

One process owns the chip for the whole run; nothing falls back to the CPU.
Every solve prints one line (phase, case, iterations, true relative
residual on the host in fp64, wall seconds, and the path actually taken);
any failed check exits non-zero. The last line of standard output is the
result::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Usage::

    python chip_smoke.py             # one chip: reference, stencil,
                                     # assembled, serving, gates
    python chip_smoke.py --chips 4   # the row-sharded path on 4 chips vs
                                     # the same solves on one, nothing else

Phases (one chip):

- ``reference``: examples/solve_linear.py (the test.py flow) and
  examples/eigensolve.py (the test2.py flow) through tools/tpurun.py's entry
  with ``-n 4``, in this process (the ranks are threads).
- ``stencil``: StencilPoisson3D 256³ fp32, CG + jacobi and CG + mg to rtol
  1e-6; the Pallas kernels must be in the solve program.
- ``assembled``: Mat.from_scipy fp64 — BCGS + bjacobi on convdiff2d(512),
  once with its default ILU(0) blocks and once with 128 dense blocks
  (``-pc_bjacobi_blocks 128 -pc_setup_device auto``, inverted on the device
  or rejected by its quality gate), GMRES + jacobi (``-ksp_monitor``) on
  poisson2d_csr(512), and the ILU(0) block apply on convdiff2d(2048)
  against numpy.
- ``serving``: a SolveServer on the 128³ stencil, 16 requests of mixed
  rtol, per-batch and persistent.
- ``gates``: what the chip shows for the capability gates of
  solvers/krylov.py (io_callback in shard_map) and solvers/eps.py (f64
  Gram error, complex64 eigh).
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
SEED = 0


class SmokeError(Exception):
    """A failed check: the run exits non-zero."""


def check(cond, msg: str):
    if not cond:
        raise SmokeError(msg)


def emit(phase: str, case: str, **fields):
    """One line per solve or finding."""
    body = ", ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] {case}: {body}", flush=True)


def fmt(v: float) -> str:
    return f"{v:.3e}"


# ---------------------------------------------------------------- oracles
def stencil_apply(x: np.ndarray, nx: int, ny: int, nz: int) -> np.ndarray:
    """``A x`` of the 7-point Dirichlet Poisson stencil in fp64 on the
    host, matrix-free (x-fastest ordering, as
    models.stencil.StencilPoisson3D)."""
    u = x.reshape(nz, ny, nx).astype(np.float64)
    y = 6.0 * u
    y[1:] -= u[:-1]
    y[:-1] -= u[1:]
    y[:, 1:] -= u[:, :-1]
    y[:, :-1] -= u[:, 1:]
    y[:, :, 1:] -= u[:, :, :-1]
    y[:, :, :-1] -= u[:, :, 1:]
    return y.reshape(-1)


def stencil_relres(x: np.ndarray, b: np.ndarray, nx: int, ny: int,
                   nz: int) -> float:
    """``||b - A x|| / ||b||`` of the stencil in fp64 on the host."""
    b = b.astype(np.float64)
    return float(np.linalg.norm(b - stencil_apply(x, nx, ny, nz))
                 / np.linalg.norm(b))


def csr_relres(A, x: np.ndarray, b: np.ndarray) -> float:
    """``||b - A x|| / ||b||`` with scipy in fp64 on the host."""
    return float(np.linalg.norm(b - A @ x) / np.linalg.norm(b))


# ----------------------------------------------------------------- solves
def stencil_problem(comm, nx: int, dtype):
    """The stencil operator and ``b = A x_true`` made on the device from
    a seeded ``x_true`` (the host holds one fp32 copy of b for the
    residual check)."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import StencilPoisson3D

    op = StencilPoisson3D(comm, nx, dtype=dtype)
    xt = np.random.default_rng(SEED).random(op.shape[0], dtype=np.float32)
    b = op.mult(tps.Vec.from_global(comm, xt, dtype=dtype))
    return op, b


def stencil_solve(comm, op, b, pc_type: str, rtol: float, bh=None):
    """CG + ``pc_type`` to ``rtol`` twice (cold, then warm) on the stencil
    operator; returns ``(result, x, bh, relres, cold_s, warm_s)`` with the
    residual of the warm solve checked on the host."""
    import mpi_petsc4py_example_tpu as tps

    ksp = tps.KSP().create(comm)
    ksp.set_operators(op)
    ksp.set_type("cg")
    ksp.get_pc().set_type(pc_type)
    ksp.set_tolerances(rtol=rtol, max_it=20000)
    ksp.set_true_residual_check(True)
    walls = []
    for _ in range(2):
        x, _ = op.get_vecs()
        t0 = time.perf_counter()
        res = ksp.solve(b, x)
        xh = x.to_numpy()
        walls.append(time.perf_counter() - t0)
    if bh is None:
        bh = b.to_numpy()
    check(res.converged, f"stencil CG+{pc_type} did not converge: {res}")
    rel = stencil_relres(xh, bh, op.nx, op.ny, op.nz)
    check(rel <= 1.05 * rtol,
          f"stencil CG+{pc_type}: host relres {rel:.3e} > 1.05*{rtol:g}")
    return res, ksp, x, bh, rel, walls[0], walls[1]


def pallas_path(comm, op, ksp, b, x) -> str:
    """Assert the Pallas kernels are in the stencil solve program: the
    operator qualifies, and the lowered program holds a Mosaic
    ``tpu_custom_call``."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import pallas_supported
    from mpi_petsc4py_example_tpu.solvers.krylov import build_ksp_program

    check(pallas_supported(op.ny, op.nx, op.dtype, comm.platform),
          f"pallas_supported is False for {op}")
    pc = ksp.get_pc()
    prog = build_ksp_program(comm, "cg", pc, op)
    dt = np.dtype(op.dtype).type
    text = prog.lower(op.device_arrays(), pc.device_arrays(), b.data,
                      x.data, dt(1e-6), dt(0.0), dt(1e5),
                      np.int32(100)).as_text()
    n = text.count("tpu_custom_call")
    check(n > 0, "no tpu_custom_call in the stencil solve program")
    return f"pallas({n} tpu_custom_call)"


def assembled_solve(comm, A, ksp_type: str, pc_type: str, rtol: float,
                    options=()):
    """``ksp_type`` + ``pc_type`` on the fp64 ``Mat.from_scipy`` of ``A``
    with ``options`` from the options DB; returns the result, the KSP,
    the solution vector, the host relres and the wall (setup, compile
    and solve)."""
    import mpi_petsc4py_example_tpu as tps

    xt = np.random.default_rng(SEED).random(A.shape[0])
    bh = A @ xt
    tps.global_options().clear()
    tps.init(["chip_smoke", *options])
    try:
        t0 = time.perf_counter()
        M = tps.Mat.from_scipy(comm, A)
        ksp = tps.KSP().create(comm)
        ksp.set_operators(M)
        ksp.set_type(ksp_type)
        ksp.get_pc().set_type(pc_type)
        ksp.set_tolerances(rtol=rtol, max_it=50000)
        ksp.set_true_residual_check(True)
        ksp.set_from_options()
        x, b = M.get_vecs()
        b.set_global(bh)
        res = ksp.solve(b, x)
        xh = x.to_numpy()
        wall = time.perf_counter() - t0
    finally:
        tps.global_options().clear()
    check(res.converged, f"{ksp_type}+{pc_type} did not converge: {res}")
    rel = csr_relres(A, xh, bh)
    check(rel <= 1.05 * rtol,
          f"{ksp_type}+{pc_type}: host relres {rel:.3e} > 1.05*{rtol:g}")
    return res, ksp, x, rel, wall


# ----------------------------------------------------------------- phases
def run_driver(script: str) -> tuple[str, float]:
    """``tpurun -n 4 <script>`` in this process; returns its stdout."""
    tools = os.path.join(REPO, "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    import tpurun

    import mpi_petsc4py_example_tpu as tps

    out = io.StringIO()
    t0 = time.perf_counter()
    try:
        with contextlib.redirect_stdout(out):
            rc = tpurun.main(["-n", "4", os.path.join(REPO, script)])
    finally:
        tps.global_options().clear()
    wall = time.perf_counter() - t0
    check(rc == 0, f"{script} under tpurun -n 4 exited {rc}:\n"
          f"{out.getvalue()[-2000:]}")
    return out.getvalue(), wall


def phase_reference():
    from mpi_petsc4py_example_tpu.models import tridiag_family
    from mpi_petsc4py_example_tpu.solvers import pc as pcmod

    g0 = sum(pcmod.gate_fallbacks.values())
    text, wall = run_driver(os.path.join("examples", "solve_linear.py"))
    last = text.strip().splitlines()[-1] if text.strip() else ""
    check(last == "True", f"solve_linear.py printed {last!r}, not True")
    emit("reference", "solve_linear.py (test.py flow) tpurun -n 4",
         check="np.allclose True", ksp="preonly+lu(mumps)",
         wall_s=f"{wall:.2f}",
         pc_gate_fallbacks=sum(pcmod.gate_fallbacks.values()) - g0)

    text, wall = run_driver(os.path.join("examples", "eigensolve.py"))
    vals = [complex(ln.split(":", 1)[1].strip())
            for ln in text.splitlines() if ln.startswith("Eigenvalue:")]
    check(vals, f"eigensolve.py printed no eigenvalue:\n{text[-2000:]}")
    ref = np.linalg.eigvalsh(tridiag_family(100).toarray())
    ref = ref[np.argmax(np.abs(ref))]
    err = abs(vals[0].real - ref) / abs(ref)
    check(err <= 1e-8 and abs(vals[0].imag) <= 1e-8 * abs(ref),
          f"eigensolve.py eigenvalue {vals[0]} vs eigvalsh {ref}")
    emit("reference", "eigensolve.py (test2.py flow) tpurun -n 4",
         eigenvalue=f"{vals[0].real:.12g}", eigvalsh_relerr=fmt(err),
         wall_s=f"{wall:.2f}")


def phase_stencil(comm, nx: int = 256, rtol: float = 1e-6):
    import jax.numpy as jnp

    op, b = stencil_problem(comm, nx, jnp.float32)
    bh = None
    for pc_type in ("jacobi", "mg"):
        res, ksp, x, bh, rel, cold, warm = stencil_solve(
            comm, op, b, pc_type, rtol, bh)
        emit("stencil", f"CG+{pc_type} {nx}^3 fp32 rtol {rtol:g}",
             iters=res.iterations, relres=fmt(rel), cold_s=f"{cold:.3f}",
             warm_s=f"{warm:.3f}", path=pallas_path(comm, op, ksp, b, x))


def ilu0_blocks_numpy(A, r: np.ndarray, m: int, bs: int) -> np.ndarray:
    """``(LU)^-1 r`` for ILU(0) of the ``bs``-row diagonal blocks of the
    five-point matrix ``A`` (lines of ``m`` points), in natural order row
    by row and every block at once, in fp64 on the host: the pivots, then
    the forward and backward substitutions, from A's diagonals alone."""
    n = A.shape[0]
    nb = n // bs

    def diag(off):
        out = np.zeros(n)
        out[max(0, -off):n - max(0, off)] = A.diagonal(off)
        return out.reshape(nb, bs)

    s, w, a, e, nn = (diag(o) for o in (-m, -1, 0, 1, m))
    d = np.empty((nb, bs))
    for q in range(bs):
        v = a[:, q].copy()
        if q >= 1:
            v -= w[:, q] * e[:, q - 1] / d[:, q - 1]
        if q >= m:
            v -= s[:, q] * nn[:, q - m] / d[:, q - m]
        d[:, q] = v
    r = r.reshape(nb, bs)
    y = np.empty((nb, bs))
    for q in range(bs):
        v = r[:, q].copy()
        if q >= 1:
            v -= w[:, q] / d[:, q - 1] * y[:, q - 1]
        if q >= m:
            v -= s[:, q] / d[:, q - m] * y[:, q - m]
        y[:, q] = v
    z = np.empty((nb, bs))
    for q in reversed(range(bs)):
        v = y[:, q].copy()
        if q + 1 < bs:
            v -= e[:, q] * z[:, q + 1]
        if q + m < bs:
            v -= nn[:, q] * z[:, q + m]
        z[:, q] = v / d[:, q]
    return z.reshape(-1)


def ilu_check(comm, nx: int = 2048, reps: int = 20):
    """PC bjacobi's ILU(0) blocks on ``convdiff2d(nx)`` fp64: one seeded
    vector through the device apply against :func:`ilu0_blocks_numpy`,
    and the device apply's warm time."""
    import jax

    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import convdiff2d
    from mpi_petsc4py_example_tpu.solvers import bjilu

    A = convdiff2d(nx)
    M = tps.Mat.from_scipy(comm, A)
    pc = tps.PC(comm)
    pc.set_type("bjacobi")
    pc.set_up(M)
    check(pc.sub_solve == "ilu0", f"bjacobi took {pc.sub_solve!r} blocks")
    info = pc.setup_breakdown
    check(info["apply"] == "pallas",
          f"the ILU(0) blocks apply by {info['apply']!r}, not the kernel")
    bs = info["lines_per_block"] * nx
    r = np.random.default_rng(SEED).standard_normal(A.shape[0])
    apply = jax.jit(bjilu.apply)
    rd = jax.device_put(r, comm.row_sharding)
    z = np.asarray(apply(pc.device_arrays(), rd))
    t0 = time.perf_counter()
    for _ in range(reps):
        zd = apply(pc.device_arrays(), rd)
    zd.block_until_ready()
    apply_ms = 1e3 * (time.perf_counter() - t0) / reps
    zr = ilu0_blocks_numpy(A, r, nx, bs)
    err = float(np.max(np.abs(z - zr)) / np.max(np.abs(zr)))
    check(err <= 1e-12, f"ILU(0) apply differs from numpy by {err:.3e}")
    emit("assembled", f"ILU(0) blocks convdiff2d({nx}) fp64 apply",
         blocks=pc.sub_blocks, lines_per_block=info["lines_per_block"],
         max_rel_diff=fmt(err), apply_ms=f"{apply_ms:.3f}",
         setup=info)


def phase_assembled(comm, nx: int = 512, rtol: float = 1e-6):
    from mpi_petsc4py_example_tpu.models import convdiff2d, poisson2d_csr
    from mpi_petsc4py_example_tpu.solvers import pc as pcmod
    from mpi_petsc4py_example_tpu.utils import native

    A = convdiff2d(nx)
    res, ksp, x, rel, wall = assembled_solve(
        comm, A, "bcgs", "bjacobi", rtol)
    pc = ksp.get_pc()
    check(pc.sub_solve == "ilu0",
          f"bjacobi on convdiff2d({nx}) took {pc.sub_solve!r} blocks, "
          "not ILU(0)")
    emit("assembled", f"BCGS+bjacobi convdiff2d({nx}) fp64 rtol {rtol:g}",
         iters=res.iterations, relres=fmt(rel), wall_s=f"{wall:.2f}",
         sub_solve=pc.sub_solve, blocks=pc.sub_blocks,
         apply=pc.setup_breakdown["apply"], pc_setup=pc.setup_mode,
         native=native.status())

    g0 = pcmod.gate_fallbacks["block"]
    res, ksp, x, rel, wall = assembled_solve(
        comm, A, "bcgs", "bjacobi", rtol,
        options=["-pc_bjacobi_blocks", "128", "-pc_setup_device", "auto"])
    pc = ksp.get_pc()
    gate = pcmod.gate_fallbacks["block"] - g0
    check(pc.sub_solve == "dense",
          f"bjacobi with 128 blocks took {pc.sub_solve!r} blocks")
    check(pc.setup_mode == "device" or gate > 0,
          f"bjacobi setup ran on {pc.setup_mode!r} with no gate rejection")
    emit("assembled",
         f"BCGS+bjacobi(128 dense) convdiff2d({nx}) fp64 rtol {rtol:g}",
         iters=res.iterations, relres=fmt(rel), wall_s=f"{wall:.2f}",
         pc_setup=pc.setup_mode, gate_fallbacks=gate)
    ilu_check(comm)

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        res, ksp, x, rel, wall = assembled_solve(
            comm, poisson2d_csr(nx), "gmres", "jacobi", rtol,
            options=["-ksp_monitor"])
    lines = sum("KSP Residual norm" in ln for ln in out.getvalue()
                .splitlines())
    check(lines > 0, "-ksp_monitor printed no residual line")
    emit("assembled", f"GMRES+jacobi poisson2d({nx}) fp64 rtol {rtol:g}",
         iters=res.iterations, relres=fmt(rel), wall_s=f"{wall:.2f}",
         monitor=ksp._last_monitor_mode, monitor_lines=lines)


def phase_serving(comm, nx: int = 128, nreq: int = 16):
    import jax.numpy as jnp

    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.serving import SolveServer

    op, _ = stencil_problem(comm, nx, jnp.float32)
    rng = np.random.default_rng(SEED + 1)
    rtols = [(1e-4, 1e-5, 1e-6)[j % 3] for j in range(nreq)]
    # manufactured right-hand sides b_j = A x_j, rounded to fp32
    B = np.stack([stencil_apply(rng.random(op.shape[0]), nx, nx, nx)
                  for _ in range(nreq)], axis=1).astype(np.float32)
    for persistent in (False, True):
        srv = SolveServer(comm, window=0.01, max_k=8, autostart=False)
        # the session KSP reads the options DB at registration: each
        # request's own rtol is checked against its TRUE residual
        tps.init(["chip_smoke", "-ksp_true_residual_check"])
        try:
            srv.register_operator("stencil", op, pc_type="jacobi",
                                  rtol=1e-6, max_it=20000,
                                  persistent=persistent)
        finally:
            tps.global_options().clear()
        t0 = time.perf_counter()
        futs = [srv.submit("stencil", B[:, j], rtol=rtols[j])
                for j in range(nreq)]
        srv.start()
        res = [f.result(900) for f in futs]
        wall = time.perf_counter() - t0
        srv.shutdown(wait=True)
        check(all(f.done() for f in futs), "unresolved future after "
              "shutdown(wait=True)")
        worst = 0.0
        for j, r in enumerate(res):
            check(r.converged, f"request {j} did not converge: {r}")
            rel = stencil_relres(np.asarray(r.x), B[:, j], nx, nx, nx)
            check(rel <= 1.05 * rtols[j],
                  f"request {j}: relres {rel:.3e} > 1.05*{rtols[j]:g}")
            worst = max(worst, rel / rtols[j])
        st = srv.stats()
        extra = {}
        if persistent:
            ps = st["persistent"]["stencil"]
            check(ps["fallbacks"] == 0,
                  f"persistent session fell back: {ps}")
            extra = dict(launches=ps["launches"], fallbacks=ps["fallbacks"])
        emit("serving", f"{nreq} requests {nx}^3 fp32 "
             f"{'persistent' if persistent else 'per-batch'}",
             iters_max=max(r.iterations for r in res),
             worst_relres_over_rtol=fmt(worst), wall_s=f"{wall:.2f}",
             batches=st["batches"], **extra)


def phase_gates(comm):
    """What the chip shows for the capability gates, one line each. These
    are probes of what the runtime can do, not solves: a probe that
    raises is reported (``runs=False``) and the gate stays as it is."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.experimental import io_callback
    from jax.sharding import PartitionSpec as P

    def probe(case, fn):
        try:
            emit("gates", case, runs=True, **fn())
        # tpslint: disable=TPS005 — a probe's failure, whatever it
        # raises, IS the finding it reports
        except Exception as e:  # noqa: BLE001
            emit("gates", case, runs=False,
                 error=f"{type(e).__name__}: {str(e)[:200]}")

    def callback_in_shard_map():
        seen = []

        def body(v):
            def step(k, acc):
                io_callback(lambda k: seen.append(int(k)), None, k,
                            ordered=True)
                return acc + 1.0
            return lax.fori_loop(0, 5, step, v)

        prog = jax.jit(comm.shard_map(body, in_specs=(P(comm.axis),),
                                      out_specs=P(comm.axis)))
        jax.block_until_ready(prog(comm.put_rows(np.zeros(comm.size * 8))))
        jax.effects_barrier()
        return dict(calls=len(seen), expected=5 * comm.size,
                    in_order=seen == sorted(seen))

    rng = np.random.default_rng(SEED)

    def gram_f64():
        V = rng.standard_normal((5000, 64))
        G = np.asarray(jax.jit(lambda v: jnp.matmul(
            v.T, v, precision=lax.Precision.HIGHEST))(jnp.asarray(V)))
        Gh = V.T @ V
        return dict(relerr=fmt(float(np.max(np.abs(G - Gh))
                                     / np.max(np.abs(Gh)))))

    def eigh_c64():
        H = rng.standard_normal((64, 64)) + 1j * rng.standard_normal((64, 64))
        H = (H + H.conj().T).astype(np.complex64)
        w = np.asarray(jax.jit(jnp.linalg.eigvalsh)(jnp.asarray(H)))
        wh = np.linalg.eigvalsh(H.astype(np.complex128))
        return dict(relerr=fmt(float(np.max(np.abs(w - wh))
                                     / np.max(np.abs(wh)))))

    probe("io_callback ordered in shard_map (solvers/krylov.py "
          "live_monitor_supported)", callback_in_shard_map)
    probe("f64 Gram V^T V, V 5000x64 (solvers/eps.py "
          "_device_matmul_trustworthy)", gram_f64)
    probe("complex64 eigh 64x64 (solvers/eps.py _device_eigh_trustworthy)",
          eigh_c64)


def phase_sharded(nx: int = 512, conv_nx: int = 512, rtol: float = 1e-6):
    """``--chips 4``: the row-sharded stencil (ppermute halos, psum dots)
    and the assembled ELL (all_gather) on every chip, each compared with
    the same solve on one chip of this process: both meet rtol on the
    host, and the iteration counts agree (CG ±2; BCGS 2%)."""
    import jax
    import jax.numpy as jnp

    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import convdiff2d

    devs = jax.devices()
    comms = {len(devs): tps.DeviceComm(devices=devs),
             1: tps.DeviceComm(devices=devs[:1])}
    iters = {}
    bh = None
    for ndev, comm in comms.items():
        op, b = stencil_problem(comm, nx, jnp.float32)
        res, _, x, bh, rel, cold, warm = stencil_solve(
            comm, op, b, "jacobi", rtol, bh)
        ids = sorted({s.device.id for s in x.data.addressable_shards})
        iters[ndev] = res.iterations
        emit("sharded", f"CG+jacobi {nx}^3 fp32 on {ndev} chip(s)",
             iters=res.iterations, relres=fmt(rel), cold_s=f"{cold:.3f}",
             warm_s=f"{warm:.3f}", shard_devices=ids)
        if ndev > 1:
            check(len(ids) == ndev, f"solution on devices {ids}, not "
                  f"{ndev} distinct chips")
        del op, b, x
    check(abs(iters[len(devs)] - iters[1]) <= 2,
          f"stencil iterations differ: {iters}")

    A = convdiff2d(conv_nx)
    iters = {}
    for ndev, comm in comms.items():
        res, _, x, rel, wall = assembled_solve(comm, A, "bcgs", "jacobi",
                                               rtol)
        ids = sorted({s.device.id for s in x.data.addressable_shards})
        iters[ndev] = res.iterations
        emit("sharded", f"BCGS+jacobi convdiff2d({conv_nx}) fp64 on "
             f"{ndev} chip(s)", iters=res.iterations, relres=fmt(rel),
             wall_s=f"{wall:.2f}", shard_devices=ids)
    # BiCGStab's iteration count follows the psum summation order: in
    # exact f64 on the CPU mesh this solve takes 848 iterations on 4
    # devices and 855 on 1 (PR 21), so it is held to 2%, not CG's ±2
    slack = max(2, int(0.02 * iters[1]))
    check(abs(iters[len(devs)] - iters[1]) <= slack,
          f"assembled iterations differ by more than {slack}: {iters}")


# ------------------------------------------------------------------- main
def tpu_devices(count: int):
    """The TPU devices of this process; raises unless there are exactly
    ``count`` of them (never a CPU fallback)."""
    import jax

    devs = jax.devices()
    check(devs[0].platform == "tpu",
          f"no TPU: jax platform is {devs[0].platform!r}")
    check(len(devs) == count, f"{len(devs)} TPU devices, expected {count}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded path and its 1-chip "
                         "comparison")
    opts = ap.parse_args(argv)
    sys.path.insert(0, REPO)
    try:
        import mpi_petsc4py_example_tpu as tps
    except ImportError as e:
        print(f"chip_smoke: the package is not here ({e})", file=sys.stderr)
        return 2
    t0 = time.perf_counter()
    try:
        devs = tpu_devices(opts.chips)
        print(f"device: {devs[0].device_kind} x{len(devs)}, compile cache "
              f"{tps.compile_cache_dir()}", flush=True)
        if opts.chips == 4:
            phase_sharded()
        else:
            comm = tps.DeviceComm(devices=devs[:1])
            tps.set_default_comm(comm)
            phase_reference()
            phase_stencil(comm)
            phase_assembled(comm)
            phase_serving(comm)
            phase_gates(comm)
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
