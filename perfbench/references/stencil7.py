"""Plain reference of operator kind ``stencil7``: the 7-point Dirichlet
Poisson stencil (diagonal 6, off-diagonals -1, x-fastest ordering).

``relres`` is the comparison that decides ``correct``: the relative
residual ``||b - A x|| / ||b||`` in fp64 on the host, in z-blocks so that
a large grid needs no fp64 copy of itself (copied from chip_smoke.py's
``stencil_apply``/``stencil_relres``, PR 21).

``solve`` is the reference put in the program's place for the control:
a textbook CG with the Jacobi preconditioner ``z = r / 6`` in plain
``jax.numpy`` and in one stated dtype (the control runs it one precision
below the configuration's). It imports nothing of the program.

Both take the configuration, as every operator kind's reference does.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np

BLOCK_PLANES = 32


def _block_sums(x3, b3, z0, z1):
    """``(sum (b - A x)^2, sum b^2)`` over planes ``[z0, z1)`` in fp64."""
    nz = x3.shape[0]
    lo, hi = max(z0 - 1, 0), min(z1 + 1, nz)
    u = x3[lo:hi].astype(np.float64)
    c0 = z0 - lo                        # index of plane z0 inside u
    m = z1 - z0
    c = u[c0:c0 + m]
    y = 6.0 * c
    if z0 > 0:
        y[0] -= u[c0 - 1]
    y[1:] -= c[:-1]
    y[:-1] -= c[1:]
    if z1 < nz:
        y[-1] -= u[c0 + m]
    y[:, 1:] -= c[:, :-1]
    y[:, :-1] -= c[:, 1:]
    y[:, :, 1:] -= c[:, :, :-1]
    y[:, :, :-1] -= c[:, :, 1:]
    bb = b3[z0:z1].astype(np.float64)
    r = bb - y
    return float(np.vdot(r, r)), float(np.vdot(bb, bb))


def _grid(cfg):
    return int(cfg["nz"]), int(cfg["ny"]), int(cfg["nx"])


def relres(x, b, cfg, threads: int | None = None) -> float:
    """``||b - A x|| / ||b||`` in fp64 for host arrays ``x``, ``b`` of the
    configuration's grid."""
    nz, ny, nx = _grid(cfg)
    x3 = np.asarray(x).reshape(nz, ny, nx)
    b3 = np.asarray(b).reshape(nz, ny, nx)
    blocks = [(z, min(z + BLOCK_PLANES, nz))
              for z in range(0, nz, BLOCK_PLANES)]
    threads = threads or min(16, os.cpu_count() or 1)
    with cf.ThreadPoolExecutor(threads) as pool:
        sums = list(pool.map(lambda zz: _block_sums(x3, b3, *zz), blocks))
    rr = sum(s[0] for s in sums)
    bb = sum(s[1] for s in sums)
    return float(np.sqrt(rr / bb))


def solve(b, cfg, rtol: float, max_it: int, dtype):
    """Reference CG + Jacobi on the device in ``dtype``; returns
    ``(x, iterations)`` with ``x`` flat in ``dtype``. ``b`` keeps its
    sharding (XLA partitions the shifted slices into halo exchanges)."""
    import jax
    import jax.numpy as jnp

    nz, ny, nx = _grid(cfg)
    dt = jnp.dtype(dtype)

    def apply(u):
        def shifted(ax, lo):
            n = u.shape[ax]
            part = (jax.lax.slice_in_dim(u, 0, n - 1, axis=ax) if lo else
                    jax.lax.slice_in_dim(u, 1, n, axis=ax))
            pad = [(0, 0)] * 3
            pad[ax] = (1, 0) if lo else (0, 1)
            return jnp.pad(part, pad)
        y = jnp.asarray(6.0, dt) * u
        for ax in range(3):
            y = y - shifted(ax, True) - shifted(ax, False)
        return y

    @jax.jit
    def run(b):
        b3 = b.reshape(nz, ny, nx).astype(dt)
        sixth = jnp.asarray(1.0 / 6.0, dt)
        x = jnp.zeros_like(b3)
        r = b3
        z = sixth * r
        p = z
        rz = jnp.vdot(r, z)
        target = jnp.asarray(rtol, jnp.float32) * jnp.linalg.norm(
            b3.astype(jnp.float32))

        def cond(s):
            k, x, r, p, rz = s
            return (k < max_it) & (jnp.linalg.norm(r.astype(jnp.float32))
                                   > target)

        def body(s):
            k, x, r, p, rz = s
            q = apply(p)
            alpha = rz / jnp.vdot(p, q)
            x = x + alpha * p
            r = r - alpha * q
            z = sixth * r
            rz_new = jnp.vdot(r, z)
            p = z + (rz_new / rz) * p
            return k + 1, x, r, p, rz_new

        k, x, *_ = jax.lax.while_loop(cond, body, (0, x, r, p, rz))
        return x.reshape(-1), k

    x, k = run(b)
    return x, int(k)
