"""Plain reference of operator kind ``convdiff5``: the 2D five-point
convection-diffusion matrix (diagonal 4, west -1-beta, east -1+beta,
south and north -1, Dirichlet, x-fastest ordering).

``relres`` is the comparison that decides ``correct``: the relative
residual ``||b - A x|| / ||b||`` in fp64 on the host, in blocks of grid
lines, so that a large grid needs no fp64 copy of itself beyond its own.

``solve`` is the reference put in the program's place for the control:
a textbook right-preconditioned BiCGStab with the point-Jacobi
preconditioner ``z = r / 4`` in plain ``jax.numpy`` and in one stated
dtype (the control runs it one precision below the configuration's). Its
inner products are a product and a sum: XLA:TPU splits an fp64 ``vdot``
into float32 pieces in loops, 200 times slower at 2048^2. It imports
nothing of the program.

Both take the configuration, as every operator kind's reference does.
"""

from __future__ import annotations

import concurrent.futures as cf
import os

import numpy as np

BLOCK_LINES = 128


def _grid(cfg):
    return int(cfg["ny"]), int(cfg["nx"])


def _block_sums(x2, b2, y0, y1, beta):
    """``(sum (b - A x)^2, sum b^2)`` over lines ``[y0, y1)`` in fp64."""
    ny = x2.shape[0]
    lo, hi = max(y0 - 1, 0), min(y1 + 1, ny)
    u = x2[lo:hi].astype(np.float64)
    c0 = y0 - lo                        # index of line y0 inside u
    c = u[c0:c0 + (y1 - y0)]
    y = 4.0 * c
    y[:, 1:] -= (1.0 + beta) * c[:, :-1]
    y[:, :-1] -= (1.0 - beta) * c[:, 1:]
    y[1:] -= c[:-1]
    y[:-1] -= c[1:]
    if y0 > 0:
        y[0] -= u[c0 - 1]
    if y1 < ny:
        y[-1] -= u[c0 + (y1 - y0)]
    bb = b2[y0:y1].astype(np.float64)
    r = bb - y
    return float(np.vdot(r, r)), float(np.vdot(bb, bb))


def relres(x, b, cfg, threads: int | None = None) -> float:
    """``||b - A x|| / ||b||`` in fp64 for host arrays ``x``, ``b`` of the
    configuration's grid."""
    ny, nx = _grid(cfg)
    beta = float(cfg["beta"])
    x2 = np.asarray(x).reshape(ny, nx)
    b2 = np.asarray(b).reshape(ny, nx)
    blocks = [(y, min(y + BLOCK_LINES, ny))
              for y in range(0, ny, BLOCK_LINES)]
    threads = threads or min(16, os.cpu_count() or 1)
    with cf.ThreadPoolExecutor(threads) as pool:
        sums = list(pool.map(lambda yy: _block_sums(x2, b2, *yy, beta),
                             blocks))
    rr = sum(s[0] for s in sums)
    bb = sum(s[1] for s in sums)
    return float(np.sqrt(rr / bb))


def solve(b, cfg, rtol: float, max_it: int, dtype):
    """Reference BiCGStab + point Jacobi on the device in ``dtype``;
    returns ``(x, iterations)`` with ``x`` flat in ``dtype``."""
    import jax
    import jax.numpy as jnp

    ny, nx = _grid(cfg)
    dt = jnp.dtype(dtype)
    beta = float(cfg["beta"])

    def apply(u):
        zc = jnp.zeros_like(u[:, :1])
        zr = jnp.zeros_like(u[:1])
        west = jnp.concatenate([zc, u[:, :-1]], axis=1)
        east = jnp.concatenate([u[:, 1:], zc], axis=1)
        south = jnp.concatenate([zr, u[:-1]], axis=0)
        north = jnp.concatenate([u[1:], zr], axis=0)
        return (jnp.asarray(4.0, dt) * u
                - jnp.asarray(1.0 + beta, dt) * west
                - jnp.asarray(1.0 - beta, dt) * east - south - north)

    def dot(u, v):
        return jnp.sum(u * v)

    @jax.jit
    def run(b):
        b2 = b.reshape(ny, nx).astype(dt)
        quarter = jnp.asarray(0.25, dt)
        x = jnp.zeros_like(b2)
        r = b2
        rhat = r
        target = jnp.asarray(rtol, dt) * jnp.linalg.norm(b2)
        one = jnp.asarray(1.0, dt)
        zero = jnp.zeros_like(b2)

        def cond(s):
            k, x, r, p, v, rho, alpha, omega = s
            return (k < max_it) & (jnp.linalg.norm(r) > target)

        def body(s):
            k, x, r, p, v, rho, alpha, omega = s
            rho_new = dot(rhat, r)
            beta_ = (rho_new / rho) * (alpha / omega)
            p = r + beta_ * (p - omega * v)
            phat = quarter * p
            v = apply(phat)
            alpha = rho_new / dot(rhat, v)
            s_ = r - alpha * v
            shat = quarter * s_
            t = apply(shat)
            omega = dot(t, s_) / dot(t, t)
            x = x + alpha * phat + omega * shat
            r = s_ - omega * t
            return k + 1, x, r, p, v, rho_new, alpha, omega

        k, x, *_ = jax.lax.while_loop(
            cond, body, (0, x, r, zero, zero, one, one, one))
        return x.reshape(-1), k

    x, k = run(b)
    return x, int(k)
