"""Operator kind ``convdiff5``: the assembled 2D five-point
convection-diffusion matrix of ``models.convdiff2d``, built through the
normal path, ``Mat.from_scipy`` (host CSR to DIA, one device placement).

The runner calls, by this file's name in the configuration's ``operator``:

- ``build(cfg, comm)``: the system under test's operator;
- ``info(cfg)``: sizes for the bytes models (``perfbench/bytes``);
- ``rhs_maker(cfg, comm)``: one jitted program ``make(key, i)`` that
  draws right-hand side ``i`` of the seed's pool on the device;
- ``zeros_maker(cfg, comm)``: one jitted program for ``x0 = 0``.

The right-hand sides are the benchmark's own: ``b = A x_true`` with
``x_true`` uniform in ``[0, 1)``, applied in fp64 by the plain five-point
apply below, not by the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

DIAGONALS = 5


def grid(cfg) -> tuple[int, int]:
    return int(cfg["ny"]), int(cfg["nx"])


def build(cfg, comm):
    from mpi_petsc4py_example_tpu import Mat
    from mpi_petsc4py_example_tpu.models import convdiff2d
    ny, nx = grid(cfg)
    return Mat.from_scipy(comm, convdiff2d(nx, ny, beta=float(cfg["beta"])),
                          jnp.dtype(cfg["dtype"]))


def info(cfg) -> dict:
    """What the bytes models need: the unknowns, the bytes of one value,
    the matrix bytes one apply reads (the five stored diagonals; DIA
    offsets are static), no stored diagonal apart from those, and the
    bytes of the block-Jacobi ILU(0) factors beyond A's own entries: the
    two L multipliers and the inverse pivot of every row."""
    ny, nx = grid(cfg)
    n = ny * nx
    item = jnp.dtype(cfg["dtype"]).itemsize
    return {"grid": (ny, nx), "n": n, "itemsize": item,
            "chips": int(cfg["chips"]),
            "matrix_bytes_per_apply": DIAGONALS * n * item,
            "diagonal_bytes": 0, "pc_factor_bytes": 3 * n * item}


def apply_fp64(u, beta: float):
    """``A u`` of ``models.convdiff2d`` on a (ny, nx) array: diagonal 4,
    west -1-beta, east -1+beta, south and north -1, Dirichlet."""
    zc = jnp.zeros_like(u[:, :1])
    zr = jnp.zeros_like(u[:1])
    west = jnp.concatenate([zc, u[:, :-1]], axis=1)
    east = jnp.concatenate([u[:, 1:], zc], axis=1)
    south = jnp.concatenate([zr, u[:-1]], axis=0)
    north = jnp.concatenate([u[1:], zr], axis=0)
    return (4.0 * u - (1.0 + beta) * west - (1.0 - beta) * east
            - south - north)


def rhs_maker(cfg, comm):
    ny, nx = grid(cfg)
    dt = jnp.dtype(cfg["dtype"])
    beta = float(cfg["beta"])
    lines = NamedSharding(comm.mesh, P(comm.axis))

    @jax.jit
    def make(key, i):
        u = jax.random.uniform(jax.random.fold_in(key, i), (ny, nx),
                               jnp.float64)
        u = jax.lax.with_sharding_constraint(u, lines)
        b = apply_fp64(u, beta).astype(dt).reshape(-1)
        return jax.lax.with_sharding_constraint(b, comm.row_sharding)

    return make


def zeros_maker(cfg, comm):
    ny, nx = grid(cfg)
    dt = jnp.dtype(cfg["dtype"])
    return jax.jit(lambda: jnp.zeros((ny * nx,), dt),
                   out_shardings=comm.row_sharding)
