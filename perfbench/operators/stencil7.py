"""Operator kind ``stencil7``: the matrix-free 7-point Dirichlet Poisson
stencil of ``models.StencilPoisson3D``, built on the cell's chips.

The runner calls, by this file's name in the configuration's ``operator``:

- ``build(cfg, comm)``: the system under test's operator;
- ``info(cfg)``: sizes for the bytes models (``perfbench/bytes``);
- ``rhs_maker(cfg, comm)``: one jitted program ``make(key, i)`` that
  draws right-hand side ``i`` of the seed's pool on the device;
- ``zeros_maker(cfg, comm)``: one jitted program for ``x0 = 0``.

The right-hand sides are the benchmark's own: ``b = A x_true`` with
``x_true`` uniform in ``[0, 1)``, applied in fp32 by the plain stencil
below, not by the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P


def grid(cfg) -> tuple[int, int, int]:
    return int(cfg["nz"]), int(cfg["ny"]), int(cfg["nx"])


def build(cfg, comm):
    from mpi_petsc4py_example_tpu.models import StencilPoisson3D
    nz, ny, nx = grid(cfg)
    return StencilPoisson3D(comm, nx, ny, nz, dtype=jnp.dtype(cfg["dtype"]))


def info(cfg) -> dict:
    """What the bytes models need: the unknowns, the bytes of one value,
    the matrix bytes one apply reads (none: matrix-free) and the bytes of
    a stored diagonal (none: the diagonal is the constant 6)."""
    nz, ny, nx = grid(cfg)
    return {"grid": (nz, ny, nx), "n": nz * ny * nx,
            "itemsize": jnp.dtype(cfg["dtype"]).itemsize,
            "chips": int(cfg["chips"]),
            "matrix_bytes_per_apply": 0, "diagonal_bytes": 0}


def apply_fp32(u):
    """``A u`` of the unit 7-point Dirichlet stencil on a (nz, ny, nx)
    array, in the array's dtype (the benchmark's own, for the RHS)."""
    def shifted(ax, lo):
        n = u.shape[ax]
        part = jax.lax.slice_in_dim(u, 0, n - 1, axis=ax) if lo else \
            jax.lax.slice_in_dim(u, 1, n, axis=ax)
        pad = [(0, 0)] * 3
        pad[ax] = (1, 0) if lo else (0, 1)
        return jnp.pad(part, pad)
    y = 6.0 * u
    for ax in range(3):
        y = y - shifted(ax, True) - shifted(ax, False)
    return y


def rhs_maker(cfg, comm):
    nz, ny, nx = grid(cfg)
    dt = jnp.dtype(cfg["dtype"])
    slab = NamedSharding(comm.mesh, P(comm.axis))

    @jax.jit
    def make(key, i):
        u = jax.random.uniform(jax.random.fold_in(key, i), (nz, ny, nx),
                               jnp.float32)
        u = jax.lax.with_sharding_constraint(u, slab)
        b = apply_fp32(u).astype(dt).reshape(-1)
        return jax.lax.with_sharding_constraint(b, comm.row_sharding)

    return make


def zeros_maker(cfg, comm):
    nz, ny, nx = grid(cfg)
    dt = jnp.dtype(cfg["dtype"])
    return jax.jit(lambda: jnp.zeros((nz * ny * nx,), dt),
                   out_shardings=comm.row_sharding)
