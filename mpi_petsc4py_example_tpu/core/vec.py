"""Distributed vector: a row-sharded ``jax.Array`` in HBM.

TPU-native equivalent of PETSc ``Vec`` (MPI) — reference usage:
``b.setArray(local_rhs)`` sets the local block and ``x.array`` reads it
(``test.py:30``, ``test.py:145``). Here the storage is one global array with a
``NamedSharding`` over the row axis; the user-visible (possibly uneven,
PETSc-style) ownership ranges live in a :class:`RowLayout` so local-block
views match the reference partition exactly even though the internal device
layout is uniform-padded.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..parallel.mesh import DeviceComm, as_comm
from ..parallel.partition import RowLayout


class Vec:
    """Row-sharded distributed vector of logical length ``n``.

    Internally stores a zero-padded array of length ``comm.padded_size(n)``
    sharded over the mesh. All solver arithmetic happens on the raw padded
    array (``.data``); the class provides the PETSc-``Vec``-shaped views.
    """

    def __init__(self, comm, n: int, data: jax.Array | None = None,
                 dtype=jnp.float64, layout: RowLayout | None = None):
        self.comm: DeviceComm = as_comm(comm)
        self.n = int(n)
        self.layout = layout or RowLayout(self.n, self.comm.size)
        if data is None:
            n_pad = self.comm.padded_size(self.n)
            data = self.comm.put_rows(np.zeros(n_pad, dtype=dtype))
        self.data = data

    # ---- construction ------------------------------------------------------
    @classmethod
    def from_global(cls, comm, arr, dtype=None, layout=None) -> "Vec":
        comm = as_comm(comm)
        arr = np.asarray(arr)
        if dtype is not None:
            arr = arr.astype(dtype)
        v = cls(comm, arr.shape[0], data=comm.put_rows(arr), dtype=arr.dtype,
                layout=layout)
        return v

    def duplicate(self) -> "Vec":
        return Vec(self.comm, self.n, data=jnp.zeros_like(self.data),
                   layout=self.layout)

    def copy(self) -> "Vec":
        return Vec(self.comm, self.n, data=self.data, layout=self.layout)

    @property
    def dtype(self):
        return self.data.dtype

    # ---- PETSc-shaped local views ------------------------------------------
    def set_array(self, local, rank: int = 0):
        """Set this rank's local block (the reference's ``b.setArray``).

        In single-controller mode the caller usually owns the whole vector
        (``rank 0`` of a 1-rank run); pass ``rank`` to set another block.
        """
        local = np.asarray(local)
        rs, re = self.layout.range(rank)
        if local.shape[0] == self.n and rs == 0 and re == self.n:
            self.data = self.comm.put_rows(local.astype(self.data.dtype))
            return
        if local.shape[0] != re - rs:
            raise ValueError(
                f"local block for rank {rank} must have length {re - rs}, "
                f"got {local.shape[0]}")
        host = self.to_numpy()
        host[rs:re] = local
        self.data = self.comm.put_rows(host.astype(self.data.dtype))

    def set_global(self, arr):
        self.data = self.comm.put_rows(np.asarray(arr, dtype=self.data.dtype))

    def local_array(self, rank: int = 0) -> np.ndarray:
        """This rank's local block (the reference's ``x.array``)."""
        rs, re = self.layout.range(rank)
        return self.to_numpy()[rs:re]

    @property
    def array(self) -> np.ndarray:
        return self.local_array(0)

    def to_numpy(self) -> np.ndarray:
        """Gather to host, dropping padding — a counts-correct ``Gatherv``
        (multi-process meshes gather the remote shards over DCN)."""
        return self.comm.host_fetch(self.data)[: self.n].copy()

    # ---- vector arithmetic (petsc4py-Vec-shaped; solvers use raw arrays) ---
    def norm(self, norm_type: str = "2") -> float:
        """Vector norm: '2' (default, PETSc NORM_2), '1', or 'inf'.

        Padding entries are zero by construction, so device-side reductions
        over the padded array are exact for all three norms."""
        t = str(norm_type).lower()
        if t in ("2", "fro", "frobenius"):
            return float(jnp.linalg.norm(self.data))
        if t in ("1", "one"):
            return float(jnp.sum(jnp.abs(self.data)))
        if t in ("inf", "infinity"):
            return float(jnp.max(jnp.abs(self.data)))
        raise ValueError(f"unknown norm type {norm_type!r}")

    def dot(self, other: "Vec"):
        """PETSc VecDot(self, other) = otherᴴ · self — conjugates the
        SECOND argument for complex dtypes (petsc4py parity; note numpy's
        ``np.vdot(u, v)`` conjugates the first, i.e. equals ``v.dot(u)``
        here)."""
        from ..utils.dtypes import is_complex
        v = jnp.vdot(other.data, self.data)
        if is_complex(self.dtype):
            return complex(v)
        return float(v)

    def axpy(self, alpha: float, other: "Vec"):
        """self += alpha * other."""
        self.data = _axpy(jnp.asarray(alpha, self.dtype), other.data,
                          self.data)
        return self

    def aypx(self, alpha: float, other: "Vec"):
        """self = alpha * self + other."""
        self.data = _axpy(jnp.asarray(alpha, self.dtype), self.data,
                          other.data)
        return self

    def scale(self, alpha: float):
        self.data = _scale(jnp.asarray(alpha, self.dtype), self.data)
        return self

    def shift(self, alpha: float):
        """self += alpha on the logical entries (padding stays zero)."""
        host = self.to_numpy() + alpha
        self.data = self.comm.put_rows(host.astype(self.data.dtype))
        return self

    def pointwise_mult(self, a: "Vec", b: "Vec"):
        self.data = _pmult(a.data, b.data)
        return self

    def sum(self) -> float:
        return float(jnp.sum(self.data))

    def mean(self) -> float:
        return float(jnp.sum(self.data)) / self.n

    def min(self) -> tuple[int, float]:
        """(location, value) of the minimum — petsc4py's ``vec.min()``."""
        h = self.to_numpy()
        i = int(np.argmin(h))
        return i, float(h[i])

    def max(self) -> tuple[int, float]:
        """(location, value) of the maximum — petsc4py's ``vec.max()``."""
        h = self.to_numpy()
        i = int(np.argmax(h))
        return i, float(h[i])

    def waxpy(self, alpha: float, x: "Vec", y: "Vec"):
        """self = alpha*x + y (PETSc VecWAXPY)."""
        self.data = _axpy(jnp.asarray(alpha, self.dtype), x.data, y.data)
        return self

    def axpby(self, alpha: float, beta: float, x: "Vec"):
        """self = alpha*x + beta*self (PETSc VecAXPBY)."""
        self.data = _axpby(jnp.asarray(alpha, self.dtype),
                           jnp.asarray(beta, self.dtype), x.data, self.data)
        return self

    def pointwise_divide(self, a: "Vec", b: "Vec"):
        """self = a / b elementwise; 0/0 on padding stays 0."""
        self.data = _pdiv(a.data, b.data)
        return self

    def reciprocal(self):
        """self = 1/self on nonzero entries (PETSc VecReciprocal; padding
        and exact zeros stay zero, matching the Jacobi-diagonal convention)."""
        self.data = _precip(self.data)
        return self

    def normalize(self) -> float:
        """Scale to unit 2-norm; returns the prior norm."""
        nrm = self.norm()
        if nrm != 0:
            self.scale(1.0 / nrm)
        return nrm

    def set_value(self, i: int, v: float):
        """Point insert by global index (assembly-time convenience)."""
        h = self.to_numpy()
        h[i] = v
        self.set_global(h)
        return self

    setValue = set_value

    def set(self, alpha: float):
        """self[:] = alpha (PETSc VecSet)."""
        self.set_global(np.full(self.n, alpha))
        return self

    def zero(self):
        # on-device zeros: a host buffer + device_put would ship O(n) bytes
        # host->device per call; jnp.zeros_like dispatches a tiny cached
        # program and preserves the sharding
        self.data = jnp.zeros_like(self.data)

    def __len__(self):
        return self.n


@jax.jit
def _axpy(alpha, x, y):
    return y + alpha * x


@jax.jit
def _scale(alpha, x):
    return alpha * x


@jax.jit
def _pmult(a, b):
    return a * b


@jax.jit
def _axpby(alpha, beta, x, y):
    return alpha * x + beta * y


@jax.jit
def _pdiv(a, b):
    return jnp.where(b == 0, 0.0, a / jnp.where(b == 0, 1.0, b))


@jax.jit
def _precip(x):
    return jnp.where(x == 0, 0.0, 1.0 / jnp.where(x == 0, 1.0, x))
