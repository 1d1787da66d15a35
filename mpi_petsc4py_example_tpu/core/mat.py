"""Distributed sparse matrix: row-sharded ELL/CSR in HBM.

TPU-native equivalent of PETSc ``Mat`` (MPIAIJ) — SURVEY.md N1. The reference
constructs it from the contract *(comm, global shape, local rebased-CSR with
global column indices)* (``petsc_funcs.py:5-10``, ``test.py:24``); the
constructors here accept exactly that, plus a whole-matrix convenience path.

Storage: the device layout is ELL (see ops/spmv.py) with rows 1-D sharded
over the mesh — one shard per device, padding rows empty. A host-side scipy
CSR copy is retained when available for preconditioner factorizations
(block-Jacobi / LU) and oracle checks.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.spmv import (accum_dtype as _accum, csr_diag,
                        csr_find_diagonals, csr_to_dia, csr_to_ell,
                        dia_spmv_local, dia_spmv_local_many,
                        ell_spmv_local, ell_spmv_local_many)
from ..parallel.mesh import DeviceComm, as_comm
from ..parallel.partition import RowLayout, concat_csr_blocks
from .vec import Vec


class Mat:
    """Row-sharded distributed sparse matrix (AIJ-equivalent)."""

    def __init__(self, comm, shape, ell_cols: jax.Array, ell_vals: jax.Array,
                 host_csr=None, layout: RowLayout | None = None):
        self.comm: DeviceComm = as_comm(comm)
        self.shape = (int(shape[0]), int(shape[1]))
        self.layout = layout or RowLayout(self.shape[0], self.comm.size)
        # (n_pad, K) arrays sharded on axis 0.
        self.ell_cols = ell_cols
        self.ell_vals = ell_vals
        # optional host CSR triple (indptr, indices, data) of the full matrix
        self.host_csr = host_csr
        self._assembled = False
        # bumped by every in-place mutation (axpy/scale/shift/zero_rows) so
        # PC/EPS setup caches keyed on this Mat know to rebuild
        self._state = 0
        # constant-diagonal fast path (set by model generators so Jacobi
        # setup never pulls a 100M-row ELL back to host)
        self._diag_value: float | None = None
        # DIA fast path for banded matrices: (n_pad, D) values + static
        # offsets; SpMV becomes shifted slices instead of a gather
        self.dia_vals: jax.Array | None = None
        self.dia_offsets: tuple[int, ...] = ()

    # ---- constructors ------------------------------------------------------
    @classmethod
    def create_aij(cls, comm, size, csr, dtype=jnp.float64) -> "Mat":
        """The reference contract: global ``size``, *local* rebased CSR.

        In single-controller mode the caller's "local" block is the whole
        matrix when its indptr covers all rows (the ``mpirun -n 1`` path the
        reference supports, ``test.py:77`` empty loop). For true per-rank
        blocks, assemble with :meth:`from_local_blocks`.
        """
        nrows, ncols = size
        indptr, indices, data = csr
        local_rows = len(indptr) - 1
        if local_rows == nrows:
            return cls.from_csr(comm, size, csr, dtype=dtype)
        raise ValueError(
            f"local CSR has {local_rows} rows but global shape is {size}; "
            "assemble per-rank blocks with Mat.from_local_blocks")

    @classmethod
    def from_csr(cls, comm, size, csr, dtype=jnp.float64) -> "Mat":
        """Build from a *global* host CSR triple.

        Validation and the CSR->ELL layout conversion run through the native
        C++ toolkit (native/csrkit.cpp) when available — the role PETSc's C
        MatAssembly plays — with a vectorized-numpy fallback.

        Round 6 (the cfg4 assembly fix): ALL host-side layout work —
        ELL conversion and the DIA detect/convert — runs first, then every
        device array ships in ONE batched placement
        (:meth:`DeviceComm.put_rows_many`), so the runtime's fixed
        per-transfer dispatch cost is paid once, not once per array; the
        placement is synced (``block_until_ready``) before its stamp so
        ``assembly_breakdown`` attributes real time, not async-dispatch
        slack spilled into whatever the caller times next.
        """
        from ..telemetry import spans as _telemetry
        comm = as_comm(comm)
        with _telemetry.span("mat.assemble", rows=int(size[0])) as sp:
            m = cls._from_csr(comm, size, csr, dtype)
            sp.set_attr("format", "dia" if m.dia_vals is not None
                        else "ell")
            return m

    @classmethod
    def _from_csr(cls, comm, size, csr, dtype) -> "Mat":
        """The body of :meth:`from_csr`, inside its ``mat.assemble`` span."""
        import time as _time

        from ..utils import native
        nrows, ncols = int(size[0]), int(size[1])
        t0 = _time.perf_counter()
        indptr = np.asarray(csr[0], dtype=np.int64)
        indices = np.asarray(csr[1], dtype=np.int32)
        data = np.asarray(csr[2], dtype=dtype)
        err = native.csr_validate(indptr, indices, ncols)
        if err != 0:
            reasons = {-1: "indptr[0] != 0", -2: "indptr not monotone",
                       -3: "indptr[-1] != nnz", -4: "column index out of range"}
            raise ValueError(f"malformed CSR: {reasons.get(err, err)}")
        t1 = _time.perf_counter()
        # the native C++ conversion handles the machine float families
        # only; ml_dtypes storage (bfloat16, numpy kind 'V') takes the
        # vectorized-numpy path, which is dtype-agnostic
        if (native.available() and len(data) > 1_000_000
                and data.dtype.kind in "fc"):
            cols, vals = native.csr_to_ell_native(indptr, indices, data)
            vals = vals.astype(dtype, copy=False)
        else:
            cols, vals = csr_to_ell(indptr, indices, data)
        K = cols.shape[1]
        t2 = _time.perf_counter()
        # auto-select the DIA layout for banded square matrices: same-order
        # storage as ELL but gather-free SpMV (shifted slices)
        offsets, dia = None, None
        if nrows == ncols:
            offsets = csr_find_diagonals(indptr, indices,
                                         max_diags=max(2 * K, 8))
            # an empty offsets set (all-zero matrix) stays on the ELL path —
            # the DIA kernels assume at least one stored diagonal
            if offsets is not None and 0 < len(offsets) <= max(2 * K, 8):
                dia = csr_to_dia(indptr, indices, data, nrows, offsets)
            else:
                offsets = None
        t3 = _time.perf_counter()
        placed = comm.put_rows_many(
            [cols, vals] + ([dia] if dia is not None else []))
        import jax as _jax
        _jax.block_until_ready(placed)
        t4 = _time.perf_counter()
        m = cls(comm, (nrows, ncols), placed[0], placed[1],
                host_csr=(indptr, indices, data))
        if dia is not None:
            m.dia_vals = placed[2]
            m.dia_offsets = tuple(int(o) for o in offsets)
        m._assembled = True
        # where MatAssembly time goes (BASELINE cfg1/cfg4 ask): validate /
        # ELL conversion / DIA detect+convert / the one synced placement
        m.assembly_breakdown = {
            "validate_s": round(t1 - t0, 4),
            "ell_convert_s": round(t2 - t1, 4),
            "dia_convert_s": round(t3 - t2, 4),
            "device_put_s": round(t4 - t3, 4),
        }
        return m

    @classmethod
    def from_local_blocks(cls, comm, size, blocks, dtype=jnp.float64) -> "Mat":
        """Build from per-rank local CSR blocks (the reference's L5 output)."""
        indptr, indices, data = concat_csr_blocks(blocks)
        return cls.from_csr(comm, size, (indptr, indices, data), dtype=dtype)

    def astype(self, dtype) -> "Mat":
        """An assembled Mat holding the same values in another storage
        dtype — the precision-plan constructor (``RefinedKSP`` builds its
        bf16/f32 inner operator through this; PARITY.md "Mixed
        precision"). Conversion runs from the retained host CSR when
        available (one rounding step from the assembly-precision values,
        not two), falling back to the fetched device layout. The null
        space, if any, rides along.

        NOTE: unlike ``ndarray.astype``, a matching dtype returns
        ``self`` (no copy) — the device operands are immutable on the
        hot paths and a same-dtype rebuild would only churn HBM; use
        :meth:`duplicate` when an independent same-dtype Mat is
        needed."""
        dtype = np.dtype(dtype)
        if dtype == np.dtype(self.dtype):
            return self
        if self.host_csr is not None:
            m = Mat.from_csr(self.comm, self.shape, self.host_csr,
                             dtype=dtype)
        else:
            m = Mat.from_scipy(self.comm, self.to_scipy(), dtype=dtype)
        ns = self.get_nullspace()
        if ns is not None:
            m.set_nullspace(ns)
        return m

    @classmethod
    def from_scipy(cls, comm, A, dtype=jnp.float64) -> "Mat":
        import time as _time
        t0 = _time.perf_counter()
        A = A.tocsr()
        tocsr = _time.perf_counter() - t0
        m = cls.from_csr(comm, A.shape, (A.indptr, A.indices, A.data),
                         dtype=dtype)
        # the format conversion is part of what callers time as assembly —
        # it must appear in the breakdown or the parts can't sum to the wall
        m.assembly_breakdown = {"tocsr_s": round(tocsr, 4),
                                **m.assembly_breakdown}
        return m

    # ---- PETSc-Mat-shaped API ----------------------------------------------
    def set_up(self):
        return self

    def assemble(self):
        self._assembled = True
        return self

    assembly_begin = assemble
    assembly_end = assemble

    @property
    def assembled(self) -> bool:
        return self._assembled

    @property
    def dtype(self):
        return self.ell_vals.dtype

    @property
    def n_pad(self) -> int:
        return self.ell_cols.shape[0]

    @property
    def K(self) -> int:
        """ELL width: max nonzeros per row."""
        return self.ell_cols.shape[1]

    def get_vecs(self) -> tuple[Vec, Vec]:
        """Compatibly-sharded (x, b) pair — the reference's ``a.getVecs()``."""
        mk = lambda: Vec(self.comm, self.shape[0], dtype=self.dtype,
                         layout=self.layout)
        return mk(), mk()

    # ---- null space (PETSc MatSetNullSpace) --------------------------------
    def set_nullspace(self, nullspace):
        """Attach a :class:`core.nullspace.NullSpace`; KSP then projects the
        RHS and all operator/PC outputs onto its complement (the PETSc route
        to compatible singular systems, e.g. pure-Neumann Poisson)."""
        self.nullspace = nullspace
        return self

    setNullSpace = set_nullspace

    def get_nullspace(self):
        return getattr(self, "nullspace", None)

    getNullSpace = get_nullspace

    # ---- assembled-matrix algebra (PETSc Mat API surface) ------------------
    def _replace_from_scipy(self, S):
        """Rebuild this Mat's storage in place from a scipy matrix (PETSc's
        mutating Mat ops rebuild the assembled form the same way)."""
        S = S.tocsr()
        rebuilt = Mat.from_csr(self.comm, S.shape,
                               (S.indptr, S.indices, S.data),
                               dtype=self.dtype)
        self.shape = rebuilt.shape
        self.layout = rebuilt.layout
        self.ell_cols = rebuilt.ell_cols
        self.ell_vals = rebuilt.ell_vals
        self.host_csr = rebuilt.host_csr
        self.dia_vals = rebuilt.dia_vals
        self.dia_offsets = rebuilt.dia_offsets
        self._diag_value = None
        self._assembled = True
        self._state += 1
        return self

    def norm(self, norm_type: str = "frobenius") -> float:
        """Matrix norm: 'frobenius' (PETSc default), '1', or 'inf'."""
        import scipy.sparse.linalg  # noqa: F401  (norm lives on the module)
        import scipy.sparse as sp
        S = self.to_scipy()
        t = str(norm_type).lower()
        if t in ("frobenius", "fro"):
            return float(sp.linalg.norm(S, "fro"))
        if t in ("1", "one"):
            return float(np.abs(S).sum(axis=0).max())
        if t in ("inf", "infinity"):
            return float(np.abs(S).sum(axis=1).max())
        raise ValueError(f"unknown norm type {norm_type!r}")

    def transpose(self) -> "Mat":
        """A new assembled Mat holding A^T."""
        return Mat.from_scipy(self.comm, self.to_scipy().T.tocsr(),
                              dtype=self.dtype)

    def duplicate(self, copy_values: bool = True) -> "Mat":
        S = self.to_scipy().copy()
        if not copy_values:
            S.data[:] = 0.0
        return Mat.from_scipy(self.comm, S, dtype=self.dtype)

    def copy(self) -> "Mat":
        return self.duplicate(copy_values=True)

    def axpy(self, alpha: float, X: "Mat") -> "Mat":
        """Y <- Y + alpha*X (PETSc MatAXPY; rebuilds the device layout)."""
        if X.shape != self.shape:
            raise ValueError(f"axpy shape mismatch: {self.shape} vs {X.shape}")
        return self._replace_from_scipy(
            self.to_scipy() + float(alpha) * X.to_scipy())

    def scale(self, alpha: float) -> "Mat":
        """A <- alpha*A — pure device-side scaling, no host rebuild."""
        alpha = self.dtype.type(alpha)
        self.ell_vals = self.ell_vals * alpha
        if self.dia_vals is not None:
            self.dia_vals = self.dia_vals * alpha
        if self.host_csr is not None:
            ip, ix, dv = self.host_csr
            self.host_csr = (ip, ix, dv * float(alpha))
        if self._diag_value is not None:
            self._diag_value *= float(alpha)
        self._state += 1
        return self

    def shift(self, alpha: float) -> "Mat":
        """A <- A + alpha*I (PETSc MatShift)."""
        import scipy.sparse as sp
        return self._replace_from_scipy(
            self.to_scipy() + float(alpha) * sp.eye(self.shape[0],
                                                    format="csr"))

    def zero_rows(self, rows, diag: float = 1.0, b: Vec | None = None,
                  x: Vec | None = None) -> "Mat":
        """PETSc MatZeroRows: zero the given global rows, put ``diag`` on
        their diagonal, and (given x, b) fix ``b[rows] = diag * x[rows]`` —
        the standard way to impose Dirichlet conditions on an assembled
        system."""
        rows = np.asarray(rows, dtype=np.int64)
        S = self.to_scipy().tolil()
        S[rows, :] = 0.0
        if diag != 0.0:
            S[rows, rows] = diag
        self._replace_from_scipy(S.tocsr())
        if b is not None and x is not None:
            bh = b.to_numpy()
            bh[rows] = diag * x.to_numpy()[rows]
            b.set_global(bh)
        return self

    zeroRows = zero_rows

    def get_row(self, i: int):
        """(cols, vals) of global row i (PETSc MatGetRow)."""
        S = self.to_scipy()
        s, e = int(S.indptr[i]), int(S.indptr[i + 1])
        return np.asarray(S.indices[s:e]), np.asarray(S.data[s:e])

    getRow = get_row

    def get_info(self) -> dict:
        """nnz / memory summary (PETSc MatGetInfo analog)."""
        if self.host_csr is not None:
            nnz = int(self.host_csr[0][-1])
        else:
            nnz = int((self.comm.host_fetch(self.ell_vals)[: self.shape[0]] != 0).sum())
        return {
            "nnz": nnz,
            "ell_width": self.K,
            "dia_diagonals": len(self.dia_offsets),
            "rows_per_device": self.comm.local_size(self.shape[0]),
            "memory_device_bytes": int(
                self.ell_vals.size * self.ell_vals.dtype.itemsize
                + self.ell_cols.size * self.ell_cols.dtype.itemsize),
        }

    getInfo = get_info

    # ---- operator application ----------------------------------------------
    def mult_padded(self, x_padded: jax.Array) -> jax.Array:
        """SpMV on the padded global device array (jit-compiled, sharded).

        Under jit with sharded operands XLA inserts the all-gather of ``x``
        itself (GSPMD); solvers instead use the explicit shard_map path via
        :meth:`device_arrays` + ops.spmv.
        """
        if self.dia_vals is not None:
            return _jit_dia_spmv(self.dia_vals, x_padded, self.dia_offsets)
        return _jit_spmv(self.ell_cols, self.ell_vals, x_padded)

    def mult(self, x: Vec, y: Vec | None = None) -> Vec:
        ypad = self.mult_padded(x.data)
        if y is None:
            y = Vec(self.comm, self.shape[0], data=ypad, layout=self.layout)
        else:
            y.data = ypad
        return y

    def mult_transpose(self, x: Vec, y: Vec | None = None) -> Vec:
        """``y = Aᵀ x`` (PETSc MatMultTranspose) via the distributed
        transpose-SpMV program (scatter-psum, the reverse pattern of the
        all-gather forward product)."""
        prog = _mult_t_program(self)
        ypad = prog(self.device_arrays(), x.data)
        if y is None:
            return Vec(self.comm, self.shape[0], data=ypad,
                       layout=self.layout)
        y.data = ypad
        return y

    multTranspose = mult_transpose

    def diagonal(self) -> np.ndarray:
        """Host-side global diagonal (for Jacobi preconditioning)."""
        if self._diag_value is not None:
            return np.full(self.shape[0], self._diag_value)
        if self.host_csr is not None:
            return csr_diag(*self.host_csr, self.shape[0])
        cols = self.comm.host_fetch(self.ell_cols)[: self.shape[0]]
        vals = self.comm.host_fetch(self.ell_vals)[: self.shape[0]]
        gidx = np.arange(self.shape[0])[:, None]
        return np.where(cols == gidx, vals, 0.0).sum(axis=1)

    def to_scipy(self):
        import scipy.sparse as sp
        if self.host_csr is not None:
            indptr, indices, data = self.host_csr
            return sp.csr_matrix((data, indices, indptr), shape=self.shape)
        cols = self.comm.host_fetch(self.ell_cols)[: self.shape[0]]
        vals = self.comm.host_fetch(self.ell_vals)[: self.shape[0]]
        n = self.shape[0]
        rows = np.repeat(np.arange(n), cols.shape[1])
        mask = vals.ravel() != 0
        return sp.csr_matrix(
            (vals.ravel()[mask], (rows[mask], cols.ravel()[mask])),
            shape=self.shape)

    # ---- linear-operator protocol (consumed by solvers.krylov) -------------
    def device_arrays(self):
        """The raw sharded arrays consumed by shard_map solver kernels."""
        if self.dia_vals is not None:
            return (self.dia_vals,)
        return self.ell_cols, self.ell_vals

    def local_spmv(self, comm: DeviceComm):
        """Local SpMV closure for use inside shard_map.

        DIA path (banded matrices): all_gather + static shifted slices.
        ELL path (general sparsity): all_gather + gather.
        """
        from jax import lax
        axis = comm.axis
        if self.dia_vals is not None:
            offsets = self.dia_offsets
            halo = max(abs(o) for o in offsets) if offsets else 0
            lsize = comm.local_size(self.shape[0])
            ndev = comm.size

            if ndev > 1 and 0 < halo <= lsize:
                # scalable banded path: every occupied diagonal reaches at
                # most one neighbour shard, so the VecScatter is a ring
                # ppermute of `halo` boundary rows each way — O(halo) bytes
                # on the ICI instead of replicating the whole vector
                # (SURVEY.md §7.4-3: the all_gather fallback bounds scaling)
                # open chain, not a ring: shards with no incoming pair
                # (the global edges) receive zeros from ppermute itself —
                # no wrap transfer, no masking needed
                fwd = [(i, i + 1) for i in range(ndev - 1)]
                bwd = [(i, i - 1) for i in range(1, ndev)]

                def spmv(op_local, x_local):
                    (dia,) = op_local
                    acc = _accum(dia.dtype)
                    # the halo ppermutes move STORAGE-dtype rows — the
                    # halved-byte budget the low-precision layouts buy
                    left = lax.ppermute(x_local[-halo:], axis, fwd)
                    right = lax.ppermute(x_local[:halo], axis, bwd)
                    ext = jnp.concatenate([left, x_local, right])
                    y = jnp.zeros(lsize, acc or dia.dtype)
                    for d, off in enumerate(offsets):
                        seg = lax.slice_in_dim(ext, halo + int(off),
                                               halo + int(off) + lsize)
                        coeff = dia[:, d].astype(acc) if acc else dia[:, d]
                        y = y + coeff * seg
                    return y.astype(dia.dtype)

                return spmv

            def spmv(op_local, x_local):
                (dia,) = op_local
                x_full = lax.all_gather(x_local, axis, tiled=True)
                row0 = lax.axis_index(axis) * lsize
                return dia_spmv_local(dia, offsets, x_full, row0, halo)

            return spmv

        def spmv(op_local, x_local):
            cols, vals = op_local
            x_full = lax.all_gather(x_local, axis, tiled=True)
            return ell_spmv_local(cols, vals, x_full)

        return spmv

    def local_spmv_many(self, comm: DeviceComm):
        """Multi-RHS local SpMV closure: ``spmv(op_local, X_local)`` with
        ``X_local`` the device's ``(lsize, nrhs)`` block of an
        ``(n_pad, nrhs)`` row-sharded RHS block.

        The communication structure mirrors :meth:`local_spmv` exactly —
        ONE collective per apply whatever ``nrhs`` is (the whole point of
        the batched solve path): the ELL/general-DIA paths all_gather the
        entire block in one op (bytes scale with k, op count does not) and
        the banded-DIA path ships the two ``(halo, nrhs)`` boundary blocks
        over the same open-chain ppermutes.
        """
        from jax import lax
        axis = comm.axis
        if self.dia_vals is not None:
            offsets = self.dia_offsets
            halo = max(abs(o) for o in offsets) if offsets else 0
            lsize = comm.local_size(self.shape[0])
            ndev = comm.size

            if ndev > 1 and 0 < halo <= lsize:
                fwd = [(i, i + 1) for i in range(ndev - 1)]
                bwd = [(i, i - 1) for i in range(1, ndev)]

                def spmv(op_local, x_local):
                    (dia,) = op_local
                    acc = _accum(dia.dtype)
                    left = lax.ppermute(x_local[-halo:], axis, fwd)
                    right = lax.ppermute(x_local[:halo], axis, bwd)
                    ext = jnp.concatenate([left, x_local, right])
                    y = jnp.zeros((lsize, x_local.shape[1]),
                                  acc or dia.dtype)
                    for d, off in enumerate(offsets):
                        seg = lax.slice_in_dim(ext, halo + int(off),
                                               halo + int(off) + lsize)
                        coeff = (dia[:, d:d + 1].astype(acc) if acc
                                 else dia[:, d:d + 1])
                        y = y + coeff * seg
                    return y.astype(dia.dtype)

                return spmv

            def spmv(op_local, x_local):
                (dia,) = op_local
                x_full = lax.all_gather(x_local, axis, tiled=True)
                row0 = lax.axis_index(axis) * lsize
                return dia_spmv_local_many(dia, offsets, x_full, row0, halo)

            return spmv

        def spmv(op_local, x_local):
            cols, vals = op_local
            x_full = lax.all_gather(x_local, axis, tiled=True)
            return ell_spmv_local_many(cols, vals, x_full)

        return spmv

    def local_spmv_t(self, comm: DeviceComm):
        """Local transpose-SpMV closure (``y = Aᵀ x``) for shard_map bodies.

        Each device forms its rows' contribution to the full output vector
        (its rows hit columns anywhere), then one ``psum`` combines them —
        the reverse communication pattern of the all-gather forward product.
        Used by KSPLSQR (PETSc's MatMultTranspose slot).
        """
        from jax import lax
        axis = comm.axis
        if self.shape[0] != self.shape[1]:
            raise ValueError(
                "local_spmv_t supports square operators only (output is "
                f"row-partitioned like the input); shape={self.shape}")
        n = self.shape[0]
        lsize = comm.local_size(n)
        n_pad = lsize * comm.size
        if self.dia_vals is not None:
            offsets = self.dia_offsets
            halo = max(abs(o) for o in offsets) if offsets else 0
            ndev = comm.size

            def accumulate_window(dia, x_local):
                """Local rows' contributions over the ±halo column window."""
                win = jnp.zeros(lsize + 2 * halo, dia.dtype)
                for d, off in enumerate(offsets):
                    win = lax.dynamic_update_slice_in_dim(
                        win,
                        lax.dynamic_slice_in_dim(win, int(off) + halo, lsize)
                        + dia[:, d] * x_local,
                        int(off) + halo, axis=0)
                return win

            if halo == 0:
                # purely diagonal: the transpose product is entirely local
                def spmv_t(op_local, x_local):
                    (dia,) = op_local
                    return dia[:, 0] * x_local

                return spmv_t

            if halo <= lsize:
                # open-chain spill exchange: a shard's contributions reach at
                # most one neighbour each way, so ship the two halo spills
                # over ppermute instead of psum-ing an O(n) buffer (an empty
                # chain on a 1-device mesh zero-fills both spills)
                fwd = [(i, i + 1) for i in range(ndev - 1)]
                bwd = [(i, i - 1) for i in range(1, ndev)]

                def spmv_t(op_local, x_local):
                    (dia,) = op_local
                    win = accumulate_window(dia, x_local)
                    spill_l = win[:halo]           # belongs to rank i-1
                    spill_r = win[halo + lsize:]   # belongs to rank i+1
                    from_left = lax.ppermute(spill_r, axis, fwd)
                    from_right = lax.ppermute(spill_l, axis, bwd)
                    y = win[halo:halo + lsize]
                    y = y.at[:halo].add(from_left)
                    y = y.at[lsize - halo:].add(from_right)
                    return y

                return spmv_t

            def spmv_t(op_local, x_local):
                (dia,) = op_local
                row0 = lax.axis_index(axis) * lsize
                win = accumulate_window(dia, x_local)
                buf = jnp.zeros(n_pad + 2 * halo, dia.dtype)
                buf = lax.dynamic_update_slice_in_dim(buf, win, row0, axis=0)
                buf = lax.psum(buf, axis)
                y_full = lax.slice_in_dim(buf, halo, halo + n_pad)
                return lax.dynamic_slice_in_dim(y_full, row0, lsize)

            return spmv_t

        def spmv_t(op_local, x_local):
            cols, vals = op_local
            contrib = vals * x_local[:, None]
            y_full = jnp.zeros(n_pad, vals.dtype)
            y_full = y_full.at[cols.ravel()].add(contrib.ravel())
            y_full = lax.psum(y_full, axis)
            row0 = lax.axis_index(axis) * lsize
            return lax.dynamic_slice_in_dim(y_full, row0, lsize)

        return spmv_t

    def op_specs(self, axis):
        from jax.sharding import PartitionSpec as P
        if self.dia_vals is not None:
            return (P(axis, None),)
        return (P(axis, None), P(axis, None))

    def program_key(self):
        if self.dia_vals is not None:
            return ("dia", self.dia_offsets)
        return ("ell",)

    def __repr__(self):
        return (f"Mat(shape={self.shape}, K={self.K}, "
                f"devices={self.comm.size}, dtype={self.dtype})")


def coo_to_csr(shape, rows, cols, vals, mode: str = "insert"):
    """Accumulate COO triplets into a host CSR triple with PETSc's
    MatSetValues duplicate semantics.

    ``mode='insert'`` (INSERT_VALUES): the LAST write to an (i, j) slot
    wins; ``mode='add'`` (ADD_VALUES): duplicates sum. Out-of-range
    indices raise (PETSc errors on them too, absent MAT_IGNORE entries).
    Used by the facade's ``Mat.setValues`` assembly path (compat/petsc4py)
    — the ``csr=`` constructor fast path bypasses this entirely.
    """
    import scipy.sparse as sp
    nrows, ncols = int(shape[0]), int(shape[1])
    rows = np.asarray(rows, dtype=np.int64).ravel()
    cols = np.asarray(cols, dtype=np.int64).ravel()
    vals = np.asarray(vals).ravel()
    if not (rows.shape == cols.shape == vals.shape):
        raise ValueError(
            f"coo_to_csr: rows/cols/vals lengths differ "
            f"({rows.shape}, {cols.shape}, {vals.shape})")
    if len(rows) and (rows.min() < 0 or rows.max() >= nrows
                      or cols.min() < 0 or cols.max() >= ncols):
        raise ValueError(
            f"coo_to_csr: index out of range for shape {(nrows, ncols)}")
    if mode == "add":
        A = sp.coo_matrix((vals, (rows, cols)), shape=(nrows, ncols)).tocsr()
        return A.indptr, A.indices, A.data
    if mode != "insert":
        raise ValueError(f"coo_to_csr: unknown mode {mode!r}")
    # INSERT: keep the last occurrence of each (i, j). np.unique on the
    # REVERSED flat keys returns the first occurrence in reversed order —
    # i.e. the last in insertion order.
    flat = rows * np.int64(ncols) + cols
    _, first_rev = np.unique(flat[::-1], return_index=True)
    keep = len(flat) - 1 - first_rev
    A = sp.coo_matrix((vals[keep], (rows[keep], cols[keep])),
                      shape=(nrows, ncols)).tocsr()
    return A.indptr, A.indices, A.data


_MULT_T_CACHE: dict = {}


def _mult_t_program(mat: Mat):
    """Cached jitted shard_map program for the transpose product."""
    from jax.sharding import PartitionSpec as P
    comm = mat.comm
    key = (comm.mesh, mat.program_key(), mat.shape, str(mat.dtype))
    prog = _MULT_T_CACHE.get(key)
    if prog is None:
        spmv_t = mat.local_spmv_t(comm)
        axis = comm.axis
        prog = jax.jit(comm.shard_map(
            spmv_t, in_specs=(mat.op_specs(axis), P(axis)),
            out_specs=P(axis)))
        _MULT_T_CACHE[key] = prog
    return prog


@jax.jit
def _jit_spmv(cols, vals, x_padded):
    return ell_spmv_local(cols, vals, x_padded)


@functools.partial(jax.jit, static_argnums=(2,))
def _jit_dia_spmv(dia, x_padded, offsets):
    halo = max(abs(o) for o in offsets) if offsets else 0
    return dia_spmv_local(dia, offsets, x_padded, 0, halo)
