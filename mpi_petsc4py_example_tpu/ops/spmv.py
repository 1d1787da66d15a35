"""Sparse matrix–vector product kernels and layouts.

TPU-native replacement for PETSc's C CSR SpMV + VecScatter halo exchange
(SURVEY.md N8/L0; triggered by every KSP/EPS iteration, ``test.py:50``,
``test2.py:88``). CSR's per-row serial pointer-chasing is hostile to the TPU
vector unit, so the device layout is **ELL** (row-padded): every row stores
exactly ``K = max nnz/row`` (column, value) slots, padding with (0, 0.0).
SpMV then becomes a dense-shaped gather + multiply + row-sum that XLA maps
onto the VPU with no data-dependent shapes.

Distribution: rows are 1-D sharded over the mesh; the input vector is
``all_gather``-ed (the general VecScatter replacement — correct for any
sparsity). Stencil operators use a matrix-free path instead (models/).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..utils.dtypes import is_low_precision


def accum_dtype(dtype):
    """Accumulation dtype of a sub-f32-storage SpMV, or None when the
    storage dtype accumulates natively (fp32/fp64/complex).

    bf16 operand storage halves the gathered/ppermuted bytes — the whole
    point of the low-precision layouts — but a row-sum ACCUMULATED in
    bf16 (8 mantissa bits) would throw the win away numerically; the
    kernels below contract into fp32 and cast the result back to the
    storage dtype, which is exactly the MXU's native bf16-in/f32-acc
    regime on TPU."""
    return jnp.float32 if is_low_precision(dtype) else None


def widened_einsum(spec, a, b, platform: str | None = None):
    """``jnp.einsum(spec, a, b)`` with the accumulation discipline of
    :func:`accum_dtype` applied once: sub-f32 operand storage contracts
    with ``preferred_element_type=f32`` and the result returns to the
    first operand's storage dtype; everything else is the plain einsum.
    The ONE definition the SpMV kernels and the PC factor applies
    (solvers/pc.py bjacobi/lu, single- and multi-RHS) all share — a
    future accumulation-policy change edits exactly one site.

    On a TPU mesh (``platform="tpu"``) f64/c128 operands contract as a
    fused multiply + reduce instead: XLA:TPU emulates an f64 dot by
    materializing split copies of the operands, 24 GiB of temporaries for
    the 4 GiB bjacobi stack of convdiff2d(512) on one v5e (sandbox
    compile), where the fused reduce streams the factor once."""
    if platform == "tpu" and jnp.dtype(a.dtype) in (jnp.float64,
                                                    jnp.complex128):
        return _multiply_reduce(spec, a, b)
    acc = accum_dtype(a.dtype)
    if acc is None:
        return jnp.einsum(spec, a, b)
    return jnp.einsum(spec, a, b, preferred_element_type=acc).astype(a.dtype)


def _multiply_reduce(spec, a, b):
    """``einsum(spec, a, b)`` as broadcast multiply + sum over the
    contracted letters (no dot_general)."""
    ins, out = spec.split("->")
    sa, sb = ins.split(",")
    letters = sorted(set(sa + sb), key=lambda c: (c not in out, c))

    def expand(x, sx):
        x = jnp.transpose(x, [sx.index(c) for c in letters if c in sx])
        return x.reshape([x.shape[[c for c in letters if c in sx].index(c)]
                          if c in sx else 1 for c in letters])

    prod = expand(a, sa) * expand(b, sb)
    red = prod.sum(axis=tuple(range(len(out), len(letters))))
    return jnp.transpose(red, [sorted(out).index(c) for c in out])


def csr_to_ell(indptr, indices, data, ncols_pad_to: int | None = None):
    """Convert host CSR to ELL ``(cols, vals)`` of shape ``(nrows, K)``.

    Padding slots use column 0 and value 0.0 (contributing exactly zero to
    the product). Vectorized host-side construction; the heavy path is also
    available from the native C++ toolkit (native/csrkit).
    """
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices)
    data = np.asarray(data)
    nrows = len(indptr) - 1
    counts = indptr[1:] - indptr[:-1]
    K = int(counts.max()) if nrows else 0
    K = max(K, 1)
    if ncols_pad_to is not None:
        K = max(K, ncols_pad_to)
    cols = np.zeros((nrows, K), dtype=np.int32)
    vals = np.zeros((nrows, K), dtype=data.dtype)
    if len(data):
        rows = np.repeat(np.arange(nrows), counts)
        pos = np.arange(len(data)) - np.repeat(indptr[:-1], counts)
        cols[rows, pos] = indices
        vals[rows, pos] = data
    return cols, vals


def ell_spmv_local(cols, vals, x_full):
    """Local ELL SpMV: ``y[i] = sum_k vals[i,k] * x_full[cols[i,k]]``.

    ``cols``/``vals`` are this shard's rows ``(lrows, K)``; ``x_full`` is the
    full (gathered) input vector. Pure jnp — jit/shard_map friendly, fused by
    XLA into a single gather+fma pass. Sub-f32 storage contracts in fp32
    (:func:`accum_dtype`) and returns the storage dtype.
    """
    return widened_einsum("rk,rk->r", vals, x_full[cols])


def ell_spmv_local_many(cols, vals, x_full_many):
    """Multi-RHS local ELL SpMV: ``Y[i, j] = sum_k vals[i,k] * X[cols[i,k], j]``.

    ``x_full_many`` is the full (gathered) ``(n, nrhs)`` RHS block. The
    inner contraction is an MXU-shaped matmul over the ``nrhs`` columns —
    the gather of X amortizes over every column (one ``all_gather`` of the
    whole block replaces ``nrhs`` per-vector gathers; the reason batched
    Krylov pays one collective per SpMV phase regardless of k).
    """
    # X[cols] is (lrows, K, nrhs); contract the ELL slot axis against vals
    return widened_einsum("rk,rkj->rj", vals, x_full_many[cols])


def dia_spmv_local_many(dia, offsets, x_full_many, row_offset, halo):
    """Multi-RHS local DIA SpMV on an ``(n, nrhs)`` gathered block.

    Identical static-shifted-slice structure to :func:`dia_spmv_local`
    (no gather at all); every slice simply carries the trailing RHS axis.
    """
    lrows = dia.shape[0]
    acc = accum_dtype(dia.dtype)
    xp = jnp.pad(x_full_many, ((halo, halo), (0, 0)))
    y = jnp.zeros((lrows, x_full_many.shape[1]), acc or dia.dtype)
    for d, off in enumerate(offsets):
        seg = jax.lax.dynamic_slice_in_dim(
            xp, row_offset + int(off) + halo, lrows)
        coeff = dia[:, d:d + 1].astype(acc) if acc else dia[:, d:d + 1]
        y = y + coeff * seg
    return y.astype(dia.dtype)


def ell_diag_local(cols, vals, row_offset, lrows):
    """Extract the local diagonal from ELL shards (device-side).

    ``row_offset`` is the global index of this shard's first row.
    """
    gidx = row_offset + jnp.arange(lrows)
    mask = cols == gidx[:, None]
    return jnp.sum(jnp.where(mask, vals, 0.0), axis=1)


def csr_find_diagonals(indptr, indices, max_diags: int = 32):
    """Offsets of the occupied matrix diagonals, or None if > max_diags.

    Banded operators (every BASELINE model: Poisson 2D/3D, convection-
    diffusion, tridiagonal) have a handful of occupied diagonals; storing
    them DIA-style turns SpMV's gather into static shifted slices — the
    layout the TPU VPU wants (gathers are its weak spot).
    """
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices)
    nrows = len(indptr) - 1
    counts = indptr[1:] - indptr[:-1]
    rows = np.repeat(np.arange(nrows), counts)
    offsets = np.unique(np.asarray(indices, dtype=np.int64) - rows)
    if len(offsets) > max_diags:
        return None
    return offsets


def csr_to_dia(indptr, indices, data, n, offsets):
    """Convert CSR to DIA: ``dia[i, d] = A[i, i + offsets[d]]``."""
    indptr = np.asarray(indptr, dtype=np.int64)
    indices = np.asarray(indices, dtype=np.int64)
    data = np.asarray(data)
    counts = indptr[1:] - indptr[:-1]
    rows = np.repeat(np.arange(n), counts)
    offs = indices - rows
    # offsets is sorted (np.unique in csr_find_diagonals) and covers every
    # entry's diagonal, so searchsorted IS the offset->slot map — a Python
    # dict loop here cost ~0.4 s at 1.8M nnz (BASELINE cfg1 assembly)
    offsets = np.asarray(offsets, dtype=np.int64)
    dcol = np.searchsorted(offsets, offs)
    dia = np.zeros((n, len(offsets)), dtype=data.dtype)
    dia[rows, dcol] = data
    return dia


def dia_spmv_local(dia, offsets, x_full, row_offset, halo):
    """Local DIA SpMV: ``y[i] = sum_d dia[i,d] * x_full[i + offsets[d]]``.

    ``x_full`` is the gathered global vector; ``row_offset`` the global index
    of this shard's first row; ``halo`` the static max |offset| used to
    zero-pad so every shifted slice is in range. All accesses are static
    contiguous slices — no gather.
    """
    lrows = dia.shape[0]
    acc = accum_dtype(dia.dtype)
    xp = jnp.pad(x_full, (halo, halo))
    y = jnp.zeros(lrows, acc or dia.dtype)
    for d, off in enumerate(offsets):
        seg = jax.lax.dynamic_slice_in_dim(
            xp, row_offset + int(off) + halo, lrows)
        coeff = dia[:, d].astype(acc) if acc else dia[:, d]
        y = y + coeff * seg
    return y.astype(dia.dtype)


def csr_diag(indptr, indices, data, n):
    """Host-side diagonal extraction from a global CSR triple."""
    indptr = np.asarray(indptr, dtype=np.int64)
    diag = np.zeros(n, dtype=np.asarray(data).dtype)
    counts = indptr[1:] - indptr[:-1]
    rows = np.repeat(np.arange(n), counts)
    hit = np.asarray(indices) == rows
    diag[rows[hit]] = np.asarray(data)[hit]
    return diag
