"""Least HBM bytes of CG + point Jacobi (Hestenes-Stiefel CG, the method
of ``-ksp_type cg -pc_type jacobi``), counted from the method, not from
any program's fused passes.

Derivation. V = one vector's bytes, M = the matrix bytes one operator
apply reads (0 matrix-free), Dg = a stored diagonal's bytes (0 where the
diagonal is a constant). The carried vectors are x, r and p; z = D^-1 r
is pointwise and never needs to be stored. Each iteration has two global
reductions, alpha = rz / (p.Ap) and beta = rz' / rz, and every point has
to be visited once between them, with the carried vectors in HBM:

- sweep S1, once alpha is known: read x, r and p (A p is recomputed from
  p in the stream), write x' = x + alpha p and r' = r - alpha A p, and sum
  rz' = r'.D^-1 r' (with ||r'|| for the stopping test): 5 V;
- sweep S2, once beta is known: read r' and p, write p' = D^-1 r' + beta p,
  and sum p'.A p' for the next alpha: 3 V.

That is 8 V per iteration, plus the operator: S1 and S2 each apply A
(2 M), unless S2 stores q = A p' and S1 reads it back (M + 2 V); the
lesser counts. The diagonal is read in S1 and S2 (2 Dg). Standard CG is
usually counted at 11 V (bench.py's PASSES_PER_ITER): that count stores
q and z and fuses nothing across the updates, so a program that fuses
more would read above 100% against it.

Per solve, from x0 = 0: one sweep reads b for rz0 and p0.Ap0 (1 V + M +
Dg), the first S1 reads no x (-1 V), and the true-residual check reads b
and x (2 V + M): 2 V + 2 M + Dg.

VMEM. Data that stays in on-chip memory across a sweep is neither read
nor written from HBM, so each sweep may save up to twice the chip's VMEM
(C bytes per chip); the least counts subtract that: 2 sweeps x 2 C per
iteration. At 256^3 (V = 64 MiB) this leaves little to count, which is
one reason the cells are larger.
"""

SWEEPS_PER_ITERATION = 2
SWEEPS_PER_SOLVE = 2


def _op(info):
    v = info["n"] * info["itemsize"]
    return v, info["matrix_bytes_per_apply"], info["diagonal_bytes"]


def per_iteration(info, vmem_bytes: int) -> int:
    """Least HBM bytes of one iteration over all chips of the cell."""
    v, m, dg = _op(info)
    raw = 8 * v + min(2 * m, m + 2 * v) + 2 * dg
    credit = SWEEPS_PER_ITERATION * 2 * vmem_bytes * info["chips"]
    return max(0, raw - credit)


def per_solve(info, vmem_bytes: int) -> int:
    """Least HBM bytes outside the iterations of one solve from x0 = 0."""
    v, m, dg = _op(info)
    raw = 2 * v + 2 * m + dg
    credit = SWEEPS_PER_SOLVE * 2 * vmem_bytes * info["chips"]
    return max(0, raw - credit)
