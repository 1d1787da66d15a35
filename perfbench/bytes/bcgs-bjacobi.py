"""Least HBM bytes of right-preconditioned BiCGStab with block Jacobi on
ILU(0) blocks (``-ksp_type bcgs -pc_type bjacobi`` at PETSc's defaults,
solvers/bjilu.py), counted from the method, not from any program's fused
passes.

Derivation. V = one vector's bytes, M = the matrix bytes one operator
apply reads (A's stored diagonals), F = the ILU(0) factor bytes beyond
A's own entries (the two L multipliers and the inverse pivot of every
row: U's off-diagonals are A's). The carried vectors are x, r, p, v and
the shadow r^; each iteration forms p^ = M^-1 p, s, s^ = M^-1 s and t.
A block's triangular solves run forward then backward over its rows; a
block (65,536 rows, 512 KiB a vector at fp64) stays on chip between the
two, so the intermediate never reaches HBM, and U's off-diagonals come
with A's apply in the same sweep. Each iteration has three global
reductions, rho = r^.r, alpha = rho / (r^.v) and omega = t.s / t.t (with
s.s for its floor on cos(t, s)), and every point has to be visited once
between them:

- sweep S1, once beta = (rho'/rho)(alpha/omega) is known: read r, p and
  v, write p' = r + beta (p - omega v), solve p^ = M^-1 p' and write it,
  apply v' = A p^ and write it, and sum r^.v' (reading r^): 7 V + F + M;
- sweep S2, once alpha is known: read r and v', write s = r - alpha v',
  solve s^ = M^-1 s and write it, apply t = A s^ and sum t.s, t.t and s.s:
  4 V + F + M, plus t's write;
- sweep S3, once omega is known: read x, p^ and s^ and write x' =
  x + alpha p^ + omega s^, read s and write r' = s - omega t, and sum
  rho' = r^.r' (reading r^) and ||r'||: 7 V, plus t's read.

That is 18 V + 2 F + 2 M per iteration, plus t: stored by S2 and read by
S3 (2 V), or applied again in S3 from s^ (M); the lesser counts. At
fp64 on the five-point operator, M = 5 V and F = 3 V: 36 V.

Per solve, from x0 = 0: one sweep reads b and writes r0 = r^ with rho0
(2 V), the first S1 reads no p or v (-2 V), and the true-residual check
reads b and x (2 V + M): 2 V + M.

VMEM. Data that stays in on-chip memory across a sweep is neither read
nor written from HBM, so each sweep may save up to twice the chip's VMEM
(C bytes per chip); the least counts subtract that: 3 sweeps x 2 C per
iteration, 2 sweeps x 2 C per solve. At 2048^2 (V = 32 MiB, C = 4 V)
this takes 24 V of the 36.
"""

SWEEPS_PER_ITERATION = 3
SWEEPS_PER_SOLVE = 2


def _op(info):
    v = info["n"] * info["itemsize"]
    return v, info["matrix_bytes_per_apply"], info.get("pc_factor_bytes", 0)


def per_iteration(info, vmem_bytes: int) -> int:
    """Least HBM bytes of one iteration over all chips of the cell."""
    v, m, f = _op(info)
    raw = 18 * v + 2 * f + 2 * m + min(2 * v, m)
    credit = SWEEPS_PER_ITERATION * 2 * vmem_bytes * info["chips"]
    return max(0, raw - credit)


def per_solve(info, vmem_bytes: int) -> int:
    """Least HBM bytes outside the iterations of one solve from x0 = 0."""
    v, m, _ = _op(info)
    raw = 2 * v + m
    credit = SWEEPS_PER_SOLVE * 2 * vmem_bytes * info["chips"]
    return max(0, raw - credit)
