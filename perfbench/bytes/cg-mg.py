"""Least HBM bytes of CG preconditioned by one geometric multigrid V-cycle
per iteration (``-ksp_type cg -pc_type mg``), counted from the method,
not from any program's fused passes.

The V-cycle is solvers/mg.py's algorithm: full coarsening by 2 in every
axis while all edges stay even and at least 4 (``mg_levels``, copied
below), pre- and post-smoothing sweeps on every level but the coarsest,
residual + restriction going down, prolongation + correction coming up,
and damped-Jacobi sweeps on the coarsest grid of N_L points. All of it
but the coarsest solve is local in space: a streamed pass can carry the
whole downward leg from the fine residual to the coarsest right-hand
side, and the whole upward leg from the coarsest correction (and the
fine residual, recomputing each level's pre-smoothed iterate) to z.

Derivation. V = one vector's bytes, M = the matrix bytes one apply reads
(0 matrix-free), w = the coarsest grid's bytes. The carried vectors are
x, r and p; z = M r is recomputed and never stored. Each iteration has
three global dependencies, alpha = rz / (p.Ap), the coarsest solve, and
beta = rz' / rz, and every point has to be visited once between them:

- S1, once alpha is known: read x, r, p; write x' and r' = r - alpha A p
  (A p recomputed), and carry the V-cycle's downward leg on r' to the
  coarsest right-hand side: 5 V + M + w;
- the coarsest solve: read and write the coarsest grid: 2 w;
- S2, once it is done: read r' and the coarsest correction, form z'
  through the upward leg, and sum rz' = r'.z': 1 V + w;
- S3, once beta is known: read r', p and the coarsest correction, form
  z' again, write p' = z' + beta p, and sum p'.A p': 3 V + M + w.

That is 9 V + 2 M + 5 w per iteration (M + 2 V in place of 2 M where
storing q = A p is cheaper). Per solve from x0 = 0: the prologue's two
sweeps read b (2 V + M + 4 w: z0 = M b needs its own coarsest solve), the
first S1 reads no x (-1 V), and the true-residual check reads b and x
(2 V + M): 3 V + 2 M + 4 w.

VMEM. Each sweep may keep up to the chip's VMEM (C bytes per chip) out
of HBM on both its read and its write side, so the least counts subtract
2 C per sweep: 3 sweeps per iteration, 3 per solve.
"""

SWEEPS_PER_ITERATION = 3
SWEEPS_PER_SOLVE = 3
MIN_EDGE = 4


def mg_levels(grid):
    """The V-cycle's grids, finest first (solvers/mg.py ``mg_levels``)."""
    levels = [tuple(grid)]
    while all(d % 2 == 0 and d // 2 >= MIN_EDGE for d in levels[-1]):
        levels.append(tuple(d // 2 for d in levels[-1]))
    return levels


def _op(info):
    coarse = mg_levels(info["grid"])[-1]
    w = coarse[0] * coarse[1] * coarse[2] * info["itemsize"]
    return info["n"] * info["itemsize"], info["matrix_bytes_per_apply"], w


def per_iteration(info, vmem_bytes: int) -> int:
    """Least HBM bytes of one iteration over all chips of the cell."""
    v, m, w = _op(info)
    raw = 9 * v + min(2 * m, m + 2 * v) + 5 * w
    credit = SWEEPS_PER_ITERATION * 2 * vmem_bytes * info["chips"]
    return max(0, raw - credit)


def per_solve(info, vmem_bytes: int) -> int:
    """Least HBM bytes outside the iterations of one solve from x0 = 0."""
    v, m, w = _op(info)
    raw = 3 * v + 2 * m + 4 * w
    credit = SWEEPS_PER_SOLVE * 2 * vmem_bytes * info["chips"]
    return max(0, raw - credit)
