"""fp64 BiCGStab on one TPU chip as double-f32 Pallas sweeps.

XLA:TPU emulates fp64: each vector operation of :func:`krylov.bcgs_kernel`
becomes fusions that read and write both float32 halves of every operand
through HBM, several times over. On a real fp64 five-point DIA operator
on one chip, preconditioned by bjacobi's ILU(0) kernel stack
(:func:`bjilu.use_kernel`), the loop here carries BiCGStab's vectors as
double-f32 pairs (ops/double_f32.py) in the ILU kernel's lines-major
layout (:func:`bjilu.lines_major`), and visits every point once between
two of the iteration's global reductions:

- S1: ``p = r + beta (p - omega v)`` and ``p^ = M^-1 p`` (in the ILU
  kernel's forward sweep), then ``v = A p^`` with ``r^.v``;
- S2: ``s = r - alpha v`` and ``s^ = M^-1 s``, then ``t = A s^`` with
  ``t.s``, ``t.t`` and ``s.s``;
- S3: ``x += alpha p^ + omega s^`` and ``r = s - omega t`` with ``r^.r``
  and ``r.r``.

The recurrence, the omega floor (:func:`krylov._limit_omega`), the
breakdown and divergence tests and the scalars stay fp64, in XLA, as in
``bcgs_kernel``; the dots sum double-f32 products in double-f32, a
partial pair per grid step, joined in fp64. The operator's stored
diagonals are split into pairs once a solve, inside the program.

A plane is ``(groups, lines, 8, m)`` float32: a group is the PC's 8
blocks of ``lines`` grid lines of ``m`` points, line ``j`` of its 8
blocks one (8, m) slab. A grid step holds one group; its SpMV takes the
line before the group and the line after it from the neighbouring
groups' edge slabs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.double_f32 import dd_add, dd_mul, dd_neg, from_pair, to_pair
from . import bjilu

GROUP = 8               # blocks a group holds: one a sublane
PLATFORMS = ("tpu",)    # where the loop engages: Mosaic compiles it
# float32 planes S1's ILU(0) call reads and writes besides its
# coefficients: r, p, v in, p and M^-1 p out
_S1_PLANES = 10


def layout(comm, ksp_type: str, pc, operator) -> tuple | None:
    """``(lines, m)`` of the double-f32 loop's groups where it engages:
    BCGS on one TPU chip, a real fp64 five-point DIA operator, PC bjacobi
    on the ILU(0) kernel's stack in groups of 8 blocks that S1's kernel
    call fits (:func:`bjilu.kernel_fits`); else None."""
    if (ksp_type != "bcgs" or comm.platform not in PLATFORMS
            or comm.size != 1 or pc.kind != "bjacobi_ilu0"
            or np.dtype(operator.dtype) != np.float64):
        return None
    m = bjilu.five_point_width(operator)
    (coef,) = pc.device_arrays()
    # Mosaic tiles lanes by 128
    if (not m or (comm.platform == "tpu" and m % 128) or coef.ndim != 5
            or coef.shape[3] != GROUP):
        return None
    lines = coef.shape[2]
    if not bjilu.kernel_fits(lines, GROUP, m, _S1_PLANES):
        return None
    return lines, m


# ---- the sweeps --------------------------------------------------------------

def _spmv(c, u, south, north, lane):
    """``A u`` on one (8, m) slab of pairs: ``c`` the slab's (south, west,
    diagonal, east, north) coefficient pairs, ``south`` and ``north`` the
    slabs of the lines before and after each of its rows. A row's west
    neighbour at lane 0 is the last point of the line before it."""
    m = u[0].shape[-1]

    def lanes(x, edge, at, shift):
        return tuple(pltpu.roll(jnp.where(lane == at, e, v), jnp.int32(shift),
                                1) for v, e in zip(x, edge))

    west = lanes(u, south, m - 1, 1)
    east = lanes(u, north, 0, m - 1)
    y = dd_mul(c[2], u)
    for k, nb in ((1, west), (3, east), (0, south), (4, north)):
        y = dd_add(y, dd_mul(c[k], nb))
    return y


def _lane_sum(p):
    """A pair of (8, m) summed down to (8, 128) (or (8, m) below 128
    lanes), 128 lanes at a time."""
    m = p[0].shape[-1]
    if m % 128 or m == 128:
        return p
    acc = (p[0][:, :128], p[1][:, :128])
    for c in range(128, m, 128):
        acc = dd_add(acc, (p[0][:, c:c + 128], p[1][:, c:c + 128]))
    return acc


def _sweep_kernel(body, n_sc, n_in, n_out, n_dots, spmv, *refs):
    """One group: the slabs ``j = 0 .. lines - 1`` of every input pair
    through ``body``, its outputs written back, its dot terms summed."""
    refs = list(refs)
    sc_ref = refs.pop(0) if n_sc else None
    take = lambda k: [tuple(refs.pop(0) for _ in range(2))  # noqa: E731
                      for _ in range(k)]
    coef = take(5) if spmv else []
    ins = take(n_in)
    halo = take(2) if spmv else []
    outs = take(n_out)
    dot_ref = refs.pop(0) if n_dots else None
    lines, _, m = ins[0][0].shape
    shape = ins[0][0].shape[1:]
    sc = bjilu.read_scalars(sc_ref, n_sc, shape)
    sub = lax.broadcasted_iota(jnp.int32, shape, 0)
    lane = lax.broadcasted_iota(jnp.int32, shape, 1)

    def load(p, j):
        return p[0][j], p[1][j]

    def step(j, south, north, acc):
        slabs = [load(p, j) for p in ins]
        au = (_spmv([load(c, j) for c in coef], slabs[0], south, north,
                    lane) if spmv else None)
        new, dots = body(sc, au, *slabs)
        for (h, lo), (vh, vl) in zip(outs, new):
            h[j], lo[j] = vh, vl
        return tuple(dd_add(a, d) for a, d in zip(acc, dots))

    zero = jnp.zeros(shape, jnp.float32)
    acc = tuple((zero, zero) for _ in range(n_dots))
    start, stop = jnp.int32(0), jnp.int32(lines)
    if not spmv:
        acc = lax.fori_loop(start, stop,
                            lambda j, a: step(j, None, None, a), acc)
    else:
        # the line before the group (row 7 of the last slab of the group
        # before) and after it (row 0 of the next one's first), zero at
        # the grid's edges; a first line's rows take their south from the
        # last slab one row on, a last line's their north from the first
        i = pl.program_id(0)
        u = ins[0]

        def edge(p, ok):
            return tuple(jnp.where(ok, v[...], 0.0) for v in p)

        prev = edge(halo[0], i > 0)
        nxt = edge(halo[1], i < pl.num_programs(0) - 1)
        first, last = load(u, start), load(u, jnp.int32(lines - 1))
        south0 = tuple(pltpu.roll(jnp.where(sub == 7, a, b), jnp.int32(1), 0)
                       for a, b in zip(prev, last))
        north1 = tuple(pltpu.roll(jnp.where(sub == 0, a, b), jnp.int32(7), 0)
                       for a, b in zip(nxt, first))
        if lines == 1:
            acc = step(start, south0, north1, acc)
        else:
            acc = step(start, south0, load(u, jnp.int32(1)), acc)
            acc = lax.fori_loop(
                jnp.int32(1), jnp.int32(lines - 1),
                lambda j, a: step(j, load(u, j - 1), load(u, j + 1), a), acc)
            acc = step(jnp.int32(lines - 1), load(u, jnp.int32(lines - 2)),
                       north1, acc)
    for k, a in enumerate(acc):
        dot_ref[2 * k], dot_ref[2 * k + 1] = _lane_sum(a)


def sweep(body, pairs, scalars=None, n_out=0, n_dots=0, coef=None,
          reuse=None, name="bcgs_dd_sweep", interpret: bool = False):
    """One streamed pass, a group a grid step, an (8, m) slab at a time:
    ``body(scalars, au, *slabs) -> (outputs, dot terms)`` on pairs.
    ``pairs`` are planes ``(groups, lines, 8, m)``; with ``coef``, the
    operator's five coefficient planes, ``au`` is ``A`` times the first.
    ``scalars``: a float32 array of (hi, lo) scalar pairs (SMEM).
    ``reuse`` maps an output to the input pair whose buffers it is
    written over (a slab is read before it is written; not with
    ``coef``, whose SpMV reads its neighbours' slabs). Returns the
    ``n_out`` output pairs and the ``n_dots`` sums, fp64."""
    groups, lines, g, m = pairs[0][0].shape
    n_sc = 0 if scalars is None else scalars.shape[0] // 2
    z = lambda i: i * 0  # noqa: E731 - int32 block indices under x64
    plane = pl.BlockSpec((None, lines, g, m),
                         lambda i: (i, z(i), z(i), z(i)))
    specs = [bjilu.smem_spec(scalars)] if n_sc else []
    args = [scalars] if n_sc else []
    if coef is not None:
        args += [a for p in coef for a in p]
    first = len(args)           # the first pair's hi plane
    args += [a for p in pairs for a in p]
    specs += [plane] * (len(args) - len(specs))
    if coef is not None:
        u = pairs[0]
        before = pl.BlockSpec((None, None, g, m), lambda i: (
            jnp.maximum(i - 1, 0), z(i) + lines - 1, z(i), z(i)))
        after = pl.BlockSpec((None, None, g, m), lambda i: (
            jnp.minimum(i + 1, groups - 1), z(i), z(i), z(i)))
        specs += [before, before, after, after]
        args += [u[0], u[1], u[0], u[1]]
    mm = 128 if m % 128 == 0 else m
    out_shape = [jax.ShapeDtypeStruct((groups, lines, g, m),
                                      jnp.float32)] * (2 * n_out)
    out_specs = [plane] * (2 * n_out)
    if n_dots:
        out_shape.append(jax.ShapeDtypeStruct((groups, 2 * n_dots, g, mm),
                                              jnp.float32))
        out_specs.append(pl.BlockSpec((None, 2 * n_dots, g, mm),
                                      lambda i: (i, z(i), z(i), z(i))))
    aliases = {}
    for out, k in (reuse or {}).items():
        aliases[first + 2 * k] = 2 * out
        aliases[first + 2 * k + 1] = 2 * out + 1
    outs = pl.pallas_call(
        functools.partial(_sweep_kernel, body, n_sc, len(pairs), n_out,
                          n_dots, coef is not None),
        grid=(groups,),
        in_specs=specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        name=name,
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=bjilu.VMEM_LIMIT),
        interpret=interpret,
    )(*args)
    new = [tuple(outs[k:k + 2]) for k in range(0, 2 * n_out, 2)]
    dots = None
    if n_dots:
        parts = outs[-1]
        dots = jnp.sum(from_pair(parts[:, 0::2], parts[:, 1::2]),
                       axis=(0, 2, 3))
    return new, dots


def scalar_pairs(*vals):
    """fp64 scalars as the float32 (hi, lo) array the sweeps read."""
    return jnp.concatenate([jnp.stack(to_pair(jnp.asarray(v, jnp.float64)))
                            for v in vals])


# ---- the loop's steps -------------------------------------------------------
# Each takes and returns pairs of planes; the dots come back fp64.

def planes(v, lines: int, m: int):
    """An fp64 vector of the device's rows as a pair of planes."""
    return to_pair(bjilu.lines_major(v, GROUP, lines, m))


def _update_p(sc, r, p, v):
    beta, omega = sc
    return dd_add(r, dd_mul(beta, dd_add(p, dd_neg(dd_mul(omega, v)))))


def _update_s(sc, r, v):
    (alpha,) = sc
    return dd_add(r, dd_neg(dd_mul(alpha, v)))


def residual(coef, x, b, interpret=False):
    """``r = b - A x`` with ``r.r`` and ``b.b``."""
    def body(sc, au, u, v):
        r = dd_add(v, dd_neg(au))
        return [r], [dd_mul(r, r), dd_mul(v, v)]

    (r,), (rr, bb) = sweep(body, [x, b], n_out=1, n_dots=2, coef=coef,
                           name="bcgs_dd_init_pallas", interpret=interpret)
    return r, rr, bb


def norm2(b, interpret=False):
    """``b.b``."""
    _, (bb,) = sweep(lambda sc, au, v: ([], [dd_mul(v, v)]), [b], n_dots=1,
                     name="bcgs_dd_init_pallas", interpret=interpret)
    return bb


def apply_dot(coef, u, w, interpret=False):
    """S1's ``v = A u`` with ``w.v`` (``w`` the shadow residual)."""
    (v,), (wv,) = sweep(lambda sc, au, u, w: ([au], [dd_mul(w, au)]),
                        [u, w], n_out=1, n_dots=1, coef=coef,
                        name="bcgs_dd_spmv_pallas", interpret=interpret)
    return v, wv


def apply_dots(coef, u, s, interpret=False):
    """S2's ``t = A u`` with ``t.s``, ``t.t`` and ``s.s``."""
    (t,), dots = sweep(
        lambda sc, au, u, s: ([au], [dd_mul(au, s), dd_mul(au, au),
                                     dd_mul(s, s)]),
        [u, s], n_out=1, n_dots=3, coef=coef, name="bcgs_dd_spmv_pallas",
        interpret=interpret)
    return t, dots


def update(x, phat, shat, s, t, rhat, scalars, interpret=False):
    """S3: ``x + alpha p^ + omega s^`` over ``x`` and ``s - omega t``
    over ``s``, with ``r^.r`` and ``r.r`` of the new residual ``r``."""
    def body(sc, au, x, ph, sh, s, t, rh):
        alpha, omega = sc
        xn = dd_add(x, dd_add(dd_mul(alpha, ph), dd_mul(omega, sh)))
        r = dd_add(s, dd_neg(dd_mul(omega, t)))
        return [xn, r], [dd_mul(rh, r), dd_mul(r, r)]

    (x, r), (rr, r2) = sweep(body, [x, phat, shat, s, t, rhat], scalars,
                             n_out=2, n_dots=2, reuse={0: 0, 1: 3},
                             name="bcgs_dd_update_pallas",
                             interpret=interpret)
    return x, r, rr, r2


# ---- the loop --------------------------------------------------------------

def solve(lines, m, op_arrays, pc_arrays, b, x0, rtol, atol, maxit,
          *, offsets, zero_guess, monitor=None, dtol=None,
          interpret=False):
    """Right-preconditioned BiCGStab to ``krylov.bcgs_kernel``'s contract:
    ``(x, iterations, rnorm, reason, hist)``, ``x`` fp64 in its own row
    order, ``pc_arrays`` the ILU(0) kernel's stack. ``x0`` is zero where
    ``zero_guess``."""
    from .krylov import _limit_omega
    from .cg_plans import _dmax, _mon0, _reason

    (dia,) = op_arrays
    coef = [planes(dia[:, d], lines, m) for d in np.argsort(offsets)]
    (ilu,) = pc_arrays

    def pc_apply(update, pairs, scalars, reuse):
        """``c = update(scalars, *pairs)`` written over ``pairs[reuse]``,
        and ``M^-1 c``, in one pass of the ILU(0) kernel."""
        return bjilu.apply_pallas_dd(ilu, pairs, scalars, update, reuse,
                                     interpret)

    x, bp = planes(x0, lines, m), planes(b, lines, m)
    if zero_guess:
        r, rr = bp, norm2(bp, interpret)
        bb = rr
    else:
        r, rr, bb = residual(coef, x, bp, interpret)
    tol = jnp.maximum(rtol * jnp.sqrt(bb), atol)
    rnorm = jnp.sqrt(rr)
    rhat = r
    dmax = _dmax(rnorm, dtol)
    hist = _mon0(monitor, rnorm, b.dtype)
    one = jnp.asarray(1.0, b.dtype)
    zero = (jnp.zeros_like(r[0]), jnp.zeros_like(r[0]))

    def cond(st):
        k, x, r, p, v, rho, rho_next, alpha, omega, rn, brk, hist = st
        return (rn > tol) & (rn < dmax) & (k < maxit) & ~brk

    def body(st):
        k, x, r, p, v, rho, rho_new, alpha, omega, rn, brk, hist = st
        brk = (rho_new == 0) | (omega == 0)
        beta = jnp.where(brk, 0.0,
                         (rho_new / jnp.where(rho == 0, 1.0, rho))
                         * (alpha / jnp.where(omega == 0, 1.0, omega)))
        # S1; p and then r are written over, the loop holds them no more
        p, phat = pc_apply(_update_p, [r, p, v], scalar_pairs(beta, omega),
                           1)
        v, rv = apply_dot(coef, phat, rhat, interpret)
        brk = brk | (rv == 0)
        alpha = jnp.where(brk, 0.0, rho_new / jnp.where(rv == 0, 1.0, rv))
        # S2
        s, shat = pc_apply(_update_s, [r, v], scalar_pairs(alpha), 0)
        t, (ts, tt, ss) = apply_dots(coef, shat, s, interpret)
        omega = jnp.where(tt == 0, 0.0, ts / jnp.where(tt == 0, 1.0, tt))
        omega = _limit_omega(omega, ts, tt, ss)
        # S3
        x, r, rho_next, r2 = update(x, phat, shat, s, t, rhat,
                                    scalar_pairs(alpha, omega), interpret)
        rn = jnp.sqrt(r2)
        if monitor is not None:
            hist = monitor(hist, k + 1, rn)
        return (k + 1, x, r, p, v, rho_new, rho_next, alpha, omega, rn,
                brk, hist)

    st0 = (jnp.int32(0), x, r, zero, zero, one, rr, one, one, rnorm,
           rnorm <= -1.0, hist)
    k, x, r, p, v, rho, _, alpha, omega, rnorm, brk, hist = lax.while_loop(
        cond, body, st0)
    x = bjilu.natural(from_pair(*x, b.dtype))
    return (x, k, rnorm, _reason(rnorm, tol, atol, k, maxit, brk, dmax),
            hist)
