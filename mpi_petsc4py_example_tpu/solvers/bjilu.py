"""Block-Jacobi with ILU(0) blocks for five-point DIA operators.

PETSc's ``PCBJACOBI`` solves each block with ``-sub_ksp_type preonly
-sub_pc_type ilu`` by default: ILU(0) in natural ordering, not an exact
inverse. PC 'bjacobi' (solvers/pc.py) takes this sub-solve where its
dense inverses do not scale: an assembled DIA operator whose offsets are
the 2D five-point set ``{0, ±1, ±m}`` (lines of ``m`` points, ``m >= 3``,
no ``±1`` coupling across a line end), whose dense blocks would pass
the dense cap: with the block count left to the library, more rows on a
device than the cap. A dense block stack reads ``8 bs`` bytes a row per apply
(16 KiB at PETSc-sized blocks); these factors read 40.

Blocks are whole grid lines, about :data:`BLOCK_ROWS` rows each or as
many as ``-pc_bjacobi_blocks`` asks, and never straddle a device. Within a block, with ``a, w, e, s, n`` row ``k``'s
diagonal, west (``-1``), east (``+1``), south (``-m``) and north (``+m``)
coefficients and every coupling that leaves the block dropped, ILU(0)
keeps nothing outside the pattern for this stencil (its fill lands at
``±(m-1)``), so the factors are

    d_k  = a_k - w_k e_{k-1} / d_{k-1} - s_k n_{k-m} / d_{k-m}
    L    = I + (w_k / d_{k-1}) at -1 + (s_k / d_{k-m}) at -m
    U    = diag(d) + A's own e (at +1) and n (at +m).

:func:`factor` computes them on the host in fp64 (a pivot depends on the
point before it in its line and the point below it in the line before,
so they are taken along anti-diagonals of each block's lines-by-points
grid, every block at once) and ships, per block, the L multipliers, the
inverse pivots and U's off-diagonals scaled by them, grid-shaped
``(blocks, 5, lines, m)``.

:func:`apply` solves ``L U z = r`` on the device. The forward sweep runs
line by line: with ``y`` of the line below known, line ``j`` is the
first-order linear recurrence ``y_i = c_i - lw_i y_{i-1}`` along x,
solved by recursive doubling (``log2 m`` shifted multiply-adds); the
backward sweep is the same from the top line down with
``x_i = c_i - ue_i x_{i+1}``. Lines are sequential within a block and
batched over the blocks; nothing crosses a device.

The transpose ``(LU)^T z = r`` is the same pair of sweeps on another
stack (:func:`transpose`): ``U^T`` forward in ``v = D w``, with U's
off-diagonals shifted one point and one line on, then ``L^T`` backward,
scaled by the inverse pivots, with L's multipliers shifted back. Several
right-hand sides (a trailing axis) apply column by column, batched
(:func:`apply_many`).

Two implementations, chosen by :func:`build` and told apart by the stack
it placed: :func:`apply_xla` in XLA ops on the stack in the operator's
dtype (CPU, fp32), and on TPU for fp64 :func:`apply_pallas`, one Pallas
kernel over groups of up to 8 blocks held in VMEM, carrying each fp64
value as a double-f32 pair (hi + lo). XLA:TPU emulates fp64, and its
doubling steps each make a round trip through HBM: at 2048^2 the XLA
apply took 16.8 ms on a v5e chip, the kernel 1.3 ms, both within 1.1e-14
of a row-by-row numpy ILU(0).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..ops.double_f32 import dd_add, dd_mul, from_pair, to_pair

# rows a block aims at: 65,536 = one MPI rank's share of a 2048^2 grid
# on a 64-rank node, PETSc's one block per rank
BLOCK_ROWS = 65536

# rows of the (blocks, 5, lines, m) factor stack
LW, LS, DINV, UE, UN = range(5)


def five_point_width(mat) -> int:
    """``m`` when ``mat`` is stored DIA with offsets exactly
    ``{0, ±1, ±m}``, ``m >= 3``, over whole lines of ``m`` rows; else 0."""
    offs = tuple(sorted(getattr(mat, "dia_offsets", ()) or ()))
    if getattr(mat, "dia_vals", None) is None or len(offs) != 5:
        return 0
    m = offs[-1]
    if offs != (-m, -1, 0, 1, m) or m < 3 or mat.shape[0] % m:
        return 0
    return m


def block_lines(lines: int, m: int, blocks: int = 0) -> int:
    """Lines per block of a device's ``lines``: ``lines // blocks`` for a
    given block count (0 when that is no whole number of lines), else the
    largest divisor that keeps a block at most :data:`BLOCK_ROWS` rows (at
    least one line)."""
    if blocks:
        return 0 if lines % blocks else lines // blocks
    want = max(1, BLOCK_ROWS // m)
    return max(d for d in range(1, min(want, lines) + 1) if lines % d == 0)


def factor(dia: np.ndarray, m: int, lines: int,
           n: int | None = None) -> np.ndarray | None:
    """ILU(0) factors of the line blocks of a five-point operator.

    ``dia`` is the host ``(n_pad, 5)`` DIA array in offset order
    ``(-m, -1, 0, 1, m)``, its rows from ``n`` on padding; ``lines``
    the lines per block. Returns the fp64 ``(blocks, 5, lines, m)``
    stack (rows :data:`LW`, :data:`LS`, :data:`DINV`, :data:`UE`,
    :data:`UN`; padding rows pass through), or None when a ``±1``
    coupling crosses a line end, which this line-wise factor does not
    model. Raises on a zero pivot, as PETSc's ILU does."""
    n_pad = dia.shape[0]
    nb = n_pad // (lines * m)
    s, w, a, e, nn = (np.array(dia[:, k], dtype=np.float64)
                      .reshape(nb, lines, m) for k in range(5))
    if np.any(w[:, :, 0]) or np.any(e[:, :, -1]):
        return None
    a.reshape(-1)[n_pad if n is None else n:] = 1.0   # padding: identity
    s[:, 0] = 0.0           # couplings that leave the block are dropped
    nn[:, -1] = 0.0
    pw = np.zeros_like(a)
    pw[:, :, 1:] = w[:, :, 1:] * e[:, :, :-1]
    ps = np.zeros_like(a)
    ps[:, 1:] = s[:, 1:] * nn[:, :-1]
    # pivots behind a border of ones: where pw or ps is zero (a line's
    # first point, a block's first line) the border divides nothing
    D = np.ones((nb, lines + 1, m + 1))
    with np.errstate(divide="ignore", invalid="ignore"):  # checked below
        for t in range(lines + m - 1):
            j = np.arange(max(0, t - m + 1), min(lines - 1, t) + 1)
            i = t - j
            D[:, j + 1, i + 1] = (a[:, j, i] - pw[:, j, i] / D[:, j + 1, i]
                                  - ps[:, j, i] / D[:, j, i + 1])
    d = D[:, 1:, 1:]
    if not np.all(np.isfinite(d)) or np.any(d == 0):
        raise ValueError("PC 'bjacobi' ILU(0) sub-solve: zero pivot")
    out = np.zeros((nb, 5, lines, m))
    out[:, LW, :, 1:] = w[:, :, 1:] / d[:, :, :-1]
    out[:, LS, 1:] = s[:, 1:] / d[:, :-1]
    out[:, DINV] = 1.0 / d
    out[:, UE] = e / d
    out[:, UN] = nn / d
    return out


def group_size(stack_shape, ndev: int) -> int:
    """Blocks per kernel instance of :func:`apply_pallas`: the largest of
    8, 4, 2, 1 dividing a device's block count that fits
    (:func:`kernel_fits`); 0 when not even one block does."""
    nb, _, lines, m = stack_shape
    for g in (8, 4, 2, 1):
        if (nb // ndev) % g == 0 and kernel_fits(lines, g, m):
            return g
    return 0


def pallas_stack(stack: np.ndarray, g: int) -> np.ndarray:
    """The fp64 ``(blocks, 5, lines, m)`` factors as :func:`apply_pallas`
    reads them: ``(blocks // g, 10, lines, g, m)`` float32, each
    coefficient a (hi, lo) pair (:data:`PALLAS_ROWS`), the L multipliers
    and U's off-diagonals negated."""
    nb, _, lines, m = stack.shape
    out = np.empty((nb // g, 2 * len(PALLAS_ROWS), lines, g, m), np.float32)
    for k, (row, sign) in enumerate(PALLAS_ROWS):
        c = (sign * stack[:, row]).reshape(nb // g, g, lines, m)
        c = c.transpose(0, 2, 1, 3)
        hi = c.astype(np.float32)
        out[:, 2 * k] = hi
        out[:, 2 * k + 1] = (c - hi).astype(np.float32)
    return out


def use_kernel(platform: str, dtype) -> bool:
    """Whether :func:`build` places the stack for :func:`apply_pallas`:
    fp64 on TPU, which XLA:TPU only emulates."""
    return platform == "tpu" and np.dtype(dtype) == np.float64


def build(comm, mat, blocks: int = 0):
    """Factor ``mat``'s line blocks on the host and place them sharded by
    block; returns ``(stack, info)`` or None where the operator is not a
    five-point grid this path can hold (the caller keeps dense blocks).
    ``blocks`` is the count a device holds (0: blocks of about
    :data:`BLOCK_ROWS` rows); each has to be whole lines."""
    m = five_point_width(mat)
    lsize = comm.local_size(mat.shape[0])
    if not m or lsize % m or not np.issubdtype(np.dtype(mat.dtype),
                                               np.floating) \
            or np.dtype(mat.dtype).itemsize < 4:
        return None
    lines = block_lines(lsize // m, m, blocks)
    if not lines:
        return None
    t0 = time.perf_counter()
    order = np.argsort(mat.dia_offsets)
    dia = comm.host_fetch(mat.dia_vals)[:, order]
    t1 = time.perf_counter()
    stack = factor(dia, m, lines, mat.shape[0])
    if stack is None:
        return None
    t2 = time.perf_counter()
    g = (group_size(stack.shape, comm.size)
         if use_kernel(comm.platform, mat.dtype) else 0)
    host = pallas_stack(stack, g) if g else stack.astype(mat.dtype)
    placed = comm.put_axis0(host)
    placed.block_until_ready()
    t3 = time.perf_counter()
    info = {"sub_solve": "ilu0", "blocks": int(stack.shape[0]),
            "lines_per_block": int(lines), "line": int(m),
            "apply": "pallas" if g else "xla",
            "fetch_s": round(t1 - t0, 4), "factor_s": round(t2 - t1, 4),
            "place_s": round(t3 - t2, 4)}
    return placed, info


def _recurrence(a, c, reverse: bool = False):
    """``y_i = c_i + a_i y_{i-1}`` along the last axis from ``y_{-1} = 0``
    (``reverse``: ``y_i = c_i + a_i y_{i+1}`` from the end), by recursive
    doubling: after the step of shift ``s``, ``y_i = c_i + a_i y_{i-2s}``."""
    m = c.shape[-1]
    s = 1
    while s < m:
        pad = [(0, 0)] * (c.ndim - 1) + [(0, s) if reverse else (s, 0)]

        def shift(v):
            part = v[..., s:] if reverse else v[..., :m - s]
            return jnp.pad(part, pad)

        c = c + a * shift(c)
        a = a * shift(a)
        s *= 2
    return c


def apply(arrs, r, interpret: bool = False):
    """``z = U^-1 L^-1 r`` on this device's blocks, ``r`` its rows:
    :func:`apply_pallas` for a stack :func:`build` placed for it (5-D),
    else :func:`apply_xla`."""
    (coef,) = arrs
    if coef.ndim == 5:
        return apply_pallas(coef, r, interpret)
    return apply_xla(coef, r)


def apply_many(arrs, R, interpret: bool = False):
    """:func:`apply` on each column of ``R`` (rows, right-hand sides)."""
    return jax.vmap(lambda r: apply(arrs, r, interpret),
                    in_axes=1, out_axes=1)(R)


def _shift(v, axis: int, on: bool):
    """``v`` moved one place along ``axis``, a zero shifted in: index
    ``i`` takes ``i - 1`` (``on``) or ``i + 1``."""
    n = v.shape[axis]
    part = lax.slice_in_dim(v, 0, n - 1, axis=axis) if on \
        else lax.slice_in_dim(v, 1, n, axis=axis)
    pad = [(0, 0)] * v.ndim
    pad[axis] = (1, 0) if on else (0, 1)
    return jnp.pad(part, pad)


def transpose(coef):
    """The stack whose :func:`apply` is ``(LU)^-T``, from either layout
    (the kernel's carries each coefficient as two planes). With
    ``v = D w``, ``U^T w = r`` reads ``v_k = r_k - UE_{k-1} v_{k-1} -
    UN_{k-m} v_{k-m}``, and ``L^T z = D^-1 v`` reads ``z_k = DINV_k v_k
    - LW_{k+1} z_{k+1} - LS_{k+m} z_{k+m}``: the forward and backward
    sweeps, U's off-diagonals moved one point (and one line) on into
    L's slots, L's moved back into U's. Every shift stays inside a
    block's lines: what it brings in from past an edge is zero."""
    w = 2 if coef.ndim == 5 else 1
    pts, lns = coef.ndim - 1, 2

    def plane(k):
        return coef[:, w * k:w * (k + 1)]

    return jnp.concatenate([_shift(plane(UE), pts, True),
                            _shift(plane(UN), lns, True),
                            plane(DINV),
                            _shift(plane(LW), pts, False),
                            _shift(plane(LS), lns, False)], axis=1)


def apply_xla(coef, r):
    """:func:`apply` in XLA ops on the ``(blocks, 5, lines, m)`` stack in
    the operator's dtype."""
    nb, _, lines, m = coef.shape
    R = r.reshape(nb, lines, m)

    def row(k, j):
        return lax.dynamic_index_in_dim(coef[:, k], j, axis=1,
                                        keepdims=False)

    def line(v, j):
        return lax.dynamic_index_in_dim(v, j, axis=1, keepdims=False)

    def forward(j, carry):
        below, Y = carry
        y = _recurrence(-row(LW, j), line(R, j) - row(LS, j) * below)
        return y, lax.dynamic_update_index_in_dim(Y, y, j, 1)

    def backward(t, carry):
        j = lines - 1 - t
        above, X = carry
        c = row(DINV, j) * line(Y, j) - row(UN, j) * above
        x = _recurrence(-row(UE, j), c, reverse=True)
        return x, lax.dynamic_update_index_in_dim(X, x, j, 1)

    edge = jnp.zeros((nb, m), r.dtype)
    _, Y = lax.fori_loop(0, lines, forward, (edge, jnp.zeros_like(R)))
    _, X = lax.fori_loop(0, lines, backward, (edge, jnp.zeros_like(R)))
    return X.reshape(-1)


# ---- the TPU kernel: fp64 as double-f32 pairs ------------------------------
#
# Mosaic holds no fp64, and XLA:TPU's emulated fp64 runs each doubling step
# of apply_xla as its own fusion through HBM. The kernel keeps a group of
# blocks' lines in VMEM and carries every value as a double-f32 pair
# (ops/double_f32.py).

# the kernel's coefficient planes, each a (hi, lo) pair: (row of the fp64
# stack, sign). Negated so that both sweeps only add products:
# y = r + (-ls) y_below + (-lw) y_west and
# x = dinv y + (-un) x_above + (-ue) x_east
PALLAS_ROWS = ((LW, -1.0), (LS, -1.0), (DINV, 1.0), (UE, -1.0), (UN, -1.0))
_AW, _NS, _PD, _AE, _NN = range(5)
# VMEM for the kernel's double-buffered blocks (of the 128 MiB of a v5e
# core; fp64 BCGS's p-update call at 2048^2 in groups of 8 blocks of 32
# lines asks all of it); the limit asked of Mosaic adds room for its
# temporaries
KERNEL_VMEM = 80 << 20
VMEM_LIMIT = 96 << 20


def smem_spec(scalars):
    """The BlockSpec of a float32 array of scalars read whole from SMEM
    (its block index int32, where x64 would make a literal 0 an int64
    that Mosaic rejects)."""
    return pl.BlockSpec(scalars.shape, lambda i: (i * 0,),
                        memory_space=pltpu.SMEM)


def read_scalars(sc_ref, n: int, shape) -> tuple:
    """The ``n`` (hi, lo) scalar pairs of ``sc_ref`` (SMEM), each half
    broadcast to ``shape``: Mosaic bitcasts (``split``) vectors only."""
    return tuple(tuple(jnp.broadcast_to(sc_ref[2 * k + h], shape)
                       for h in range(2)) for k in range(n))


def kernel_fits(lines: int, g: int, m: int, io_planes: int = 4) -> bool:
    """Whether :func:`apply_pallas_dd`'s blocks for groups of ``g`` blocks
    of ``lines`` lines of ``m``, two buffers each, fit
    :data:`KERNEL_VMEM`: 10 coefficient planes and ``io_planes`` float32
    planes in and out (4 for ``z = M^-1 r``; 10 where it reads r, p, v
    and writes p and ``M^-1 p``)."""
    planes = 2 * len(PALLAS_ROWS) + io_planes
    return 2 * planes * lines * g * m * 4 <= KERNEL_VMEM


def _dd_recurrence(a, c, reverse: bool):
    """:func:`_recurrence` on (hi, lo) pairs along the lanes, the shifts
    lane rotations with the wrapped lanes zeroed."""
    shape = c[0].shape
    m = shape[-1]
    lane = lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    s = 1
    while s < m:
        keep = lane < m - s if reverse else lane >= s

        def shift(v):
            v = pltpu.roll(v, jnp.int32(m - s if reverse else s),
                           len(shape) - 1)
            return jnp.where(keep, v, 0.0)

        c = dd_add(c, dd_mul(a, (shift(c[0]), shift(c[1]))))
        if 2 * s < m:
            a = dd_mul(a, (shift(a[0]), shift(a[1])))
        s *= 2
    return c


def _ilu_kernel(n_sc, n_in, combine, *refs):
    """One group of blocks, lines-major ``(lines, g, m)``: the forward
    sweep writes y into the outputs line by line, the backward sweep
    reads each line's y back and overwrites it with x. With ``combine``
    the right-hand side of each line is ``combine(scalars, *lines)`` of
    the ``n_in`` input pairs and ``n_sc`` scalar pairs (from SMEM), and is
    written out too; without, it is the one input pair."""
    sc_ref, refs = (refs[0], refs[1:]) if n_sc else (None, refs)
    coef_ref, refs = refs[0], refs[1:]
    ins = [refs[2 * k:2 * k + 2] for k in range(n_in)]
    outs = refs[2 * n_in:]
    zh_ref, zl_ref = outs[-2:]
    lines = zh_ref.shape[0]
    sc = read_scalars(sc_ref, n_sc, zh_ref.shape[1:])

    def co(k, j):
        return coef_ref[2 * k, j], coef_ref[2 * k + 1, j]

    zero = jnp.zeros(zh_ref.shape[1:], jnp.float32)

    def forward(j, below):
        rhs = [(h[j], lo[j]) for h, lo in ins]
        if combine is None:
            (rhs,) = rhs
        else:
            rhs = combine(sc, *rhs)
            outs[0][j], outs[1][j] = rhs
        c = dd_add(rhs, dd_mul(co(_NS, j), below))
        y = _dd_recurrence(co(_AW, j), c, reverse=False)
        zh_ref[j], zl_ref[j] = y
        return y

    def backward(t, above):
        j = jnp.int32(lines - 1) - t
        c = dd_add(dd_mul(co(_PD, j), (zh_ref[j], zl_ref[j])),
                   dd_mul(co(_NN, j), above))
        x = _dd_recurrence(co(_AE, j), c, reverse=True)
        zh_ref[j], zl_ref[j] = x
        return x

    # int32 bounds: under x64 a Python int would index as i64, which
    # Mosaic rejects
    start, stop = jnp.int32(0), jnp.int32(lines)
    lax.fori_loop(start, stop, forward, (zero, zero))
    lax.fori_loop(start, stop, backward, (zero, zero))


def lines_major(v, g: int, lines: int, m: int):
    """A vector of this device's rows in the kernel's layout: each group
    of ``g`` blocks ``(lines, g, m)``, line ``j`` of its blocks together."""
    return v.reshape(-1, g, lines, m).transpose(0, 2, 1, 3)


def natural(V):
    """:func:`lines_major`'s inverse: the rows in their own order."""
    return V.transpose(0, 2, 1, 3).reshape(-1)


def apply_pallas_dd(coef, pairs, scalars=None, combine=None, reuse=None,
                    interpret: bool = False):
    """The kernel on (hi, lo) float32 pairs held in its layout
    (:func:`lines_major`, ``(groups, lines, g, m)`` each): ``z`` for the
    one pair of ``pairs``, or, with ``combine``, ``[c, z]`` for the
    right-hand side ``c = combine(scalars, *pairs)`` formed line by line
    in the forward sweep (``scalars``: a float32 array of (hi, lo) scalar
    pairs, read from SMEM), written over ``pairs[reuse]`` where given
    (a line is read before it is written)."""
    ng, planes, lines, g, m = coef.shape
    # block indices stay int32 (i * 0), where x64 would make a literal 0
    # an int64 that Mosaic rejects
    vec = pl.BlockSpec((None, lines, g, m),
                       lambda i: (i, i * 0, i * 0, i * 0))
    n_sc = 0 if scalars is None else scalars.shape[0] // 2
    sc_specs = [smem_spec(scalars)] if n_sc else []
    n_out = 2 if combine is None else 4
    first = len(sc_specs) + 1 + 2 * (reuse or 0)
    outs = pl.pallas_call(
        functools.partial(_ilu_kernel, n_sc, len(pairs), combine),
        grid=(ng,),
        in_specs=sc_specs + [pl.BlockSpec((None, planes, lines, g, m),
                                          lambda i: (i,) + (i * 0,) * 4)]
        + [vec] * (2 * len(pairs)),
        out_specs=[vec] * n_out,
        out_shape=[jax.ShapeDtypeStruct((ng, lines, g, m),
                                        jnp.float32)] * n_out,
        input_output_aliases=({} if reuse is None
                              else {first: 0, first + 1: 1}),
        name="bjacobi_ilu0_pallas",
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(*([scalars] if n_sc else []), coef, *(a for p in pairs for a in p))
    outs = [tuple(outs[k:k + 2]) for k in range(0, n_out, 2)]
    return outs[0] if combine is None else outs


def apply_pallas(coef, r, interpret: bool = False):
    """:func:`apply` as one Pallas kernel over groups of ``g`` blocks:
    ``coef`` is :func:`pallas_stack`'s ``(groups, 10, lines, g, m)``,
    ``r`` fp64. XLA splits r into (hi, lo) float32 lines-major and joins
    the result back (:func:`apply_pallas_dd` takes the pairs as they
    are)."""
    _, _, lines, g, m = coef.shape
    z = apply_pallas_dd(coef, [to_pair(lines_major(r, g, lines, m))],
                        interpret=interpret)
    return natural(from_pair(*z, r.dtype))
