"""fp64 as double-f32 pairs, for Pallas kernels on TPU.

Mosaic holds no fp64, and XLA:TPU only emulates it. The kernels that
carry fp64 values (solvers/bjilu.py's ILU(0) sweeps, solvers/bcgs_dd.py's
BiCGStab sweeps) hold each as an unevaluated sum ``hi + lo`` of two
float32 (Dekker's double-length arithmetic, ~48 bits of mantissa, the
precision of XLA:TPU's own fp64 emulation). A pair is a tuple
``(hi, lo)`` of equal-shaped float32 values.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

_HI_MASK = 0xFFFFF000   # sign, exponent and the top 11 mantissa bits


def two_sum(a, b):
    """``s + e == a + b`` exactly, ``s`` the rounded sum."""
    s = a + b
    v = s - a
    return s, (a - (s - v)) + (b - v)


def fast_two_sum(a, b):
    """:func:`two_sum` where ``|a| >= |b|``."""
    s = a + b
    return s, b - (s - a)


def split(a):
    """``a`` as ``hi + lo``, each of at most 12 significant bits: ``hi``
    is ``a`` with the low 12 bits of its mantissa cleared (a mask: two
    VPU operations, where Veltkamp's ``t - (t - a)``, ``t = 4097 a``,
    takes four)."""
    hi = lax.bitcast_convert_type(
        lax.bitcast_convert_type(a, jnp.uint32) & jnp.uint32(_HI_MASK),
        jnp.float32)
    return hi, a - hi


def dd_add(x, y):
    """``x + y`` of two pairs."""
    s, e = two_sum(x[0], y[0])
    return fast_two_sum(s, e + (x[1] + y[1]))


def dd_mul(x, y):
    """``x * y`` of two pairs: Dekker's product where Mosaic compiles the
    kernel, the fastest form on TPU. XLA:CPU (a kernel in interpret
    mode) contracts ``x[0] * y[0]`` into Dekker's ``ah * bh - p`` as a
    fused multiply-add, which cancels the unrounded product and loses
    the error term: there the leading product is summed from the split
    halves' exact products instead."""
    return lax.platform_dependent(x, y, mosaic=_dekker_mul,
                                  default=_summed_mul)


def _dekker_mul(x, y):
    p = x[0] * y[0]
    ah, al = split(x[0])
    bh, bl = split(y[0])
    e = ((ah * bh - p) + ah * bl + al * bh) + al * bl
    return fast_two_sum(p, e + (x[0] * y[1] + x[1] * y[0]))


def _summed_mul(x, y):
    ah, al = split(x[0])
    bh, bl = split(y[0])
    m, me = two_sum(ah * bl, al * bh)
    # |m| < 2**-10 |ah * bh|
    p, e = fast_two_sum(ah * bh, m)
    return fast_two_sum(p, e + (me + al * bl
                                + (x[0] * y[1] + x[1] * y[0])))


def dd_neg(x):
    return -x[0], -x[1]


def to_pair(v):
    """An fp64 array as a (hi, lo) float32 pair (in XLA, outside a kernel)."""
    hi = v.astype(jnp.float32)
    return hi, (v - hi.astype(v.dtype)).astype(jnp.float32)


def from_pair(hi, lo, dtype=jnp.float64):
    """The fp64 value of a (hi, lo) pair."""
    return hi.astype(dtype) + lo.astype(dtype)
