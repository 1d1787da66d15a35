"""Geometric multigrid V-cycle preconditioner for structured Poisson.

Beyond-parity performance component (the reference's PETSc stack exposes
PCMG/GAMG the same way behind ``setFromOptions`` — /root/reference/test.py:46
[external]): a matrix-free V-cycle on the 7-point 3D Poisson operator, used
as a preconditioner inside CG. Damped-Jacobi smoothing (ω = 2/3), full
coarsening by 2× per level.

Transfer operators (round 4 — replaces the round-3 ``jax.image.resize``
pair, measured 50 CG its at 32³ where this scheme needs 11):

* prolongation ``P``: per-axis linear interpolation on the cell-pair grid
  with ZERO ghosts at the global boundary (Dirichlet-consistent — the
  eliminated-boundary unit stencil behaves as a grid with zero ghost
  values);
* restriction ``R = (1/2)·Pᵀ`` (per-axis scale ``(4)^{1/3}/2``, so the
  3-axis product carries the h²-ratio factor 4 of the residual equation
  under the level-independent unit stencil).

Because R ∝ Pᵀ and the pre/post smoothers are equal-count damped Jacobi,
the V-cycle is a SYMMETRIC linear operator — a valid CG preconditioner
(measured: 11/12/14 its at 32³/64³/128³, rtol 1e-8, vs 50+ for any
non-adjoint pairing).

Distribution (round 4 — replaces the round-3 gather-and-replicate cycle):
the cycle runs z-slab-decomposed inside the same shard_map program as the
Krylov loop. Every level keeps the slab decomposition while its local
plane count stays even; smoothing, restriction and prolongation each touch
only the two neighbouring boundary planes, exchanged with one
``lax.ppermute`` ring shift each way — the stencil-SpMV halo pattern
(models/stencil.py). Once the slab thins below two planes the remaining
tiny levels are ``all_gather``-ed (≤ a few thousand entries), cycled
locally, and the local slab of the correction sliced back. Slab and
replicated cycles compute the SAME arithmetic, so solves are
device-count-independent (tests/test_mg_slab.py asserts this).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_OMEGA = 2.0 / 3.0
# one axis of R = (1/2)·Pᵀ: the 3-axis product must scale the restricted
# residual by 4 (= h_c²/h_f² under the level-independent unit stencil) on
# top of the weight-2-per-axis adjoint, i.e. (2s)³ = 4
_RSCALE = 4.0 ** (1.0 / 3.0) / 2.0


# The smoother/residual bodies route through the fused Pallas pipeline when
# the level's plane shape supports it (fp32, nx%128==0, ny%8==0 — true for
# the fine levels of the production 512³/256³ grids): one streamed pass per
# sweep (~3.3 HBM passes) instead of a 21-pass jnp stencil apply plus an
# XLA update chain. The jnp body (single shared definition,
# models/stencil.py) covers everything else — coarse levels, f64, CPU.
# Each Pallas call is named by its level (``level`` below: 0 the finest),
# so the device trace splits the cycle's kernel time by level.

def _level_name(kernel, level):
    """``<kernel>_l<level>``, the Pallas kernel's name at an MG level; None
    (the kernel's own name) where no level is given."""
    return None if level is None else f"{kernel.__name__}_l{level}"


def _stencil7(u, halo_lo, halo_hi, platform=None):
    """7-point Dirichlet Laplacian on a z-slab with explicit z-halo planes
    (jnp body; the Pallas fast paths live in _sweep/_residual).

    ``platform`` is the SOLVE MESH's platform (comm.platform) — the Mosaic
    gate must not key on the process default backend (ADVICE r4: a
    CPU-device mesh in a TPU-capable process would otherwise attempt
    Mosaic kernels on CPU devices)."""
    from ..models.stencil import StencilPoisson3D
    from ..ops.pallas_stencil import pallas_supported, stencil3d_apply_pallas
    lz, ny, nx = u.shape
    if pallas_supported(ny, nx, u.dtype, platform):
        return stencil3d_apply_pallas(u, halo_lo[None], halo_hi[None],
                                      lz, ny, nx)
    return StencilPoisson3D._stencil7_jnp(u, halo_lo, halo_hi)


def _sweep(u, f, halo_lo, halo_hi, omega: float = _OMEGA, platform=None,
           level=None):
    """One damped-Jacobi sweep ``u + (ω/6)(f - A u)`` — fused Pallas pass
    where supported."""
    from ..ops.pallas_stencil import pallas_supported, stencil3d_smooth_pallas
    lz, ny, nx = u.shape
    if pallas_supported(ny, nx, u.dtype, platform):
        return stencil3d_smooth_pallas(
            u, f, halo_lo[None], halo_hi[None], lz, ny, nx, omega / 6.0,
            name=_level_name(stencil3d_smooth_pallas, level))
    return u + (omega / 6.0) * (f - _stencil7(u, halo_lo, halo_hi, platform))


def _residual(u, f, halo_lo, halo_hi, platform=None, level=None):
    """Residual ``f - A u`` — fused Pallas pass where supported."""
    from ..ops.pallas_stencil import (pallas_supported,
                                      stencil3d_residual_pallas)
    lz, ny, nx = u.shape
    if pallas_supported(ny, nx, u.dtype, platform):
        return stencil3d_residual_pallas(
            u, f, halo_lo[None], halo_hi[None], lz, ny, nx,
            name=_level_name(stencil3d_residual_pallas, level))
    return f - _stencil7(u, halo_lo, halo_hi, platform)


def _zeros_plane(u):
    return jnp.zeros_like(u[0])


def _no_exchange(u):
    """Replicated / single-device halo: zero planes (global Dirichlet)."""
    z = _zeros_plane(u)
    return z, z


def _mk_exchange(axis, ndev):
    """Boundary-plane halo exchange along the z-slab ring — the single
    shared definition (models/stencil.py), used here by smoothing,
    restriction and prolongation at every level."""
    if ndev == 1:
        return _no_exchange
    from ..models.stencil import make_plane_exchange
    return make_plane_exchange(axis, ndev)


def cheby_omegas(degree: int, b: float = 2.0, a_frac: float = 0.25):
    """Per-sweep damping factors realizing a degree-``degree`` Chebyshev
    polynomial smoother as plain damped-Jacobi sweeps (round 5).

    With the UNIFORM diagonal D = 6I, every sweep ``u + (ω/6)(f - A u)``
    is a polynomial factor ``(I - ω·Ã)`` in ``Ã = A/6``; choosing the ω_j
    as inverses of the Chebyshev-T_degree roots on ``[a_frac·b, b]``
    (⊂ spectrum(Ã) ⊂ (0, 2)) makes the product the min-max-optimal
    residual polynomial on that interval — the textbook Chebyshev smoother
    at EXACTLY the cost of the same number of Jacobi sweeps: same fused
    Pallas pass per sweep, no auxiliary carry vector, no reductions, no
    setup eigenestimate (the stencil's λ_max(Ã) < 2 is analytic). The
    factors commute (all polynomials in A), so pre/post applying the same
    ω-set in any order keeps the V-cycle a symmetric operator (module
    docstring) — a valid CG preconditioner.

    Measured (CG+MG to rtol 1e-8, fp64 CPU mesh): 32³/64³/128³ take
    9/11/12 iterations vs 11/12/14 with the fixed-ω Jacobi pair — same
    cycle cost, ~10-18% fewer cycles.
    """
    import math
    lo = a_frac * b
    mid, half = (b + lo) / 2.0, (b - lo) / 2.0
    roots = [mid + half * math.cos(math.pi * (2 * j - 1) / (2 * degree))
             for j in range(1, degree + 1)]
    return tuple(1.0 / r for r in roots)


def _smooth(u, f, iters: int, exchange, omega=_OMEGA, platform=None,
            level=None):
    """Damped-Jacobi sweeps for the unit 7-point stencil; ``omega`` may be
    a scalar (``iters`` equal sweeps, fori_loop) or a tuple of per-sweep
    factors (a Chebyshev-root schedule, unrolled — see cheby_omegas).

    A 2-sweep schedule on a SINGLE-DEVICE slab runs both sweeps in ONE
    streamed Pallas pass (stencil3d_smooth_pair_pallas: ~3.2 HBM passes
    vs ~6.6 for two separate fused sweeps — round 5)."""
    if isinstance(omega, (tuple, list)):
        if len(omega) == 2 and exchange is _no_exchange:
            from ..ops.pallas_stencil import (pallas_supported,
                                              stencil3d_smooth_pair_pallas)
            lz, ny, nx = u.shape
            if pallas_supported(ny, nx, u.dtype, platform):
                try:
                    return stencil3d_smooth_pair_pallas(
                        u, f, lz, ny, nx, float(omega[0]) / 6.0,
                        float(omega[1]) / 6.0,
                        name=_level_name(stencil3d_smooth_pair_pallas,
                                         level))
                except ValueError:
                    pass    # no feasible >=2 z-chunk: two separate sweeps
        for w in omega:
            lo, hi = exchange(u)
            u = _sweep(u, f, lo, hi, w, platform, level)
        return u
    if iters <= 0:
        return u

    def body(_, u):
        lo, hi = exchange(u)
        return _sweep(u, f, lo, hi, omega, platform, level)

    return lax.fori_loop(0, iters, body, u)


def _smooth0(f, iters: int, exchange, omega=_OMEGA, platform=None,
             level=None):
    """Sweeps from a ZERO initial guess: the first sweep is the closed form
    ``u = (ω/6) f`` — no stencil apply, no halo exchange. A scalar ω keeps
    the remaining sweeps in a fori_loop (the 20-sweep coarse solve must
    not unroll); a Chebyshev ω tuple unrolls its (short) remainder."""
    if isinstance(omega, (tuple, list)):
        ws = tuple(float(w) for w in omega)
        if not ws:
            return jnp.zeros_like(f)
        if len(ws) == 2 and exchange is _no_exchange:
            # both sweeps collapse to ONE stencil apply on f itself:
            # u = (w1+w2) f - w1 w2 (A f), one streamed pass (round 5)
            from ..ops.pallas_stencil import (pallas_supported,
                                              stencil3d_smooth0_pair_pallas)
            lz, ny, nx = f.shape
            if pallas_supported(ny, nx, f.dtype, platform):
                return stencil3d_smooth0_pair_pallas(
                    f, lz, ny, nx, ws[0] / 6.0, ws[1] / 6.0,
                    name=_level_name(stencil3d_smooth0_pair_pallas, level))
        return _smooth((ws[0] / 6.0) * f, f, 0, exchange, ws[1:], platform,
                       level)
    if iters <= 0:
        return jnp.zeros_like(f)
    return _smooth((omega / 6.0) * f, f, iters - 1, exchange, omega,
                   platform, level)


def _r1d(f, ax: int, lo=None, hi=None):
    """One axis of ``R = (1/2)·Pᵀ``::

        coarse[i] = s·(0.75·(f[2i] + f[2i+1]) + 0.25·(f[2i-1] + f[2i+2]))

    with zero ghosts; ``lo``/``hi`` (the neighbouring slabs' boundary
    planes: f[-1] and f[2m]) override the ghosts in the sharded z pass."""
    sh = f.shape
    m = sh[ax] // 2
    g = f.reshape(sh[:ax] + (m, 2) + sh[ax + 1:])
    ev = jnp.take(g, 0, axis=ax + 1)          # f[2i]
    od = jnp.take(g, 1, axis=ax + 1)          # f[2i+1]
    if lo is None:
        lo = jnp.zeros_like(jnp.take(od, 0, axis=ax))
    if hi is None:
        hi = jnp.zeros_like(lo)
    odm = jnp.concatenate([jnp.expand_dims(lo, ax),
                           lax.slice_in_dim(od, 0, m - 1, axis=ax)], axis=ax)
    evp = jnp.concatenate([lax.slice_in_dim(ev, 1, m, axis=ax),
                           jnp.expand_dims(hi, ax)], axis=ax)
    return _RSCALE * (0.75 * (ev + od) + 0.25 * (odm + evp))


def _p1d(c, ax: int, lo=None, hi=None):
    """One axis of the linear prolongation ``P``::

        fine[2i]   = 0.75·c[i] + 0.25·c[i-1]
        fine[2i+1] = 0.75·c[i] + 0.25·c[i+1]

    with zero ghosts; ``lo``/``hi`` are the neighbouring slabs' boundary
    coarse planes in the sharded z pass."""
    m = c.shape[ax]
    if lo is None:
        lo = jnp.zeros_like(jnp.take(c, 0, axis=ax))
    if hi is None:
        hi = jnp.zeros_like(lo)
    cm = jnp.concatenate([jnp.expand_dims(lo, ax),
                          lax.slice_in_dim(c, 0, m - 1, axis=ax)], axis=ax)
    cp = jnp.concatenate([lax.slice_in_dim(c, 1, m, axis=ax),
                          jnp.expand_dims(hi, ax)], axis=ax)
    a = 0.75 * c + 0.25 * cm
    b = 0.75 * c + 0.25 * cp
    out = jnp.stack([a, b], axis=ax + 1)
    sh = list(c.shape)
    sh[ax] *= 2
    return out.reshape(sh)


# per-axis-length banded transfer matrices for the einsum path (host f64,
# converted to the requested dtype at each call)
_TMAT_CACHE: dict = {}


def _tmat(n: int, dtype, scale: float = _RSCALE):
    """(n, n/2) one-axis restriction matrix: column i carries the weights
    scale·[1/4, 3/4, 3/4, 1/4] on rows [2i-1, 2i+2] (zero ghosts).
    Its transpose is the one-axis prolongation (the R = (1/2)Pᵀ pair, per
    axis); ``scale=1`` gives P's own weights, exact in any float dtype.
    A 512-wide axis costs 512×256×4B = 512 KB as a constant."""
    # cache HOST numpy, convert per call: caching a jnp array built inside
    # a trace would leak that trace's tracer into every later program
    Wn = _TMAT_CACHE.get(n)
    if Wn is None:
        import numpy as np
        Wn = np.zeros((n, n // 2))
        i = np.arange(n // 2)
        Wn[2 * i, i] = 0.75
        Wn[2 * i + 1, i] = 0.75
        Wn[2 * i[1:] - 1, i[1:]] = 0.25
        Wn[2 * i[:-1] + 2, i[:-1]] = 0.25
        _TMAT_CACHE[n] = Wn
    return jnp.asarray(scale * Wn, dtype)


def _mm_ok(dtype, platform=None) -> bool:
    """The einsum transfer path needs matmuls at working precision: CPU
    always; TPU for f32 (f64 matmuls there carry ~f32 accumulation).
    ``platform`` is the solve mesh's platform (ADVICE r4), defaulting to
    the process backend."""
    import jax
    return ((platform or jax.default_backend()) == "cpu"
            or jnp.dtype(dtype) == jnp.dtype(jnp.float32))


def _hp(*args, **kw):
    import jax
    return jnp.einsum(*args, precision=jax.lax.Precision.HIGHEST, **kw)


def _restrict_mm(r, lo, hi):
    """R as three banded-matrix einsums riding the MXU (~2.6 HBM passes
    total) — the staged slicing chains cost ~17 passes at 512³ (measured),
    a 3D conv hits a pathological XLA:TPU 5-D layout (68 GB copy), and a
    single-channel 2D conv is MXU-degenerate; small dense (n, n/2)
    constants with 4 nonzeros per column are the shape XLA handles well."""
    nz, ny, nx = r.shape
    dt = r.dtype
    out = _hp("zyx,zc->cyx", r, _tmat(nz, dt))
    out = _hp("cyx,yd->cdx", out, _tmat(ny, dt))
    out = _hp("cdx,xe->cde", out, _tmat(nx, dt))
    # the z-halo planes touch only the first/last coarse plane, each with
    # total z-weight _RSCALE/4; y/x still restrict
    if lo is not None:
        c = _hp("yx,yd->dx", lo, _tmat(ny, dt))
        c = _hp("dx,xe->de", c, _tmat(nx, dt))
        out = out.at[0].add(jnp.asarray(_RSCALE * 0.25, dt) * c)
    if hi is not None:
        c = _hp("yx,yd->dx", hi, _tmat(ny, dt))
        c = _hp("dx,xe->de", c, _tmat(nx, dt))
        out = out.at[-1].add(jnp.asarray(_RSCALE * 0.25, dt) * c)
    return out


def _prolong_mm(e, lo, hi):
    """P as the transposed einsums — the exact adjoint of
    :func:`_restrict_mm` up to the global 1/2: P = 2·Rᵀ, and since the
    three W factors carry _RSCALE each, the rescale is
    2/(_RSCALE³·_RSCALE³)·_RSCALE³ = 1/_RSCALE³ (= 2, as _RSCALE³ = 1/2)."""
    nzc, nyc, nxc = e.shape
    dt = e.dtype
    out = _hp("cyx,zc->zyx", e, _tmat(2 * nzc, dt))
    out = _hp("zyx,dy->zdx", out, _tmat(2 * nyc, dt))
    out = _hp("zdx,ex->zde", out, _tmat(2 * nxc, dt))
    out = out * (jnp.asarray(1.0, dt) / jnp.asarray(_RSCALE ** 3, dt))
    # coarse z-halo planes contribute quarter-weight to the boundary fine
    # planes; y/x still prolong (1/_RSCALE² removes their R scaling)
    if lo is not None:
        c = _hp("yx,yd->dx", lo, _tmat(2 * nyc, dt).T)
        c = _hp("dx,xe->de", c, _tmat(2 * nxc, dt).T)
        out = out.at[0].add(jnp.asarray(0.25 / _RSCALE ** 2, dt) * c)
    if hi is not None:
        c = _hp("yx,yd->dx", hi, _tmat(2 * nyc, dt).T)
        c = _hp("dx,xe->de", c, _tmat(2 * nxc, dt).T)
        out = out.at[-1].add(jnp.asarray(0.25 / _RSCALE ** 2, dt) * c)
    return out


def _restrict(r, lo=None, hi=None, platform=None):
    """Full 3-axis restriction; z first (the only axis needing halos)."""
    if _mm_ok(r.dtype, platform):
        return _restrict_mm(r, lo, hi)
    return _r1d(_r1d(_r1d(r, 0, lo, hi), 1), 2)


def _residual_restrict_fused(u, f, platform=None, level=None):
    """Fine residual + full restriction fused INTO the residual kernel.

    Round 6: where the level shape allows it
    (ops/pallas_stencil.fullrestrict_supported) the ENTIRE 3-axis
    restriction runs inside the residual kernel's VMEM-resident chunks
    (stencil3d_residual_restrict_pallas — in-kernel MXU matmuls with the
    same _tmat weights): the kernel reads u and f once and writes only the
    (lz/2, ny/2, nx/2) coarse RHS, so neither the fine residual nor the
    half-restricted intermediate ever touches HBM (~3 fine passes saved
    vs separate residual+restrict, ~1 vs the round-5 z-only fusion).

    Round-5 fallback tier: z-axis restriction fused into the kernel
    (stencil3d_residual_zrestrict_pallas) with the y/x einsum stages on
    HALF the data. Final tier: separate residual + restrict passes.

    SINGLE-DEVICE slabs only (zero Dirichlet ghosts are built into the
    kernels; a sharded slab would need 2-deep u halos — the slab cycle
    keeps the separate residual/restrict passes with 1-plane exchanges).
    Identical weights across all tiers (pinned in tests/test_pallas.py).
    """
    from ..ops.pallas_stencil import (fullrestrict_supported,
                                      pallas_supported,
                                      stencil3d_residual_restrict_pallas,
                                      stencil3d_residual_zrestrict_pallas)
    lz, ny, nx = u.shape
    if (lz % 2 == 0 and _mm_ok(u.dtype, platform)
            and fullrestrict_supported(ny, nx, u.dtype, platform)):
        dt = u.dtype
        return stencil3d_residual_restrict_pallas(
            u, f, _tmat(ny, dt).T, _tmat(nx, dt), lz, ny, nx, _RSCALE,
            name=_level_name(stencil3d_residual_restrict_pallas, level))
    if (lz % 2 == 0 and pallas_supported(ny, nx, u.dtype, platform)
            and _mm_ok(u.dtype, platform)):
        rz = stencil3d_residual_zrestrict_pallas(
            u, f, lz, ny, nx, _RSCALE,
            name=_level_name(stencil3d_residual_zrestrict_pallas, level))
        dt = rz.dtype
        out = _hp("cyx,yd->cdx", rz, _tmat(ny, dt))
        return _hp("cdx,xe->cde", out, _tmat(nx, dt))
    lo, hi = _no_exchange(u)
    r = _residual(u, f, lo, hi, platform, level)
    return _restrict(r, platform=platform)


def _prolong(e, lo=None, hi=None, platform=None):
    """Full 3-axis prolongation; z first (the only axis needing halos)."""
    if _mm_ok(e.dtype, platform):
        return _prolong_mm(e, lo, hi)
    return _p1d(_p1d(_p1d(e, 0, lo, hi), 1), 2)


def _prolong_add(u, e_c, platform=None, level=None):
    """Coarse-grid correction ``u + P e_c`` on a SINGLE-DEVICE slab.

    Where the level's coarse planes stay (8, 128)-tileable in fp32
    (the restriction kernel's gate) it is one streamed Pallas pass
    (stencil3d_prolong_add_pallas: read u and e_c, write u, the y/x
    transfer as in-VMEM MXU matmuls on coarse planes), where the einsum
    chain runs its z and y stages on fine-sized arrays and writes both
    intermediates to HBM. Elsewhere ``u + _prolong(e_c)``. The weights
    are P's on both paths (pinned in tests/test_pallas.py)."""
    from ..ops.pallas_stencil import (fullrestrict_supported,
                                      stencil3d_prolong_add_pallas)
    lz, ny, nx = u.shape
    if (_mm_ok(u.dtype, platform)
            and fullrestrict_supported(ny, nx, u.dtype, platform)):
        dt = u.dtype
        return stencil3d_prolong_add_pallas(
            u, e_c, _tmat(ny, dt, 1.0), _tmat(nx, dt, 1.0).T, lz, ny, nx,
            name=_level_name(stencil3d_prolong_add_pallas, level))
    return u + _prolong(e_c, platform=platform)


def mg_levels(nz: int, ny: int, nx: int, min_dim: int = 4):
    """Grid hierarchy: halve every dimension while all stay even and big."""
    levels = [(nz, ny, nx)]
    while all(d % 2 == 0 and d // 2 >= min_dim for d in levels[-1]):
        levels.append(tuple(d // 2 for d in levels[-1]))
    return levels


def make_vcycle3d(nz: int, ny: int, nx: int, pre: int = 2, post: int = 2,
                  coarse_iters: int = 20, axis=None, ndev: int = 1,
                  platform: str | None = None,
                  smoother: str = "chebyshev"):
    """Return ``cycle(r_slab (lz,ny,nx)) -> z_slab`` approximating A⁻¹ r —
    the 3D-native form the stencil-CG fast path composes with its
    grid-shaped loop carries (no flat↔3D reshapes inside the Krylov loop;
    see cg_stencil_kernel's traffic note).

    Pure jnp over static shapes; safe inside jit/shard_map. With
    ``ndev == 1`` the cycle is fully local; with ``ndev > 1`` it must run
    inside shard_map over mesh axis ``axis`` and operates on the local
    z-slab (``nz/ndev`` planes), slab-decomposed per the module docstring.
    ``platform`` is the platform of the mesh the cycle runs on
    (``comm.platform``) — it gates the Mosaic and einsum fast paths
    (ADVICE r4: the process default backend is the wrong key for a
    CPU-device mesh in a TPU-capable process).

    ``smoother``: ``'chebyshev'`` (default, round 5) runs the pre/post
    sweeps with the Chebyshev-root ω schedule (:func:`cheby_omegas` —
    same per-sweep cost as Jacobi, better smoothing: 14 → 12 CG its at
    128³); ``'jacobi'`` keeps the fixed ω = 2/3 pair.

    Each level's work sits in a ``jax.named_scope`` ``mg_l<li>`` (0 the
    finest), with ``smooth_pre``, ``residual_restrict``, ``prolong``,
    ``smooth_post`` and, on the coarsest, ``coarse`` inside; the scopes
    reach the HLO's ``op_name`` metadata, and the level's Pallas kernels
    carry ``_l<li>`` in their names. The coarser levels' cycle runs
    between a level's restriction and its prolongation, outside its scope.
    """
    levels = mg_levels(nz, ny, nx)
    if smoother == "chebyshev":
        pre_w, post_w = cheby_omegas(pre), cheby_omegas(post)
    elif smoother == "jacobi":
        pre_w, post_w = _OMEGA, _OMEGA
    else:
        raise ValueError(f"unknown MG smoother {smoother!r}; "
                         "available: 'chebyshev', 'jacobi'")

    def scope(li, phase):
        return jax.named_scope(f"mg_l{li}/{phase}")

    def local_cycle(f, li: int):
        if li == len(levels) - 1:
            with scope(li, "coarse"):
                return _smooth0(f, coarse_iters, _no_exchange,
                                platform=platform, level=li)
        with scope(li, "smooth_pre"):
            u = _smooth0(f, pre, _no_exchange, omega=pre_w,
                         platform=platform, level=li)
        with scope(li, "residual_restrict"):
            f_c = _residual_restrict_fused(u, f, platform, li)
        e_c = local_cycle(f_c, li + 1)
        with scope(li, "prolong"):
            u = _prolong_add(u, e_c, platform, li)
        with scope(li, "smooth_post"):
            return _smooth(u, f, post, _no_exchange, omega=post_w,
                           platform=platform, level=li)

    if ndev == 1:
        return lambda f: local_cycle(f, 0)

    if nz % ndev:
        raise ValueError(f"slab V-cycle needs nz ({nz}) divisible by the "
                         f"device count ({ndev})")
    exchange = _mk_exchange(axis, ndev)

    # slab-eligible prefix: levels whose local plane count is even, so the
    # 2x z-coarsening never splits a plane pair across a device boundary;
    # the first non-eligible level is the gather point for the tiny tail
    split = 0
    while (split < len(levels) - 1
           and levels[split][0] % (2 * ndev) == 0):
        split += 1

    def slab_cycle(f, li: int):
        if li == split:
            # tail: gather the (tiny) coarse grid, cycle locally, slice the
            # local slab of the correction back out
            lzi = levels[li][0] // ndev
            f_full = lax.all_gather(f, axis, tiled=True)
            e_full = local_cycle(f_full, li)
            i = lax.axis_index(axis)
            return lax.dynamic_slice_in_dim(e_full, i * lzi, lzi, axis=0)
        with scope(li, "smooth_pre"):
            u = _smooth0(f, pre, exchange, omega=pre_w, platform=platform,
                         level=li)
        with scope(li, "residual_restrict"):
            lo, hi = exchange(u)
            r = _residual(u, f, lo, hi, platform, li)
            rlo, rhi = exchange(r)
            f_c = _restrict(r, rlo, rhi, platform)
        e_c = slab_cycle(f_c, li + 1)
        with scope(li, "prolong"):
            elo, ehi = exchange(e_c)
            u = u + _prolong(e_c, elo, ehi, platform)
        with scope(li, "smooth_post"):
            return _smooth(u, f, post, exchange, omega=post_w,
                           platform=platform, level=li)

    return lambda f: slab_cycle(f, 0)


def make_vcycle(nz: int, ny: int, nx: int, pre: int = 2, post: int = 2,
                coarse_iters: int = 20, axis=None, ndev: int = 1,
                platform: str | None = None, smoother: str = "chebyshev"):
    """Flat-vector wrapper over :func:`make_vcycle3d`:
    ``vcycle(r_local_flat) -> z_local_flat`` (the generic PC-apply shape)."""
    cycle = make_vcycle3d(nz, ny, nx, pre=pre, post=post,
                          coarse_iters=coarse_iters, axis=axis, ndev=ndev,
                          platform=platform, smoother=smoother)
    lz = nz // ndev

    def vcycle(r_flat):
        return cycle(r_flat.reshape(lz, ny, nx)).reshape(-1)

    return vcycle
