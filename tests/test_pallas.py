"""Pallas stencil kernel correctness via the interpreter (runs off-TPU).

The double-buffered DMA pipeline (per-bank semaphores, 3-way halo DMA
routing, two-deep output drain) only executes on real TPUs in production;
interpret mode runs the same kernel logic through the Pallas interpreter on
any backend, so CI pins its correctness — including the edge-chunk paths
``nchunks == 1 / 2 / 3+``.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
    stencil3d_apply_pallas, stencil3d_dot_pallas)


def reference_stencil(u, lo, hi):
    """Pure-numpy 7-point stencil on the extended slab."""
    ext = np.concatenate([lo, u, hi], axis=0)
    c = ext[1:-1]
    y = 6.0 * c - ext[:-2] - ext[2:]
    y -= np.pad(c[:, :-1, :], ((0, 0), (1, 0), (0, 0)))
    y -= np.pad(c[:, 1:, :], ((0, 0), (0, 1), (0, 0)))
    y -= np.pad(c[:, :, :-1], ((0, 0), (0, 0), (1, 0)))
    y -= np.pad(c[:, :, 1:], ((0, 0), (0, 0), (0, 1)))
    return y


@pytest.mark.parametrize("lz,max_chunk", [
    (4, None),   # single chunk
    (4, 2),      # nchunks == 2
    (6, 2),      # nchunks == 3
    (8, 1),      # nchunks == 8, chunk == 1 plane
])
def test_interpret_parity(lz, max_chunk):
    ny, nx = 8, 128
    rng = np.random.default_rng(lz)
    u = rng.random((lz, ny, nx)).astype(np.float32)
    lo = rng.random((1, ny, nx)).astype(np.float32)
    hi = rng.random((1, ny, nx)).astype(np.float32)
    y = np.asarray(stencil3d_apply_pallas(
        jnp.asarray(u), jnp.asarray(lo), jnp.asarray(hi),
        lz, ny, nx, True, max_chunk))
    ref = reference_stencil(u.astype(np.float64), lo.astype(np.float64),
                            hi.astype(np.float64))
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lz,max_chunk", [
    (4, None),   # single chunk
    (6, 2),      # nchunks == 3
    (8, 1),      # chunk == 1 plane
])
def test_fused_dot_parity(lz, max_chunk):
    """stencil3d_dot_pallas returns (A u, <u, A u>) matching the plain
    kernel + a separate dot — the fused reduction CG's fast path relies on
    (krylov.cg_stencil_kernel)."""
    ny, nx = 8, 128
    rng = np.random.default_rng(100 + lz)
    u = rng.random((lz, ny, nx)).astype(np.float32)
    lo = rng.random((1, ny, nx)).astype(np.float32)
    hi = rng.random((1, ny, nx)).astype(np.float32)
    y, dot = stencil3d_dot_pallas(
        jnp.asarray(u), jnp.asarray(lo), jnp.asarray(hi),
        lz, ny, nx, True, max_chunk)
    ref = reference_stencil(u.astype(np.float64), lo.astype(np.float64),
                            hi.astype(np.float64))
    np.testing.assert_allclose(np.asarray(y), ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(dot), float((u.astype(np.float64)
                                                  * ref).sum()), rtol=1e-5)


def test_zero_halos_dirichlet():
    """Zero halos (the global-boundary case) reproduce the Dirichlet rows."""
    lz, ny, nx = 4, 8, 128
    u = np.ones((lz, ny, nx), dtype=np.float32)
    z = np.zeros((1, ny, nx), dtype=np.float32)
    y = np.asarray(stencil3d_apply_pallas(
        jnp.asarray(u), jnp.asarray(z), jnp.asarray(z), lz, ny, nx, True))
    ref = reference_stencil(u.astype(np.float64), z, z)
    np.testing.assert_allclose(y, ref, rtol=1e-6)


@pytest.mark.parametrize("lz,max_chunk", [
    (4, None),   # single chunk
    (6, 2),      # nchunks == 3
    (8, 1),      # chunk == 1 plane
])
def test_fused_smooth_parity(lz, max_chunk):
    """stencil3d_smooth_pallas == u + w*(f - A u) (the MG damped-Jacobi
    sweep fused into one streamed pass, solvers/mg._sweep)."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_smooth_pallas)
    ny, nx = 8, 128
    rng = np.random.default_rng(200 + lz)
    u = rng.random((lz, ny, nx)).astype(np.float32)
    f = rng.random((lz, ny, nx)).astype(np.float32)
    lo = rng.random((1, ny, nx)).astype(np.float32)
    hi = rng.random((1, ny, nx)).astype(np.float32)
    w = 2.0 / 3.0 / 6.0
    out = np.asarray(stencil3d_smooth_pallas(
        jnp.asarray(u), jnp.asarray(f), jnp.asarray(lo), jnp.asarray(hi),
        lz, ny, nx, w, True, max_chunk))
    ref = u + w * (f - reference_stencil(
        u.astype(np.float64), lo.astype(np.float64), hi.astype(np.float64)))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lz,max_chunk", [
    (4, None),
    (6, 2),
    (8, 1),
])
def test_fused_residual_parity(lz, max_chunk):
    """stencil3d_residual_pallas == f - A u (the V-cycle's fused
    pre-restriction residual, solvers/mg._residual)."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_residual_pallas)
    ny, nx = 8, 128
    rng = np.random.default_rng(300 + lz)
    u = rng.random((lz, ny, nx)).astype(np.float32)
    f = rng.random((lz, ny, nx)).astype(np.float32)
    lo = rng.random((1, ny, nx)).astype(np.float32)
    hi = rng.random((1, ny, nx)).astype(np.float32)
    out = np.asarray(stencil3d_residual_pallas(
        jnp.asarray(u), jnp.asarray(f), jnp.asarray(lo), jnp.asarray(hi),
        lz, ny, nx, True, max_chunk))
    ref = f - reference_stencil(
        u.astype(np.float64), lo.astype(np.float64), hi.astype(np.float64))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nbuf,lz,max_chunk", [
    (3, 6, 2),     # depth 3, 3 chunks: one interior (wide-DMA) chunk
    (3, 8, 1),     # depth 3, 8 single-plane chunks
    (4, 8, 2),     # depth 4, 4 chunks
    (4, 4, 4),     # depth deeper than nchunks: drain guards must hold
])
def test_pipeline_depth_parity(nbuf, lz, max_chunk):
    """The nbuf-deep pipeline (TPU_SOLVE_STENCIL_NBUF retuning knob) and
    the wide contiguous interior DMAs compute exactly what the classic
    double-buffered 3-way-split pipeline computed."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_apply_pallas, stencil3d_dot_pallas)
    ny, nx = 8, 128
    rng = np.random.default_rng(900 + nbuf * 10 + lz)
    u = rng.random((lz, ny, nx)).astype(np.float32)
    lo = rng.random((1, ny, nx)).astype(np.float32)
    hi = rng.random((1, ny, nx)).astype(np.float32)
    ref = reference_stencil(u.astype(np.float64), lo.astype(np.float64),
                            hi.astype(np.float64))
    y = np.asarray(stencil3d_apply_pallas(
        jnp.asarray(u), jnp.asarray(lo), jnp.asarray(hi),
        lz, ny, nx, True, max_chunk, nbuf))
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-5)
    y2, d = stencil3d_dot_pallas(jnp.asarray(u), jnp.asarray(lo),
                                 jnp.asarray(hi), lz, ny, nx, True,
                                 max_chunk, nbuf)
    np.testing.assert_allclose(np.asarray(y2), ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(d), float((u.astype(np.float64)
                                                * ref).sum()),
                               rtol=1e-4)


def test_pipeline_depth_env(monkeypatch):
    """TPU_SOLVE_STENCIL_NBUF parses defensively and clamps to [2, 4]."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import _pipeline_depth
    monkeypatch.delenv("TPU_SOLVE_STENCIL_NBUF", raising=False)
    assert _pipeline_depth() == 2
    monkeypatch.setenv("TPU_SOLVE_STENCIL_NBUF", "3")
    assert _pipeline_depth() == 3
    monkeypatch.setenv("TPU_SOLVE_STENCIL_NBUF", "9")
    assert _pipeline_depth() == 4
    monkeypatch.setenv("TPU_SOLVE_STENCIL_NBUF", "1")
    assert _pipeline_depth() == 2
    monkeypatch.setenv("TPU_SOLVE_STENCIL_NBUF", "bogus")
    assert _pipeline_depth() == 2


def test_fast_path_gates_key_on_mesh_platform(monkeypatch):
    """ADVICE r4: the Mosaic / einsum fast-path gates must key on the
    platform of the mesh the op runs on, NOT the process default backend —
    a CPU-device mesh inside a TPU-capable process takes the CPU paths."""
    import jax

    from mpi_petsc4py_example_tpu.ops.pallas_stencil import pallas_supported
    from mpi_petsc4py_example_tpu.solvers.mg import _mm_ok

    # simulate a TPU-capable process hosting a CPU-device mesh
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pallas_supported(8, 128, np.float32, platform="cpu") is False
    assert pallas_supported(8, 128, np.float32, platform="tpu") is True
    assert pallas_supported(8, 128, np.float32) is True      # legacy default
    assert _mm_ok(np.float64, platform="cpu") is True
    assert _mm_ok(np.float64, platform="tpu") is False


def test_vmem_plan_per_generation():
    """The Mosaic VMEM limit/budget derive from the named generation's
    physical VMEM (v5e: 128 MiB -> 64 MiB limit, 48 MiB budget)."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import _vmem_plan

    limit, budget = _vmem_plan("TPU v5 lite")
    assert limit == 64 << 20 and budget == 48 << 20
    limit, budget = _vmem_plan(None)        # CPU/interpret: production plan
    assert limit == 64 << 20 and budget == 48 << 20


@pytest.mark.parametrize("kind", ["TPU v3", "TPU v6 lite", "TPU v5e"])
def test_vmem_plan_unknown_device_kind_raises(kind):
    """A TPU device_kind without a known VMEM size is an error, never a
    guessed default."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import _vmem_plan

    with pytest.raises(ValueError, match="no VMEM size known"):
        _vmem_plan(kind)


@pytest.mark.parametrize("lz,ny,nx,max_chunk", [
    (4, 8, 128, None),          # single chunk (both edge masks in one)
    (8, 8, 128, 2),             # multi-chunk: cross-chunk coarse planes
    (12, 16, 128, 4),
    (6, 8, 128, 2),
])
def test_fused_residual_zrestrict_parity(lz, ny, nx, max_chunk):
    """stencil3d_residual_zrestrict_pallas == mg._r1d(f - A u, axis=0)
    with zero Dirichlet ghosts — the round-5 V-cycle fusion that keeps the
    fine residual out of HBM (solvers/mg._residual_restrict_fused)."""
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    from mpi_petsc4py_example_tpu.models.stencil import StencilPoisson3D
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_residual_zrestrict_pallas)
    rng = np.random.default_rng(500 + lz)
    u = rng.random((lz, ny, nx)).astype(np.float32)
    f = rng.random((lz, ny, nx)).astype(np.float32)
    z = jnp.zeros((ny, nx), jnp.float64)
    r = f - StencilPoisson3D._stencil7_jnp(jnp.asarray(u, jnp.float64),
                                           z, z)
    ref = np.asarray(mg._r1d(r, 0))
    out = np.asarray(stencil3d_residual_zrestrict_pallas(
        jnp.asarray(u), jnp.asarray(f), lz, ny, nx, mg._RSCALE,
        True, max_chunk))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lz,ny,nx,max_chunk", [
    (4, 8, 128, None),          # single chunk (both edge masks in one)
    (8, 8, 128, 2),             # multi-chunk: cross-chunk coarse planes
    (12, 16, 128, 4),
    (6, 16, 256, 2),            # the production tileable-coarse shape class
])
def test_fused_residual_restrict3_parity(lz, ny, nx, max_chunk):
    """stencil3d_residual_restrict_pallas == mg._restrict(f - A u) with
    zero Dirichlet ghosts — the round-6 FULL fusion that produces the
    coarse RHS from the kernel's VMEM-resident fine chunks (neither the
    residual nor any intermediate hits HBM)."""
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    from mpi_petsc4py_example_tpu.models.stencil import StencilPoisson3D
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_residual_restrict_pallas)
    rng = np.random.default_rng(700 + lz + nx)
    u = rng.random((lz, ny, nx)).astype(np.float32)
    f = rng.random((lz, ny, nx)).astype(np.float32)
    z = jnp.zeros((ny, nx), jnp.float64)
    r = f - StencilPoisson3D._stencil7_jnp(jnp.asarray(u, jnp.float64),
                                           z, z)
    ref = np.asarray(mg._restrict(r))
    dt = jnp.float32
    out = np.asarray(stencil3d_residual_restrict_pallas(
        jnp.asarray(u), jnp.asarray(f), mg._tmat(ny, dt).T,
        mg._tmat(nx, dt), lz, ny, nx, mg._RSCALE, True, max_chunk))
    assert out.shape == (lz // 2, ny // 2, nx // 2)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_fused_residual_restrict3_rejects_odd_dims():
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_residual_restrict_pallas)
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    u = jnp.zeros((4, 7, 128), jnp.float32)
    with pytest.raises(ValueError, match="even dims"):
        stencil3d_residual_restrict_pallas(
            u, u, mg._tmat(8, jnp.float32).T, mg._tmat(128, jnp.float32),
            4, 7, 128, mg._RSCALE, True, None)


def _prolong_add_interpret(u, e, max_chunk=None):
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_prolong_add_pallas)
    lz, ny, nx = u.shape
    dt = jnp.float32
    return stencil3d_prolong_add_pallas(
        jnp.asarray(u, dt), jnp.asarray(e, dt), mg._tmat(ny, dt, 1.0),
        mg._tmat(nx, dt, 1.0).T, lz, ny, nx, True, max_chunk)


@pytest.mark.parametrize("lz,ny,nx,max_chunk", [
    (4, 8, 128, None),          # single chunk (both ghost planes in one)
    (8, 8, 128, 2),             # one coarse plane a chunk, all carried
    (12, 16, 128, 4),           # multi-chunk: Q crosses chunk boundaries
    (6, 16, 256, 2),            # the production tileable-coarse shape class
])
def test_fused_prolong_add_parity(lz, ny, nx, max_chunk):
    """stencil3d_prolong_add_pallas == u + mg._prolong_mm(e) with zero
    Dirichlet ghosts — the V-cycle's upward leg in one streamed pass
    (mg._prolong_add), its correction never in HBM."""
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    rng = np.random.default_rng(800 + lz + nx)
    u = rng.standard_normal((lz, ny, nx)).astype(np.float32)
    e = rng.standard_normal((lz // 2, ny // 2, nx // 2)).astype(np.float32)
    ref = u + np.asarray(mg._prolong_mm(jnp.asarray(e, jnp.float64),
                                        None, None))
    out = np.asarray(_prolong_add_interpret(u, e, max_chunk))
    assert out.shape == (lz, ny, nx)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_fused_prolong_add_adjoint_of_restriction():
    """<P e, r> == 2<e, R r> through the fused kernel (u = 0): P = 2·Rᵀ
    keeps the V-cycle symmetric, so a valid CG preconditioner (the einsum
    pair's pin is tests/test_mg_slab.py::test_transfer_adjointness)."""
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    rng = np.random.default_rng(3)
    lz, ny, nx = 8, 16, 256
    r = rng.standard_normal((lz, ny, nx))
    e = rng.standard_normal((lz // 2, ny // 2, nx // 2)).astype(np.float32)
    pe = np.asarray(_prolong_add_interpret(np.zeros((lz, ny, nx)), e, 2))
    lhs = float(np.vdot(pe.astype(np.float64), r))
    rhs = 2.0 * float(np.vdot(e.astype(np.float64),
                              np.asarray(mg._restrict(jnp.asarray(r)))))
    assert abs(lhs - rhs) <= 1e-5 * max(abs(rhs), 1.0), (lhs, rhs)


def test_fused_prolong_add_rejects_odd_dims():
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_prolong_add_pallas)
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    u = jnp.zeros((4, 7, 128), jnp.float32)
    e = jnp.zeros((2, 3, 64), jnp.float32)
    with pytest.raises(ValueError, match="even dims"):
        stencil3d_prolong_add_pallas(
            u, e, mg._tmat(8, jnp.float32, 1.0),
            mg._tmat(128, jnp.float32, 1.0).T, 4, 7, 128, True, None)


@pytest.mark.parametrize("shape,dtype,platform,engaged", [
    ((8, 16, 256), np.float32, "tpu", True),
    ((8, 8, 128), np.float32, "tpu", False),    # coarse planes untileable
    ((8, 16, 256), np.float64, "tpu", False),
    ((8, 16, 256), jnp.bfloat16, "tpu", False),
    ((8, 16, 256), np.float32, "cpu", False),
])
def test_prolong_add_gate(monkeypatch, shape, dtype, platform, engaged):
    """mg._prolong_add takes the fused kernel only for fp32 TPU levels
    with (8, 128)-tileable coarse planes; every other level keeps
    ``u + _prolong(e)``, and both give the same correction."""
    import mpi_petsc4py_example_tpu.ops.pallas_stencil as ps
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    calls = []
    real = ps.stencil3d_prolong_add_pallas

    def kernel(u, e, wy, wxt, lz, ny, nx, *, name):
        calls.append(name)
        return real(u, e, wy, wxt, lz, ny, nx, True)

    kernel.__name__ = real.__name__         # mg names it by level from this
    monkeypatch.setattr(ps, "stencil3d_prolong_add_pallas", kernel)
    rng = np.random.default_rng(9)
    u = jnp.asarray(rng.standard_normal(shape), dtype)
    e = jnp.asarray(rng.standard_normal(tuple(s // 2 for s in shape)),
                    dtype)
    out = mg._prolong_add(u, e, platform, level=1)
    assert calls == (["stencil3d_prolong_add_pallas_l1"] if engaged else [])
    ref = (np.asarray(u, np.float64)
           + np.asarray(mg._prolong_mm(jnp.asarray(e, jnp.float64),
                                       None, None)))
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5     # 3 bf16 roundings
    np.testing.assert_allclose(np.asarray(out, np.float64), ref, rtol=tol,
                               atol=tol)


def test_fullrestrict_gate():
    """The 3-axis fusion additionally needs (8,128)-tileable COARSE
    planes; shapes that fail it still take the z-only fusion tier."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        fullrestrict_supported, pallas_supported)
    import jax
    if jax.default_backend() != "tpu":
        # gates are platform-keyed; force the TPU branch via the argument
        assert fullrestrict_supported(16, 256, np.float32,
                                      platform="tpu") is True
        assert fullrestrict_supported(8, 128, np.float32,
                                      platform="tpu") is False
        assert pallas_supported(8, 128, np.float32, platform="tpu") is True
    assert fullrestrict_supported(16, 256, np.float32,
                                  platform="cpu") is False


def test_fused_residual_restrict_matches_separate_passes():
    """mg._residual_restrict_fused's fallback == fused arithmetic: on CPU
    the helper takes the separate-pass path; pin that both compose to the
    same full 3-axis restriction of the residual."""
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    rng = np.random.default_rng(7)
    u = jnp.asarray(rng.random((8, 8, 8)))
    f = jnp.asarray(rng.random((8, 8, 8)))
    lo, hi = mg._no_exchange(u)
    r = mg._residual(u, f, lo, hi)
    expect = mg._restrict(r)
    got = mg._residual_restrict_fused(u, f)
    np.testing.assert_allclose(got, expect, atol=1e-13)


@pytest.mark.parametrize("lz,mc", [(4, None), (8, 2), (6, 3)])
def test_fused_smooth_pairs_parity(lz, mc):
    """stencil3d_smooth_pair_pallas == two staged sweeps, and
    stencil3d_smooth0_pair_pallas == (w1+w2)f − w1w2·Af (two sweeps from a
    zero guess) — the round-5 single-pass smoothing fusions
    (mg._smooth/_smooth0's 2-sweep single-device fast paths)."""
    import mpi_petsc4py_example_tpu.solvers.mg as mg
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_smooth0_pair_pallas, stencil3d_smooth_pair_pallas)
    ny, nx = 8, 128
    rng = np.random.default_rng(600 + lz)
    u = jnp.asarray(rng.random((lz, ny, nx)).astype(np.float32))
    f = jnp.asarray(rng.random((lz, ny, nx)).astype(np.float32))
    w1, w2 = mg.cheby_omegas(2)
    lo, hi = mg._no_exchange(u)
    u1 = u + (w1 / 6.0) * (f - mg._stencil7(u, lo, hi))
    ref = u1 + (w2 / 6.0) * (f - mg._stencil7(u1, lo, hi))
    out = stencil3d_smooth_pair_pallas(u, f, lz, ny, nx, w1 / 6.0,
                                       w2 / 6.0, True, mc)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
    v1 = (w1 / 6.0) * f
    ref0 = v1 + (w2 / 6.0) * (f - mg._stencil7(v1, lo, hi))
    out0 = stencil3d_smooth0_pair_pallas(f, lz, ny, nx, w1 / 6.0,
                                         w2 / 6.0, True, mc)
    np.testing.assert_allclose(out0, ref0, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nrhs,lz,max_chunk,nbuf", [
    (1, 4, None, None),   # degenerate single-RHS batch
    (3, 4, 2, None),      # nchunks == 2
    (3, 6, 2, None),      # nchunks == 3 (interior wide-copy path)
    (2, 8, 1, None),      # chunk == 1 plane
    (4, 8, 2, 3),         # deeper pipeline, multi-column
])
def test_interpret_parity_many(nrhs, lz, max_chunk, nbuf):
    """Multi-RHS kernel == per-column reference stencil across the same
    chunk-geometry edge cases the single-RHS kernel pins (the VMEM chunk
    plan accounts for the k resident columns via _pick_chunk ncols)."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_apply_many_pallas)
    ny, nx = 8, 128
    rng = np.random.default_rng(97 + nrhs * 10 + lz)
    u = rng.random((nrhs, lz, ny, nx)).astype(np.float32)
    lo = rng.random((nrhs, 1, ny, nx)).astype(np.float32)
    hi = rng.random((nrhs, 1, ny, nx)).astype(np.float32)
    y = np.asarray(stencil3d_apply_many_pallas(
        jnp.asarray(u), jnp.asarray(lo), jnp.asarray(hi),
        lz, ny, nx, nrhs, True, max_chunk, nbuf))
    for j in range(nrhs):
        ref = reference_stencil(u[j].astype(np.float64),
                                lo[j].astype(np.float64),
                                hi[j].astype(np.float64))
        np.testing.assert_allclose(y[j], ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("nrhs,lz,max_chunk", [(2, 4, None), (3, 8, 2)])
def test_fused_dot_parity_many(nrhs, lz, max_chunk):
    """Fused multi-RHS apply+dot: per-column <u_j, A u_j> partials match
    the separate computation (the batched CG phase-1 reduction input)."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_dot_many_pallas)
    ny, nx = 8, 128
    rng = np.random.default_rng(31 + nrhs + lz)
    u = rng.random((nrhs, lz, ny, nx)).astype(np.float32)
    lo = rng.random((nrhs, 1, ny, nx)).astype(np.float32)
    hi = rng.random((nrhs, 1, ny, nx)).astype(np.float32)
    y, dots = stencil3d_dot_many_pallas(
        jnp.asarray(u), jnp.asarray(lo), jnp.asarray(hi),
        lz, ny, nx, nrhs, True, max_chunk)
    assert dots.shape == (nrhs,)
    for j in range(nrhs):
        ref = reference_stencil(u[j].astype(np.float64),
                                lo[j].astype(np.float64),
                                hi[j].astype(np.float64))
        np.testing.assert_allclose(np.asarray(y[j]), ref, rtol=1e-5,
                                   atol=1e-5)
        np.testing.assert_allclose(float(dots[j]),
                                   float((u[j] * ref).sum()), rtol=1e-4)


def test_pick_chunk_accounts_for_columns():
    """The multi-RHS chunk plan shrinks with the batch width: k resident
    columns divide the per-plane budget, so a k-wide batch must never
    plan a DEEPER chunk than k=1 — and shrinks once k overflows it."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import _pick_chunk
    lz, ny, nx = 512, 512, 512
    c1, _ = _pick_chunk(lz, 4, ny, nx, None)
    c8, _ = _pick_chunk(lz, 4, ny, nx, None, ncols=8)
    assert c8 <= c1
    assert c8 >= 1
    # the degenerate ncols=1 call is byte-identical to the old plan
    assert _pick_chunk(lz, 4, ny, nx, None, ncols=1) == (c1, lz // c1)
