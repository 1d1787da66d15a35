"""Described-chip compiles of the main-path Pallas kernels at real widths.

Each test compiles one kernel for a v5e chip that is described, not
attached (``jax.experimental.topologies``), and checks what interpret mode
cannot: that Mosaic accepts the kernel (VMEM limit, aligned chunk slices)
— a ``tpu_custom_call`` in the compiled program — and that the program
fits one chip's 16 GB of HBM. Nothing runs, so nothing here says anything
about results or times.

The topology is described inside a module fixture (never at import): only
one process at a time may load the TPU library, and the xdist worker that
runs this file is that process. The persistent compilation cache is off
around these compiles (a described-chip entry cannot be read back here).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

HBM_BYTES = 16 * 10 ** 9            # one v5e: 16 GB of HBM


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled(fn, args, one_chip):
    """Compile ``fn(*args)`` for the described chip: arrays given as
    ``(shape, dtype)`` become placed shapes, everything else is static."""
    spec = [jax.ShapeDtypeStruct(a[0], a[1], sharding=one_chip)
            if isinstance(a, tuple) else a for a in args]
    compiled = fn.lower(*spec).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


F32 = jnp.float32


def _slab(lz, n, k=None):
    lead = () if k is None else (k,)
    return (lead + (lz, n, n), F32), (lead + (1, n, n), F32)


@pytest.mark.parametrize("n", [256, 512])
def test_apply(one_chip, n):
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_apply_pallas)
    u, plane = _slab(n, n)
    _compiled(stencil3d_apply_pallas, [u, plane, plane, n, n, n], one_chip)


@pytest.mark.parametrize("n", [256, 512])
def test_fused_dot(one_chip, n):
    """The CG hot loop's fused stencil + <p, Ap> kernel."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_dot_pallas)
    u, plane = _slab(n, n)
    _compiled(stencil3d_dot_pallas, [u, plane, plane, n, n, n], one_chip)


def test_mg_smoother(one_chip):
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_smooth_pallas)
    from mpi_petsc4py_example_tpu.solvers.mg import _OMEGA
    u, plane = _slab(256, 256)
    _compiled(stencil3d_smooth_pallas,
              [u, u, plane, plane, 256, 256, 256, _OMEGA / 6.0], one_chip)


def test_mg_smoother_pair(one_chip):
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_smooth_pair_pallas)
    u, _ = _slab(256, 256)
    _compiled(stencil3d_smooth_pair_pallas,
              [u, u, 256, 256, 256, 0.1, 0.1], one_chip)


def test_mg_residual_restrict(one_chip):
    """The fused residual + full 3-axis restriction of the V-cycle's
    finest single-device level."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_residual_restrict_pallas)
    from mpi_petsc4py_example_tpu.solvers.mg import _RSCALE, _tmat
    u, _ = _slab(256, 256)
    wyt = np.asarray(_tmat(256, np.float32)).T
    _compiled(stencil3d_residual_restrict_pallas,
              [u, u, (wyt.shape, F32), ((256, 128), F32), 256, 256, 256,
               _RSCALE], one_chip)


def test_mg_prolong_add(one_chip):
    """The fused prolongation + correction of the V-cycle's finest
    single-device level (coarse planes 128², fine 256²)."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_prolong_add_pallas)
    u, _ = _slab(256, 256)
    _compiled(stencil3d_prolong_add_pallas,
              [u, ((128, 128, 128), F32), ((256, 128), F32),
               ((128, 256), F32), 256, 256, 256], one_chip)


def test_batched_apply_k8(one_chip):
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_apply_many_pallas)
    u, plane = _slab(128, 256, k=8)
    _compiled(stencil3d_apply_many_pallas,
              [u, plane, plane, 128, 256, 256, 8], one_chip)


def test_batched_fused_dot_k8(one_chip):
    """The batched stencil-CG fast path (served blocks of k requests)."""
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_dot_many_pallas)
    u, plane = _slab(128, 256, k=8)
    _compiled(stencil3d_dot_many_pallas,
              [u, plane, plane, 128, 256, 256, 8], one_chip)


def test_mg_vcycle_kernels_named_by_level(one_chip):
    """The single-device V-cycle names each Pallas kernel by its MG level
    (solvers/mg.py): the compiled program's custom calls, which the device
    trace's op names come from, read ``<kernel>_l<level>``."""
    import jax

    from mpi_petsc4py_example_tpu.solvers.mg import make_vcycle3d
    # Pallas at l0-l2; full restriction and fused prolongation at l0-l1
    shape = (32, 256, 512)
    cycle = jax.jit(make_vcycle3d(*shape, platform="tpu"))
    text = _compiled(cycle, [(shape, F32)], one_chip).as_text()
    for name in ("stencil3d_smooth0_pair_pallas_l0",
                 "stencil3d_residual_restrict_pallas_l0",
                 "stencil3d_prolong_add_pallas_l0",
                 "stencil3d_smooth_pair_pallas_l0",
                 "stencil3d_residual_restrict_pallas_l1",
                 "stencil3d_prolong_add_pallas_l1",
                 "stencil3d_residual_zrestrict_pallas_l2",
                 "stencil3d_smooth_pair_pallas_l2"):
        assert f"%{name}." in text, name
    assert "%stencil3d_smooth_pair_pallas." not in text
    assert "stencil3d_prolong_add_pallas_l2" not in text


@pytest.mark.parametrize("form", ["apply", "transpose", "many"])
def test_bjacobi_ilu0_apply_2048(one_chip, form):
    """PC bjacobi's ILU(0) block apply (solvers/bjilu.py) at 2048^2 in
    fp64: 64 blocks of 32 lines, the double-f32 Pallas kernel over 8
    groups of 8 blocks inside its VMEM limit, and the whole apply within
    HBM; also on the transposed stack (BiCG) and batched over 4 columns
    (``KSP.solve_many``)."""
    from mpi_petsc4py_example_tpu.solvers.bjilu import (apply, apply_many,
                                                        group_size,
                                                        transpose)
    assert group_size((64, 5, 32, 2048), 1) == 8
    coef = jax.ShapeDtypeStruct((8, 10, 32, 8, 2048), jnp.float32,
                                sharding=one_chip)
    shape = (2048 * 2048, 4) if form == "many" else (2048 * 2048,)
    r = jax.ShapeDtypeStruct(shape, jnp.float64, sharding=one_chip)
    fn = {"apply": apply, "many": apply_many,
          "transpose": lambda a, v: apply((transpose(a[0]),), v)}[form]
    c = jax.jit(fn).lower((coef,), r).compile()
    assert "bjacobi_ilu0_pallas" in c.as_text()
    m = c.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("pc_type", ["jacobi", "mg"])
def test_solve_program_exports(topo, monkeypatch, pc_type):
    """The stencil cells' single-RHS CG program exports for the chip
    (utils/aot), its Pallas kernels included, when lowered for this
    runtime as a jit lowers it: lowered for older runtimes too, Mosaic
    recurses without end on the stencil kernels' 64-bit pad constant."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import StencilPoisson3D
    from mpi_petsc4py_example_tpu.parallel.mesh import DeviceComm
    from mpi_petsc4py_example_tpu.solvers.krylov import build_ksp_program
    from mpi_petsc4py_example_tpu.utils import aot

    # described devices hold no arrays: every placement is its shape
    monkeypatch.setattr(
        DeviceComm, "_put", lambda self, arr, sharding: jax.ShapeDtypeStruct(
            np.shape(arr), np.asarray(arr).dtype, sharding=sharding))
    comm = DeviceComm(devices=[topo.devices[0]])
    op = StencilPoisson3D(comm, 256, 256, 256, dtype=np.float32)
    pc = tps.PC(comm)
    pc.set_type(pc_type)
    pc.set_up(op)
    prog = build_ksp_program(comm, "cg", pc, op, zero_guess=True,
                             true_res=True, donate=True)
    v = jax.ShapeDtypeStruct((op.shape[0],), F32,
                             sharding=comm.row_sharding)
    args = (op.device_arrays(), pc.device_arrays(), v, v, np.float32(1e-6),
            np.float32(0), np.float32(0), np.int32(10))
    with aot._this_runtime():
        exported = jax.export.export(prog.traced, platforms=["tpu"])(*args)
    assert "tpu_custom_call" in exported.mlir_module()


@pytest.mark.parametrize("pc_type", ["bjacobi"])
def test_bcgs_double_f32_program_2048(topo, one_chip, pc_type):
    """The fp64 BCGS solve program with its double-f32 loop
    (solvers/bcgs_dd.py) at 2048^2 on a five-point DIA operator with 64
    ILU(0) blocks in 8 groups: Mosaic accepts every sweep within its
    VMEM limit and the program fits one chip."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.parallel.mesh import DeviceComm
    from mpi_petsc4py_example_tpu.solvers import krylov

    comm = DeviceComm(devices=[topo.devices[0]])
    nx, F64 = 2048, jnp.float64
    n = nx * nx

    def placed(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=comm.row_sharding)

    # described devices hold no arrays: the operator and the PC carry
    # their arrays' shapes
    op = tps.Mat(comm, (n, n), placed((n, 5), jnp.int32), placed((n, 5), F64))
    op.dia_vals, op.dia_offsets = placed((n, 5), F64), (-nx, -1, 0, 1, nx)
    pc = tps.PC(comm)
    pc.set_type(pc_type)
    pc.sub_solve = "ilu0"
    pc._arrays = (placed((8, 10, 32, 8, nx), F32),)
    krylov._PROGRAM_CACHE.clear()
    prog = krylov.build_ksp_program(comm, "bcgs", pc, op, zero_guess=True,
                                    true_res=True, donate=True)
    krylov._PROGRAM_CACHE.clear()
    assert prog.loop == "dd_pallas"
    v = placed((n,), F64)
    args = (op.device_arrays(), pc.device_arrays(), v, v, np.float64(1e-8),
            np.float64(0), np.float64(0), np.int32(10))
    compiled = getattr(prog, "traced", prog).lower(*args).compile()
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    text = compiled.as_text()
    for name in ("bcgs_dd_spmv_pallas", "bcgs_dd_update_pallas",
                 "bjacobi_ilu0_pallas"):
        assert f"%{name}" in text, name
