"""Names the device trace reads: every Pallas kernel passes ``name=``, and
the solve program's HLO carries the MG level and Krylov phase scopes in
its ``op_name`` metadata (solvers/mg.py, cg_plans.py, krylov.py).

Scopes change metadata only, so these tests read the lowered text; the
level-qualified kernel names themselves are pinned on a described chip
in tests/test_chip_compile.py.
"""

import ast
from pathlib import Path

import numpy as np
import pytest

import mpi_petsc4py_example_tpu as tps

PKG = Path(tps.__file__).resolve().parent


def _pallas_calls():
    for path in sorted(PKG.rglob("*.py")):
        tree = ast.parse(path.read_text())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                yield path, node


def test_every_pallas_call_is_named():
    calls = list(_pallas_calls())
    assert len(calls) >= 10
    unnamed = [f"{p.name}:{n.lineno}" for p, n in calls
               if "name" not in {k.arg for k in n.keywords}]
    assert not unnamed, unnamed


def test_mg_level_names():
    from mpi_petsc4py_example_tpu.ops.pallas_stencil import (
        stencil3d_smooth_pair_pallas)
    from mpi_petsc4py_example_tpu.solvers.mg import _level_name
    assert _level_name(stencil3d_smooth_pair_pallas, 0) == \
        "stencil3d_smooth_pair_pallas_l0"
    assert _level_name(stencil3d_smooth_pair_pallas, None) is None


@pytest.fixture(scope="module")
def mg_program_text():
    """The HLO text of a 16^3 CG + MG solve program with the true-residual
    epilogue, on one device."""
    import jax

    from mpi_petsc4py_example_tpu.contracts import _raw_programs
    from mpi_petsc4py_example_tpu.models import StencilPoisson3D
    from mpi_petsc4py_example_tpu.solvers.krylov import build_ksp_program
    comm = tps.DeviceComm(n_devices=1)
    with _raw_programs():
        op = StencilPoisson3D(comm, 16, 16, 16)
        ksp = tps.KSP().create(comm)
        ksp.set_operators(op)
        ksp.set_type("cg")
        ksp.get_pc().set_type("mg")
        ksp.set_up()
        pc = ksp.get_pc()
        prog = build_ksp_program(comm, "cg", pc, op, true_res=True)
        x, b = op.get_vecs()
        dt = np.dtype(op.dtype).type
        lowered = prog.lower(op.device_arrays(), pc.device_arrays(), b.data,
                             x.data, dt(1e-6), dt(0.0), dt(0.0),
                             np.int32(50))
    assert isinstance(lowered, jax.stages.Lowered)
    return lowered.as_text(debug_info=True)


@pytest.mark.parametrize("scope", ["mg_l0/smooth_pre", "mg_l0/prolong",
                                   "mg_l1/residual_restrict",
                                   "mg_l1/smooth_post", "/coarse/",
                                   "cg.apply", "cg.pc", "cg.dot",
                                   "cg.update", "true_residual"])
def test_solve_program_scopes(mg_program_text, scope):
    assert scope in mg_program_text


def test_levels_do_not_nest(mg_program_text):
    """A level's scope closes before the coarser levels' cycle runs, so
    ``mg_l1`` never sits inside ``mg_l0``."""
    assert "mg_l1/" in mg_program_text
    assert "mg_l0/mg_l1" not in mg_program_text
    assert "mg_l0/residual_restrict/mg_l1" not in mg_program_text
