"""The bytes models count the method at a small grid as a hand count
does (the derivations are in each model's docstring)."""

import importlib.util
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def model(name):
    spec = importlib.util.spec_from_file_location(
        "bytes_" + name.replace("-", "_"),
        os.path.join(BENCH, "bytes", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def info(edge=16, chips=1, m=0, dg=0):
    return {"grid": (edge, edge, edge), "n": edge ** 3, "itemsize": 4,
            "chips": chips, "matrix_bytes_per_apply": m,
            "diagonal_bytes": dg}


V = 16 ** 3 * 4            # one fp32 vector of the 16^3 grid


def test_cg_jacobi_matrix_free():
    cj = model("cg-jacobi")
    # S1 reads x, r, p and writes x, r; S2 reads r, p and writes p
    assert cj.per_iteration(info(), 0) == 8 * V
    # prologue reads b, the first S1 skips x, the check reads b and x
    assert cj.per_solve(info(), 0) == 2 * V


def test_cg_jacobi_assembled_and_diagonal():
    cj = model("cg-jacobi")
    # a cheap matrix is applied twice; a dear one is applied once and
    # q = A p is stored and read back (2 V)
    assert cj.per_iteration(info(m=V // 2, dg=V), 0) == 8 * V + V + 2 * V
    assert cj.per_iteration(info(m=10 * V), 0) == 8 * V + 10 * V + 2 * V


def test_cg_jacobi_vmem_credit():
    cj = model("cg-jacobi")
    # two sweeps, each may keep C on both sides: 4 C per chip
    assert cj.per_iteration(info(), 1024) == 8 * V - 4 * 1024
    assert cj.per_iteration(info(chips=4), 1024) == 8 * V - 16 * 1024
    assert cj.per_iteration(info(), V) == 4 * V
    assert cj.per_iteration(info(), 10 * V) == 0


def test_cg_mg_levels_and_counts():
    cm = model("cg-mg")
    assert cm.mg_levels((16, 16, 16)) == [(16, 16, 16), (8, 8, 8),
                                          (4, 4, 4)]
    assert cm.mg_levels((2048, 512, 512))[-1] == (16, 4, 4)
    w = 4 ** 3 * 4          # the coarsest grid of 16^3
    assert cm.per_iteration(info(), 0) == 9 * V + 5 * w
    assert cm.per_solve(info(), 0) == 3 * V + 4 * w
    # three sweeps per iteration: 6 C of credit per chip
    assert cm.per_iteration(info(), 1024) == 9 * V + 5 * w - 6 * 1024


@pytest.mark.parametrize("grid", [(16, 16, 16), (32, 16, 64),
                                  (512, 512, 512), (2048, 512, 512)])
def test_mg_levels_match_the_program(grid):
    """The copy of the level rule agrees with solvers/mg.py."""
    from mpi_petsc4py_example_tpu.solvers.mg import mg_levels
    assert model("cg-mg").mg_levels(grid) == mg_levels(*grid)
