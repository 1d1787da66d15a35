"""The whole run, with the chip look skipped and the timed path broken
underneath, must come out ``correct: false``: once for each fault a solve
cell can have; the exchange between chips on the MG cell z-sharded over
four virtual devices, the path a four-chip cell takes. A batch with half
left out does not exist here: each solve is one right-hand side."""

import jax.numpy as jnp
import pytest

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.models import stencil as stencil_mod
from mpi_petsc4py_example_tpu.solvers import krylov
from bench_util import run_tiny


def _wrap_solve(monkeypatch, after):
    orig = tps.KSP.solve

    def solve(self, b, x, **kw):
        res = orig(self, b, x, **kw)
        if not kw:                      # the timed call, not a re-entry
            after(x)
        return res

    monkeypatch.setattr(tps.KSP, "solve", solve)


CASES = [("p3d512-cg-mg", None), ("p3d512-cg-jacobi", None),
         ("p3d512-cg-mg", 4)]


@pytest.mark.parametrize("workload,chips", CASES)
def test_state_returned_unchanged(monkeypatch, workload, chips):
    _wrap_solve(monkeypatch, lambda x: setattr(
        x, "data", jnp.zeros_like(x.data)))
    res = run_tiny(workload, seconds=0.3, chips=chips)
    assert res["correct"] is False
    assert res["checks"]["relres_over_rtol"]["value"] > 1e5


@pytest.mark.parametrize("workload,chips", CASES)
def test_answer_altered_where_produced(monkeypatch, workload, chips):
    _wrap_solve(monkeypatch, lambda x: setattr(
        x, "data", x.data.at[7].add(jnp.asarray(1e-3, x.data.dtype))))
    res = run_tiny(workload, seconds=0.3, chips=chips)
    assert res["correct"] is False


def test_exchange_between_chips_left_out(monkeypatch):
    def no_exchange(axis, ndev):
        def exchange(u):
            z = jnp.zeros_like(u[0])
            return z, z
        return exchange

    monkeypatch.setattr(stencil_mod, "make_plane_exchange", no_exchange)
    # the solve programs are cached per process: build them anew
    monkeypatch.setattr(krylov, "_PROGRAM_CACHE", {})
    res = run_tiny("p3d512-cg-mg", seconds=0.3, chips=4)
    assert res["correct"] is False


def test_unconverged_solve_is_failed(monkeypatch):
    orig = tps.KSP.set_tolerances

    def loose(self, rtol=None, atol=None, divtol=None, max_it=None):
        return orig(self, rtol=rtol, atol=atol, divtol=divtol,
                    max_it=None if max_it is None else min(max_it, 3))

    monkeypatch.setattr(tps.KSP, "set_tolerances", loose)
    res = run_tiny("p3d512-cg-jacobi", seconds=0.3)
    assert res["correct"] is False and res["failed"] > 0
