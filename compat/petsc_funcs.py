"""The L4 wrapper API with a backend flag — the north-star entry point.

Same shape as the reference's wrapper (petsc_funcs.py:5-20):
``createPETScMat(comm, shape, csr)`` and ``solveSLEPcEigenvalues(comm, A)``.
The ``backend`` flag (default from env ``TPU_SOLVE_BACKEND``, per
BASELINE.json north_star) selects the execution path:

* ``'tpu'`` (default) — the TPU framework via the petsc4py/slepc4py facades
  in this directory: assembly, VecScatter and solves run as jit-compiled
  JAX over the device mesh.
* ``'petsc'`` — the real petsc4py/slepc4py, when installed (not available
  in the TPU environment; provided for CPU-cluster parity runs).
"""

from __future__ import annotations

import mpi_petsc4py_example_tpu as _tps

_BACKEND = _tps.backend()


def _modules(backend=None):
    backend = backend or _BACKEND
    if backend == "petsc":
        import petsc4py.PETSc as PETSc_real  # real bindings, if installed
        import slepc4py.SLEPc as SLEPc_real
        return PETSc_real, SLEPc_real
    from petsc4py import PETSc
    from slepc4py import SLEPc
    return PETSc, SLEPc


def createPETScMat(comm, shape, csr, backend=None):
    """(comm, global shape, local rebased-CSR) -> assembled distributed Mat.

    The single most important API contract in the reference (SURVEY.md §3.3).
    """
    PETSc, _ = _modules(backend)
    A = PETSc.Mat().createAIJ(comm=comm, size=shape, csr=csr)
    A.assemble()
    return A


def solveSLEPcEigenvalues(comm, A, backend=None):
    """Hermitian eigensolve with SLEPc-default semantics (nev=1, largest
    magnitude), runtime-configurable via -eps_* options."""
    _, SLEPc = _modules(backend)
    E = SLEPc.EPS().create(comm=comm)
    E.setOperators(A)
    E.setProblemType(SLEPc.EPS.ProblemType.HEP)
    E.setFromOptions()
    E.solve()
    return E
