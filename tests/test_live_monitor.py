"""Live -ksp_monitor streaming on callback-capable backends (round 4).

PETSc prints each residual AS THE SOLVE RUNS; the TPU runtime can't host
callbacks, so there the in-program buffer is replayed after the fetch
(round 3). On the CPU mesh the monitor now streams DURING the program via
ordered io_callback (krylov._LiveMonitor), one emission per device per
record, deduped host-side on monotone k.
"""

import numpy as np
import pytest

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.models import poisson2d_csr
from mpi_petsc4py_example_tpu.solvers.krylov import live_monitor_supported

# On meshes without live streaming (live_monitor_supported False) the
# designed behavior is the buffered replay — covered elsewhere. These
# tests exercise the live path specifically.
pytestmark = pytest.mark.skipif(
    not live_monitor_supported(),
    reason="live -ksp_monitor streaming unsupported on this runtime "
           "(buffered replay is the designed fallback)")


def _monitored_solve(comm, monitor, ksp_type="cg", pc_type="jacobi"):
    A = poisson2d_csr(24)
    M = tps.Mat.from_scipy(comm, A, dtype=np.float64)
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type(ksp_type)
    ksp.get_pc().set_type(pc_type)
    ksp.set_tolerances(rtol=1e-8, max_it=500)
    ksp.set_monitor(monitor)
    x, bv = M.get_vecs()
    bv.set_global(A @ np.random.default_rng(0).random(A.shape[0]))
    res = ksp.solve(bv, x)
    return ksp, res


class TestLiveMonitor:
    def test_cpu_mesh_streams_live(self, comm8):
        """On the CPU mesh the monitor mode is 'live': every iteration is
        delivered exactly once, in order, starting at the iteration-0
        initial norm."""
        assert live_monitor_supported()
        calls = []
        ksp, res = _monitored_solve(comm8,
                                    lambda k, it, rn: calls.append((it, rn)))
        assert ksp._last_monitor_mode == "live"
        ks = [it for it, _ in calls]
        assert ks == sorted(set(ks)), "duplicated or out-of-order emission"
        assert ks[0] == 0
        assert len(ks) == res.iterations + 1     # + iteration-0 norm
        assert all(rn >= 0 for _, rn in calls)

    def test_live_matches_history(self, comm8):
        """The live stream and the in-program history buffer agree."""
        calls = []
        A = poisson2d_csr(16)
        M = tps.Mat.from_scipy(comm8, A, dtype=np.float64)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-8, max_it=500)
        ksp.set_monitor(lambda k, it, rn: calls.append(rn))
        ksp.set_convergence_history()
        x, bv = M.get_vecs()
        bv.set_global(np.ones(A.shape[0]))
        ksp.solve(bv, x)
        hist = ksp.get_convergence_history()
        np.testing.assert_allclose(np.asarray(calls), hist, rtol=1e-12)

    def test_gmres_cycle_granular_live(self, comm8):
        """Cycle-granular kernels (gmres: one record per restart) stream
        their sparse k sequence in order too."""
        calls = []
        ksp, res = _monitored_solve(
            comm8, lambda k, it, rn: calls.append(it), ksp_type="gmres")
        assert ksp._last_monitor_mode == "live"
        ks = calls
        assert ks == sorted(set(ks))
        assert ks[0] == 0
