"""Test configuration: simulated 8-device CPU mesh + float64.

The reference tests multi-node behavior by oversubscribing MPI ranks on one
machine (``mpirun -n N``, SURVEY.md §4). The analog here: force the JAX CPU
backend with 8 virtual devices (``--xla_force_host_platform_device_count=8``)
so every sharded/collective code path runs as true SPMD without TPU hardware.
float64 is enabled globally to match the reference's fp64 PETSc stack.
``JAX_PLATFORMS=cpu`` is set before jax is imported; that alone selects
the CPU backend.
"""

import os

os.environ.setdefault("XLA_FLAGS",
                      (os.environ.get("XLA_FLAGS", "") +
                       " --xla_force_host_platform_device_count=8").strip())
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest

import mpi_petsc4py_example_tpu as tps


@pytest.fixture(scope="session")
def comm8():
    """A communicator over all 8 simulated devices."""
    assert len(jax.devices()) == 8, "expected 8 forced host devices"
    return tps.DeviceComm()


@pytest.fixture(scope="session")
def comm1():
    """A degenerate 1-device communicator (the mpirun -n 1 analog)."""
    return tps.DeviceComm(n_devices=1)


@pytest.fixture(params=[1, 3, 8], ids=["ndev1", "ndev3", "ndev8"])
def comm(request):
    """Communicators of several sizes, including a non-dividing one."""
    return tps.DeviceComm(n_devices=request.param)


@pytest.fixture(autouse=True)
def clean_options():
    """Isolate the global options DB between tests."""
    tps.global_options().clear()
    yield
    tps.global_options().clear()


@pytest.fixture(autouse=True)
def aot_cache_dir(tmp_path_factory, monkeypatch):
    """Export-cache blobs (utils/aot) in a temporary directory of each
    test's own: no test reads a blob that an earlier run left in the
    checkout, or one that another test exported from a function it had
    patched."""
    d = str(tmp_path_factory.mktemp("aot"))
    monkeypatch.setenv("TPU_SOLVE_AOT_DIR", d)
    return d
