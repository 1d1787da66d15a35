"""Rules that decide where the program runs and what it assumes of the
device: the compile-cache placement, the per-device tables that refuse an
unknown chip, the native build key, and the capability gates as the chip
showed them (chip_smoke.py, PR 21)."""

import os
import subprocess
import sys
import types

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cache_dir_in_fresh_process(env_value):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, mpi_petsc4py_example_tpu as t;"
         "print(jax.config.jax_compilation_cache_dir);"
         "print(t.compile_cache_dir())"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


class TestCompileCachePlacement:
    def test_env_var_wins(self, tmp_path):
        """With JAX_COMPILATION_CACHE_DIR set the package sets no
        directory of its own: JAX's cache is the variable's."""
        d = str(tmp_path / "cc")
        assert _cache_dir_in_fresh_process(d) == [d, d]

    def test_default_is_fixed_path_in_checkout(self):
        want = os.path.join(REPO, ".jax_cache")
        assert _cache_dir_in_fresh_process(None) == [want, want]

    def test_aot_default_in_checkout(self, monkeypatch):
        from mpi_petsc4py_example_tpu.utils import aot
        monkeypatch.delenv("TPU_SOLVE_AOT_DIR", raising=False)
        assert aot.cache_dir() == os.path.join(REPO, ".tpu_solve_cache",
                                               "aot")


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, REPO)
    import bench
    sys.path.remove(REPO)
    return bench


class TestDeviceTables:
    def test_hbm_peak_v5e(self, bench):
        assert bench.hbm_peak_gbps("TPU v5 lite") == 819.0

    @pytest.mark.parametrize("kind", ["TPU v4", "TPU v6 lite", "cpu"])
    def test_hbm_peak_unknown_raises(self, bench, kind):
        with pytest.raises(ValueError, match="no HBM peak known"):
            bench.hbm_peak_gbps(kind)


class TestNativeBuildKey:
    def test_key_tracks_source(self):
        from mpi_petsc4py_example_tpu.utils import native
        a = native.build_key(b"int f() { return 1; }")
        assert a == native.build_key(b"int f() { return 1; }")
        assert a != native.build_key(b"int f() { return 2; }")

    def test_loaded_library_matches_this_source(self):
        from mpi_petsc4py_example_tpu.utils import native
        if not native.available():
            pytest.skip(native.status())
        with open(os.path.join(REPO, "native", "csrkit.cpp"), "rb") as fh:
            key = native.build_key(fh.read())
        assert native.status() == f"loaded libcsrkit-{key}.so"


def _fake_comm(platform):
    dev = types.SimpleNamespace(platform=platform)
    return types.SimpleNamespace(devices=[dev])


class TestCapabilityGates:
    @pytest.mark.parametrize("platform,live", [("cpu", True), ("tpu", True),
                                               ("gpu", False)])
    def test_live_monitor(self, platform, live):
        from mpi_petsc4py_example_tpu.solvers.krylov import (
            live_monitor_supported)
        assert live_monitor_supported(_fake_comm(platform)) is live

    @pytest.mark.parametrize("dtype,trusted", [
        (np.float32, True), (np.float64, True), (np.complex64, True),
        (np.complex128, False)])
    def test_device_eigh_on_tpu(self, dtype, trusted):
        from mpi_petsc4py_example_tpu.solvers.eps import (
            _device_eigh_trustworthy)
        assert _device_eigh_trustworthy(_fake_comm("tpu"),
                                        np.dtype(dtype)) is trusted

    @pytest.mark.parametrize("dtype,trusted", [(np.float32, True),
                                               (np.float64, False)])
    def test_device_matmul_on_tpu(self, dtype, trusted):
        from mpi_petsc4py_example_tpu.solvers.eps import (
            _device_matmul_trustworthy)
        assert _device_matmul_trustworthy(_fake_comm("tpu"),
                                          np.dtype(dtype)) is trusted
