"""Each cell's whole path at a 16^3 grid on the CPU (and the MG cell
z-sharded over four virtual devices, the path a four-chip cell takes):
set-up, warm-up, a window, the check; the result line parses, ``correct``
is decided, and no device metric is printed. A traffic mix with a key the
runner does not read is refused."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import run as bench_run
from bench_util import run_tiny, tiny_spec

CELLS = [w["name"] for w in bench_run.load_json(
    os.path.join(bench_run.ROOT, "BENCHMARK.json"))["workloads"]]


@pytest.mark.parametrize("workload,chips",
                         [(c, None) for c in CELLS] + [("p3d512-cg-mg", 4)])
def test_cell_path_on_cpu(workload, chips):
    res = json.loads(json.dumps(run_tiny(workload, seed=2 ** 31 + 11,
                                         chips=chips)))
    assert set(res) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    assert res["metrics"] == {}          # CPU: no device number, any name
    assert res["device"]["platform"] == "cpu"
    chips = chips or bench_run.cell_spec(workload)["config"]["chips"]
    assert res["device"]["count"] == chips
    c = res["checks"]["relres_over_rtol"]
    assert 0 < c["value"] <= c["limit"]


def test_same_seed_same_inputs():
    import jax.numpy as jnp
    import numpy as np

    import mpi_petsc4py_example_tpu as tps
    spec = tiny_spec("p3d512-cg-mg")
    comm = tps.DeviceComm(devices=bench_run.cell_devices(1, allow_cpu=True))
    make = bench_run.load_module("operators", "stencil7").rhs_maker(
        spec["config"], comm)

    def rhs(seed, i):
        return np.asarray(make(bench_run.seed_key(seed), jnp.int32(i)))

    big = 2 ** 33 + 5
    assert np.array_equal(rhs(big, 1), rhs(big, 1))
    assert not np.array_equal(rhs(big, 1), rhs(big, 2))
    assert not np.array_equal(rhs(big, 1), rhs(big + 2 ** 32, 1))


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "p3d512-cg-mg",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_accelerator_no_result():
    p = _run_cli(bench_run.ROOT)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{")
    assert "no TPU" in p.stderr


def test_bare_benchmark_dir_no_result(tmp_path):
    shutil.copy(os.path.join(bench_run.ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(bench_run.HERE, tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__", "data"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())



def test_traffic_key_not_read_is_refused():
    traffic = dict(bench_run.cell_spec("p3d512-cg-mg")["traffic"], clients=4)
    with pytest.raises(bench_run.BenchError, match="clients"):
        bench_run.check_traffic("cg-mg-rtol1e-6", traffic)
