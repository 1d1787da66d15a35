"""The control: the plain reference CG, one precision below the
configuration's fp32, comes out ``correct: false`` by the run's own
comparison (``run.judge``); at 16^3 the same reference in fp32 comes out
``correct: true``. On the CPU; the chip readings at the
cells' sizes are in PERF.md (at 512^3 the fp32 reference, which has no
true-residual check, reads 1.52: its recurrence drifts)."""

import pytest

import control
import run as bench_run
from bench_util import tiny_spec


@pytest.mark.parametrize("workload,chips", [("p3d512-cg-mg", None),
                                            ("p3d512-cg-jacobi", None),
                                            ("p3d512-cg-mg", 4)])
def test_bf16_reference_fails_fp32_passes(workload, chips):
    spec = tiny_spec(workload, chips=chips)
    devices = bench_run.cell_devices(int(spec["config"]["chips"]),
                                     allow_cpu=True)
    low = control.read_control(spec, 2 ** 33 + 1, "bfloat16", devices,
                               max_it=400, count=2)
    c = low["checks"]["relres_over_rtol"]
    assert low["correct"] is False and c["value"] > 3 * c["limit"]
    own = control.read_control(spec, 2 ** 33 + 1, "float32", devices,
                               max_it=400, count=2)
    assert own["correct"] is True, own
