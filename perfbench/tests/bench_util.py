"""Helpers of perfbench's tests: a cell cut to a tiny grid, run in this
process on the CPU devices (``chips=4``: z-sharded over four virtual
devices, the harness's multi-chip path)."""

import copy

import run as bench_run


def tiny_spec(workload: str, edge: int = 16, chips: int | None = None) -> dict:
    spec = copy.deepcopy(bench_run.cell_spec(workload))
    spec["config"].update(nx=edge, ny=edge, nz=edge)
    if chips is not None:
        spec["config"]["chips"] = chips
    return spec


def run_tiny(workload: str, seed: int = 7, seconds: float = 0.5,
             trace: bool = False, edge: int = 16,
             chips: int | None = None) -> dict:
    spec = tiny_spec(workload, edge, chips)
    devices = bench_run.cell_devices(int(spec["config"]["chips"]),
                                     allow_cpu=True)
    return bench_run.run_cell(spec, seed, seconds, trace, devices, cpu=True,
                              log=lambda *a: None)
