#!/usr/bin/env python3
"""The control of a cell's ``correct``: the plain reference solver put in
the program's place, one precision below the configuration's, judged by
the run's own comparison (``run.judge``). The benchmark's own runs never
run this.

    python3 perfbench/control.py --workload <cell> --seeds 1,2,3 [--dtype bfloat16]

For each seed it draws the run's right-hand-side pool, solves the traffic
mix's ``check_sample`` of them with ``references/<kind>.py``'s ``solve``
in ``--dtype`` (bfloat16 for an fp32 configuration), and prints one JSON
line with ``correct`` and the numbers compared beside their limits, as a
run prints them. The control has to come out ``correct: false``. In the
configuration's own dtype the reference is no witness at the cells'
sizes: its fp32 recurrence drifts with no true-residual check (it read
relres/rtol 1.52 and 1.80 at 512^3 on the chip, PR 22); at 16^3 it passes
(``tests/test_control.py``).
"""

from __future__ import annotations

import argparse
import json
import sys

import run as bench


def read_control(spec: dict, seed: int, dtype: str, devices,
                 max_it: int = 2000, count: int | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi_petsc4py_example_tpu as tps

    cfg, traffic = spec["config"], spec["traffic"]
    comm = tps.DeviceComm(devices=list(devices))
    opmod = bench.load_module("operators", cfg["operator"])
    refmod = bench.load_module("references", cfg["operator"])
    count = count or int(traffic["check_sample"])
    make = opmod.rhs_maker(cfg, comm)
    key = bench.seed_key(seed)
    checked, converged, iters = [], [], []
    for i in range(count):
        b = make(key, jnp.int32(i))
        x, k = refmod.solve(b, cfg, float(traffic["rtol"]), max_it, dtype)
        checked.append((np.asarray(jax.device_get(x)).astype(np.float32),
                        np.asarray(jax.device_get(b))))
        converged.append(k < max_it)    # the reference's own claim
        iters.append(k)
    correct, checks, _ = bench.judge(checked, converged, cfg, traffic,
                                     refmod)
    return {"workload": spec["cell"]["name"], "seed": seed, "dtype": dtype,
            "iterations": iters, "correct": correct, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--max-it", type=int, default=2000)
    ap.add_argument("--count", type=int, default=None)
    args = ap.parse_args(argv)
    spec = bench.cell_spec(args.workload)
    bench.set_cache_env()
    devices = bench.cell_devices(int(spec["config"]["chips"]))
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(read_control(spec, seed, args.dtype, devices,
                                      args.max_it, args.count)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
