#!/usr/bin/env python
"""tpurun — the framework's ``mpirun``: run a driver under N virtual ranks.

Usage::

    python tools/tpurun.py -n 4 driver.py [driver args...]

Spawns N threads, each executing ``driver.py`` as ``__main__`` with a
thread-local MPI rank (compat/mpi4py). :func:`main` takes an argument list,
so a process that already holds the chip (chip_smoke.py) runs a driver
in-process rather than in a child that would need the same chip.
Point-to-point sends/recvs and collectives rendezvous in-process; device
work (assembly, KSP/EPS solves) executes once on the rank-0 thread over the
full device mesh. This is the TPU analog of the reference's oversubscribed
``mpirun -n N python test.py`` testing idiom (SURVEY.md §4) — the way to
exercise multi-rank driver logic without a cluster or MPI.
"""

from __future__ import annotations

import argparse
import os
import sys
import threading
import traceback


def main(argv=None):
    """Run a driver script under ``-n`` virtual ranks; returns the exit
    code (1 when any rank raised). ``argv`` defaults to ``sys.argv[1:]``;
    ``sys.argv`` and ``sys.path`` are restored afterwards."""
    saved = sys.argv[:], sys.path[:]
    try:
        return _run(argv)
    finally:
        sys.argv[:], sys.path[:] = saved


def _run(argv):
    ap = argparse.ArgumentParser(prog="tpurun", add_help=True)
    ap.add_argument("-n", "--np", type=int, default=1,
                    help="number of virtual ranks (threads)")
    ap.add_argument("script", help="driver script to run")
    ap.add_argument("args", nargs=argparse.REMAINDER,
                    help="arguments passed to the driver")
    opts = ap.parse_args(argv)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    compat = os.path.join(repo, "compat")
    for p in (repo, compat):
        if p not in sys.path:
            sys.path.insert(0, p)
    # like ``python script.py`` (and mpirun): the script's own directory leads
    # sys.path, so a driver's sibling modules (e.g. the reference repo's
    # petsc_funcs.py, /root/reference/test2.py:4) shadow the compat copies
    script_dir = os.path.dirname(os.path.abspath(opts.script))
    if script_dir in sys.path:
        sys.path.remove(script_dir)
    sys.path.insert(0, script_dir)

    sys.argv = [opts.script] + opts.args

    from mpi4py import MPI as _MPI  # the facade (compat/ is on sys.path)

    with open(opts.script) as f:
        code = compile(f.read(), opts.script, "exec")

    nprocs = opts.np
    errors: list = []

    if nprocs == 1:
        _MPI._set_context(None)
        g = {"__name__": "__main__", "__file__": opts.script,
             "__builtins__": __builtins__}
        exec(code, g)
        return 0

    ctx = _MPI.VirtualContext(nprocs)
    _MPI._set_context(ctx)

    def run_rank(rank: int):
        ctx.register(rank)
        g = {"__name__": "__main__", "__file__": opts.script,
             "__builtins__": __builtins__}
        try:
            exec(code, g)
        # tpslint: disable=TPS005 — rank thread runs an arbitrary user
        # script: even SystemExit/KeyboardInterrupt must be reported and
        # must release peers blocked on collectives
        except BaseException as e:  # noqa: BLE001
            errors.append((rank, e, traceback.format_exc()))
            # release peers blocked on collectives so the job aborts
            ctx.barrier.abort()

    threads = [threading.Thread(target=run_rank, args=(r,), name=f"rank{r}")
               for r in range(nprocs)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    _MPI._set_context(None)

    if errors:
        for rank, _, tb in errors:
            print(f"--- rank {rank} failed ---\n{tb}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
