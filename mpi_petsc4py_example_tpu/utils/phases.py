"""Env-gated phase stamps for driver wall accounting.

With ``TPU_SOLVE_PHASE_LOG=<path>`` set, :func:`stamp` appends
``(name, time.time())`` pairs and rewrites the JSON file each time —
crash-safe, and benchmarks/run_all.py config2 diffs the absolute
timestamps against its own start time to itemize tpurun setup, assembly,
solve and teardown. Without the env var every call is a no-op (one dict
lookup); no call site pays anything in production.

Stamp sites: tools/tpurun.py (tpurun_main, driver_exec),
parallel/mesh.py::DeviceComm (backend_init_begin/end — the first
``jax.devices()``), compat/petsc_funcs.py (mat_assembled, eps_solved).
"""

from __future__ import annotations

import json
import os
import threading
import time

_STAMPS: list = []
_LOCK = threading.Lock()   # tpurun's virtual ranks are threads of one
#                            process; serialize list append + file rewrite
#                            so concurrent stamps can't interleave writes


def stamp(name: str) -> None:
    path = os.environ.get("TPU_SOLVE_PHASE_LOG")
    if not path:
        return
    with _LOCK:
        _STAMPS.append((name, time.time()))
        try:
            # write-then-atomic-replace: a reader (the parent process) can
            # never observe a truncated/partial JSON file
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(_STAMPS, f)
            os.replace(tmp, path)
        except OSError:
            pass
