"""tpu-sparse-solve: TPU-native distributed sparse linear algebra.

A brand-new framework with the capability surface of the petsc4py/slepc4py
MPI example (`Dxslab/mpi-petsc4py-example`): distributed AIJ-style sparse
matrices and vectors, Krylov solvers with preconditioners, a Hermitian
eigensolver, a PETSc-style options database and row-block data distribution —
re-designed for TPU (JAX/XLA/Pallas): row-sharded HBM storage over a
`jax.sharding.Mesh`, jit-compiled `shard_map` Krylov loops whose reductions
are `lax.psum` collectives over ICI, and `device_put`-based data placement
replacing MPI point-to-point scatter.

See SURVEY.md at the repo root for the reference analysis this builds to.
"""

import os as _os

import jax as _jax

# The reference stack is fp64-native (PETSc/MUMPS). JAX canonicalizes to
# float32 unless x64 is enabled, which would silently truncate the library's
# float64 defaults — so enable it at import, PETSc-style. Opt out with
# TPU_SOLVE_NO_X64=1 (e.g. for pure-fp32 TPU benchmarking).
if _os.environ.get("TPU_SOLVE_NO_X64", "0") != "1":
    _jax.config.update("jax_enable_x64", True)

# Persistent XLA compilation cache. Where JAX_COMPILATION_CACHE_DIR is set,
# JAX reads it and the package sets no directory of its own. Otherwise the
# cache lives at a FIXED path in the checkout: the directory is part of the
# cache key, so a temporary or per-process name would never hit again.
CHECKOUT_DIR = _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__)))


def compile_cache_dir() -> str:
    """Where this process's XLA compilation cache lives."""
    return (_os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or _os.path.join(CHECKOUT_DIR, ".jax_cache"))


if not _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    _jax.config.update("jax_compilation_cache_dir", compile_cache_dir())
# fresh-process driver runs (tpurun, the reference test2.py flow) are
# compile-dominated: cache every program that took a noticeable compile
_jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
_jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)

from .parallel.mesh import (DeviceComm, get_default_comm, set_default_comm,
                            as_comm, init_multihost)
from .parallel.partition import (
    RowLayout, row_partition, ownership_range, slice_csr_block,
    partition_csr, concat_csr_blocks)
from .core.vec import Vec
from .core.mat import Mat
from .core.shell import ShellMat
from .core.nullspace import NullSpace
from .solvers.pc import PC
from .solvers.ksp import KSP
from .solvers.refine import RefinedKSP
from .utils.convergence import (BatchedSolveResult, ConvergedReason,
                                RecoveryEvent, SolveResult)
from .utils.errors import (DeadlineExceededError, DeviceExecutionError,
                           ServerOverloadedError, SilentCorruptionError)
from .utils.options import Options, global_options, init, backend
from .utils import petsc_io
from . import resilience
from . import telemetry
from .resilience.faults import inject_faults

__version__ = "0.1.0"

__all__ = [
    "DeviceComm", "get_default_comm", "set_default_comm", "as_comm",
    "init_multihost",
    "RowLayout", "row_partition", "ownership_range", "slice_csr_block",
    "partition_csr", "concat_csr_blocks",
    "Vec", "Mat", "ShellMat", "NullSpace", "PC", "KSP", "RefinedKSP",
    "EPS", "ST", "SVD",
    "ConvergedReason", "RecoveryEvent", "SolveResult",
    "BatchedSolveResult",
    "DeviceExecutionError", "SilentCorruptionError",
    "DeadlineExceededError", "ServerOverloadedError",
    "Options", "global_options", "init", "backend", "petsc_io",
    "resilience", "telemetry", "inject_faults", "RetryPolicy",
    "resilient_solve",
    "resilient_solve_many", "ElasticPolicy", "HealthMonitor",
    "KSPFallbackChain",
    "SolveServer", "ServedSolveResult", "ServerClosedError",
    "SolveRouter", "QoSClass", "AutoscalePolicy",
    "MultisplitSolver", "MultisplitResult", "StaleExchange",
]


def __getattr__(name):
    # EPS/ST/SVD + resilience solver wrappers imported lazily to keep base
    # import light
    if name == "EPS":
        from .solvers.eps import EPS
        return EPS
    if name == "ST":
        from .solvers.st import ST
        return ST
    if name == "SVD":
        from .solvers.svd import SVD
        return SVD
    if name in ("RetryPolicy", "resilient_solve",
                "resilient_solve_many", "KSPFallbackChain",
                "ElasticPolicy", "HealthMonitor"):
        return getattr(resilience, name)
    if name in ("SolveServer", "ServedSolveResult", "ServerClosedError",
                "SolveRouter", "QoSClass", "AutoscalePolicy"):
        # the serving layer pulls in KSP + resilience machinery — lazy,
        # like the other solver-object imports above
        from . import serving as _serving
        return getattr(_serving, name)
    if name in ("MultisplitSolver", "MultisplitResult"):
        from .solvers import multisplit as _multisplit
        return getattr(_multisplit, name)
    if name == "StaleExchange":
        from .parallel.exchange import StaleExchange
        return StaleExchange
    raise AttributeError(name)
