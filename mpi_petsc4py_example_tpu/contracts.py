"""Program contracts: the declarative registry behind ``tpscheck``.

Round 16. The collective-schedule guarantees this repo actually ships —
one psum per pipelined iteration, one stacked Gram psum per s-block,
vector-sized SpMV gathers and nothing larger, gather op counts
independent of the RHS-block width, halved bf16 byte budgets, the
``[outer, inner]`` schedules of the fused megasolve programs — used to
live as ~1,000 lines of hand-written asserts in
``tests/test_collective_volume.py``.  Each new plan re-derived its pins
by hand and nothing could check them outside that one test file.

This module turns each pin into DATA: a :class:`ProgramContract` names a
program class (kind × plan schedule × guard/precision/batch axis), knows
how to lower it over the 8-device host grid, and declares the
communication schedule the lowering must exhibit — own reduce-site
counts per while-loop depth, collective byte budgets as functions of
``(n, k, dtype)``, gather-op counts, reduce-channel dtypes, and donation
aliasing.  The checker (``tools/tpscheck``) lowers every registered
contract, parses the StableHLO with :mod:`.utils.hlo`, and diffs actual
vs. declared; the collective-volume tests are now thin ``tpscheck``
invocations, and a new plan gets lowered-HLO gating by writing ONE entry
here.

Cross-program pins (the k=8 program has the SAME gather op count as
k=1; the bf16 program ships HALF the f32 bytes) are expressed as
absolute declarations sharing a module constant — two entries citing
``_ELL_SPMV_GATHER_SITES`` cannot drift apart independently.

Declared numbers are all MEASURED (lower, parse, pin), never derived
from wishful algebra; ``tpscheck --update-baseline`` snapshots the full
observed metrics so even unpinned drift is caught.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import os
from typing import Callable

import numpy as np
import scipy.sparse as sp

#: every AOT program kind the repo compiles, in one authoritative place —
#: the reverse-coverage meta-test pins registry kinds == this vocabulary
#: and greps the solver sources for each literal's use site
PROGRAM_KINDS = ("ksp", "ksp_many", "megasolve", "megasolve_many",
                 "persistent_serve",
                 "seedfacto", "restartfacto", "heploop",
                 "multisplit_block", "multisplit_residual")

#: problem geometry every contract lowers at (8 host devices; 512 % 8
#: == 0, so n_pad == n and the budgets below are exact, not padded)
N = 512
NCV = 16
NRHS = 8
STENCIL_SHAPE = (16, 16, 16)


# ---------------------------------------------------------------------------
# contract schema
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ProgramContract:
    """One program class and its declared communication schedule.

    Every expectation field is optional (``None`` = not pinned by this
    contract); the checker verifies exactly the declared subset, and the
    committed baseline (``tools/tpscheck/baseline.json``) catches drift
    in everything else.
    """

    name: str                     # unique, e.g. "ksp/pipecg/ell"
    kind: str                     # one of PROGRAM_KINDS
    description: str
    build: Callable               # (comm) -> lowered StableHLO text
    # --- reduce channel ---
    #: per-depth OWN all_reduce counts along the largest while chain
    #: (utils.hlo.nested_loop_reduce_site_chain)
    reduce_site_chain: tuple | None = None
    #: whole-program all_reduce op count (init + loop + epilogue) — the
    #: absolute form of the old "guarded <= plain" / "rr on == off"
    #: cross-lowering pins
    total_reduce_sites: int | None = None
    #: allowed reduce-channel element types (every all_reduce result
    #: dtype must be in this set)
    reduce_dtypes: frozenset | None = None
    # --- gather channel ---
    gather_sites: int | None = None        # exact all_gather op count
    gather_sites_max: int | None = None
    gather_elems: int | None = None        # exact per-site element count
    gather_elems_max: int | None = None
    gather_bytes: int | None = None        # exact per-site byte volume
    forbid_gathers: bool = False           # DIA/banded: no all_gather
    # --- halo (ppermute) channel ---
    ppermute_sites: int | None = None
    ppermute_sites_min: int | None = None
    ppermute_total_bytes: int | None = None
    # --- donation ---
    min_donated_args: int | None = None    # jax.buffer_donor markers
    min_aliased_outputs: int | None = None  # committed tf.aliasing_output
    #: repo-relative source files this contract's lowering depends on —
    #: ``tpscheck --changed-files`` re-lowers a contract iff one of
    #: these (or contracts.py itself) changed
    deps: tuple = ()


# ---------------------------------------------------------------------------
# model problems (memoized; every contract lowers the same operators)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=1)
def _ell_scipy():
    """Random sparsity — enough distinct diagonals that the DIA layout
    is rejected and the general ELL all_gather path is kept."""
    rng = np.random.default_rng(11)
    A = sp.random(N, N, density=0.02, random_state=rng, format="csr")
    return (A + sp.eye(N, format="csr") * N).tocsr()   # diag dominant


@functools.lru_cache(maxsize=1)
def _dia_scipy():
    from .models import tridiag_family
    return tridiag_family(N)


def _mat(comm, operator="ell", dtype=None):
    import mpi_petsc4py_example_tpu as tps
    if operator == "stencil":
        from .models import StencilPoisson3D
        kw = {} if dtype is None else {"dtype": dtype}
        return StencilPoisson3D(comm, *STENCIL_SHAPE, **kw)
    A = _ell_scipy() if operator == "ell" else _dia_scipy()
    kw = {} if dtype is None else {"dtype": dtype}
    M = tps.Mat.from_scipy(comm, A, **kw)
    if operator == "ell":
        assert M.dia_vals is None, "contract needs the general ELL path"
    return M


@contextlib.contextmanager
def _raw_programs():
    """Disable the AOT wrapper (so ``.lower()`` is reachable on the raw
    traced program) and clear the program caches on BOTH sides — the
    injected-regression tests monkeypatch plan seams and re-lower, and a
    cache hit keyed identically to the healthy program would hand back
    the unregressed lowering. ``aot_on`` is part of every cache key, so
    this never pollutes the wrapped-program caches."""
    from .solvers import eps as eps_mod
    from .solvers import krylov as krylov_mod
    from .solvers import megasolve as mega_mod

    def _clear():
        krylov_mod._PROGRAM_CACHE.clear()
        krylov_mod._PROGRAM_CACHE_MANY.clear()
        mega_mod._MEGASOLVE_CACHE.clear()
        mega_mod._MEGASOLVE_CACHE_MANY.clear()
        mega_mod._PERSISTENT_CACHE.clear()
        eps_mod._PROGRAM_CACHE.clear()

    prev = os.environ.get("TPU_SOLVE_AOT")
    os.environ["TPU_SOLVE_AOT"] = "0"
    _clear()
    try:
        yield
    finally:
        _clear()
        if prev is None:
            os.environ.pop("TPU_SOLVE_AOT", None)
        else:
            os.environ["TPU_SOLVE_AOT"] = prev


# ---------------------------------------------------------------------------
# lowering builders (the one place the lower-argument shapes live)
# ---------------------------------------------------------------------------


def _ksp_pc(comm, M, ksp_type, pc_type):
    import mpi_petsc4py_example_tpu as tps
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type(ksp_type)
    ksp.get_pc().set_type(pc_type)
    ksp.set_up()
    return ksp.get_pc()


def _guard_checksums(comm, M, pc, abft_pc=True):
    from .resilience import abft
    cs = abft.column_checksum(M)
    out = [cs] + ([abft.pc_checksum(pc, M)] if abft_pc else [])
    return tuple(comm.put_rows_many(out))


def lower_ksp(comm, ksp_type="cg", pc_type="none", operator="ell",
              dtype=None, guard=False, rr=False, nrhs=None,
              sstep_s=None, donate=False, wrap_op=None):
    """Lower a (possibly guarded/batched/banded/low-precision) KSP
    program to StableHLO text — the single entry point every ``ksp`` /
    ``ksp_many`` contract builds through.

    ``wrap_op`` (operator shim applied to the built Mat) exists for the
    injected-regression tests: a deliberately regressed operator rides
    the SAME builder, proving the checker — not a bespoke assert — has
    teeth.
    """
    from .solvers.krylov import (build_ksp_program,
                                 build_ksp_program_many)
    from .utils.dtypes import tolerance_dtype
    with _raw_programs():
        M = _mat(comm, operator, dtype)
        if wrap_op is not None:
            M = wrap_op(M)
        pc = _ksp_pc(comm, M, ksp_type, pc_type)
        dt = (np.dtype(np.float64) if dtype is None
              else tolerance_dtype(M.dtype))
        rtol = dt.type(1e-8 if dtype is None else 1e-2)
        kw = {}
        if sstep_s is not None:
            kw["sstep_s"] = sstep_s
        if nrhs is not None:
            prog = build_ksp_program_many(
                comm, ksp_type, pc, M, nrhs=nrhs, abft=guard,
                abft_pc=guard, rr=rr, donate=donate, **kw)
            cs_args = (_guard_checksums(comm, M, pc) if guard else ())
            # the RHS/iterate blocks ride the STORAGE dtype — a
            # tolerance-width block would silently widen every gather
            sd = np.dtype(M.dtype)
            Bp = comm.put_rows(np.zeros((N, nrhs), sd))
            X0 = comm.put_rows(np.zeros((N, nrhs), sd))
            tail = ((dt.type(256.0), np.int32(25)) if guard else ())
            return prog.lower(
                M.device_arrays(), pc.device_arrays(), *cs_args, Bp, X0,
                rtol, dt.type(0.0), dt.type(0.0), np.int32(50),
                *tail).as_text()
        prog = build_ksp_program(comm, ksp_type, pc, M, abft=guard,
                                 abft_pc=guard, rr=rr, donate=donate,
                                 **kw)
        cs_args = (_guard_checksums(comm, M, pc) if guard else ())
        x, b = M.get_vecs()
        tail = ()
        if guard:
            tail = (dt.type(256.0), np.int32(25 if rr else 0))
            if ksp_type == "sstep":
                tail = tail + (np.int32(3),)
        return prog.lower(
            M.device_arrays(), pc.device_arrays(), *cs_args, b.data,
            x.data, rtol, dt.type(0.0), dt.type(0.0), np.int32(50),
            *tail).as_text()


def lower_megasolve(comm, ksp_type="cg", pc_type="jacobi", guard=False,
                    rr=False, nrhs=None, operator="ell",
                    stencil_fastpath=False):
    """Lower a fused whole-solve (megasolve) program to StableHLO
    text."""
    from .solvers.megasolve import (build_megasolve_program,
                                    build_megasolve_program_many)
    from .utils.convergence import ConvergedReason
    with _raw_programs():
        M = _mat(comm, operator)
        n = int(M.shape[0])
        pc = _ksp_pc(comm, M, ksp_type, pc_type)
        dt = np.dtype(np.float64)
        scal = (dt.type(1e-10), dt.type(0.0), dt.type(1e-10),
                dt.type(0.0), np.int32(50), np.int32(4),
                np.int32(ConvergedReason.DIVERGED_MAX_IT))
        cs_args = ()
        if guard:
            cs_args = _guard_checksums(comm, M, pc)
            scal = scal + (dt.type(256.0), np.int32(25 if rr else 0))
        if nrhs is not None:
            prog = build_megasolve_program_many(
                comm, ksp_type, pc, M, None, nrhs=nrhs, abft=guard,
                abft_pc=guard, rr=rr,
                stencil_fastpath=stencil_fastpath)
            Bp = comm.put_rows(np.zeros((n, nrhs)))
            X0 = comm.put_rows(np.zeros((n, nrhs)))
            return prog.lower(M.device_arrays(), pc.device_arrays(),
                              *cs_args, Bp, X0, *scal).as_text()
        prog = build_megasolve_program(comm, ksp_type, pc, M, None,
                                       abft=guard, abft_pc=guard, rr=rr,
                                       stencil_fastpath=stencil_fastpath)
        x, b = M.get_vecs()
        return prog.lower(M.device_arrays(), pc.device_arrays(),
                          *cs_args, b.data, x.data, *scal).as_text()


def lower_persistent(comm, ksp_type="cg", pc_type="jacobi", nrhs=NRHS,
                     operator="ell", stencil_fastpath=False):
    """Lower the persistent-serving multi-request program
    (serving/persistent.py) to StableHLO text: the megasolve_many body
    fed PER-SLOT ``(nrhs,)``-shaped rtol/atol operands, with the X0
    slot buffer donated (the double-buffered launch discipline)."""
    from .solvers.megasolve import build_megasolve_program_many
    from .utils.convergence import ConvergedReason
    with _raw_programs():
        M = _mat(comm, operator)
        n = int(M.shape[0])
        pc = _ksp_pc(comm, M, ksp_type, pc_type)
        dt = np.dtype(np.float64)
        rt = np.full(nrhs, 1e-10)
        at = np.zeros(nrhs)
        scal = (rt, at, rt.copy(), dt.type(0.0), np.int32(50),
                np.int32(4), np.int32(ConvergedReason.DIVERGED_MAX_IT))
        prog = build_megasolve_program_many(
            comm, ksp_type, pc, M, None, nrhs=nrhs, donate=True,
            stencil_fastpath=stencil_fastpath, persistent=True)
        Bp = comm.put_rows(np.zeros((n, nrhs)))
        X0 = comm.put_rows(np.zeros((n, nrhs)))
        return prog.lower(M.device_arrays(), pc.device_arrays(),
                          Bp, X0, *scal).as_text()


def lower_seedfacto(comm):
    from .solvers.eps import _build_seed_facto_program
    with _raw_programs():
        M = _mat(comm, "ell")
        prog = _build_seed_facto_program(comm, M, NCV)
        v0 = comm.put_rows(np.zeros(N))
        return prog.lower(M.device_arrays(), (), v0).as_text()


def lower_restartfacto(comm):
    from .solvers.eps import _build_restart_facto_program
    with _raw_programs():
        M = _mat(comm, "ell")
        prog = _build_restart_facto_program(comm, M, NCV)
        n_pad = comm.padded_size(N)
        V = np.zeros((NCV + 1, n_pad))
        H = np.zeros((NCV + 1, NCV))
        S = np.zeros((NCV, NCV))
        return prog.lower(M.device_arrays(), (), V, H, S,
                          np.int32(NCV // 2)).as_text()


def lower_heploop(comm):
    from .solvers.eps import _build_hep_loop_program
    with _raw_programs():
        M = _mat(comm, "dia")
        prog = _build_hep_loop_program(comm, M, NCV, NCV // 2, 1,
                                       which="largest_magnitude",
                                       st_type="shift")
        v0 = comm.put_rows(np.zeros(N))
        dt = np.dtype(np.float64)
        return prog.lower(M.device_arrays(), (), v0, dt.type(1e-8),
                          dt.type(0.0), dt.type(0.0),
                          np.int32(10)).as_text()


def lower_multisplit_block(comm, ksp_type="cg", pc_type="jacobi"):
    """Lower the inner-block program of the async multisplit tier: the
    block's own KSP on a **1-device sub-communicator** over its diagonal
    block ``A_ii`` — the program one ``multisplit.step`` dispatches.
    Every all_reduce/all_gather in it is a singleton-group no-op on the
    wire: ZERO outer (cross-device) collectives per async step, the
    tier's defining contract (the only cross-device collective lives in
    ``multisplit_residual``, paid per convergence check)."""
    import jax
    from .parallel.mesh import DeviceComm
    from .solvers.krylov import build_ksp_program
    with _raw_programs():
        sub = DeviceComm(devices=[jax.devices()[0]])
        import mpi_petsc4py_example_tpu as tps
        nb = len(jax.devices())
        blk = _ell_scipy()[: N // nb, : N // nb].tocsr()
        M = tps.Mat.from_scipy(sub, blk)
        pc = _ksp_pc(sub, M, ksp_type, pc_type)
        prog = build_ksp_program(sub, ksp_type, pc, M)
        x, b = M.get_vecs()
        dt = np.dtype(np.float64)
        return prog.lower(
            M.device_arrays(), pc.device_arrays(), b.data, x.data,
            dt.type(1e-2), dt.type(0.0), dt.type(0.0),
            np.int32(50)).as_text()


def lower_multisplit_residual(comm):
    """Lower the consistent-cut residual program of the async tier:
    ``||b - A x||^2`` over the FULL mesh — the tier's ONLY cross-device
    collective, one psum per convergence check (solvers/multisplit.py
    ``build_multisplit_residual_program``)."""
    from .solvers.multisplit import build_multisplit_residual_program
    with _raw_programs():
        M = _mat(comm, "ell")
        prog = build_multisplit_residual_program(comm, M)
        b = comm.put_rows(np.zeros(N))
        x = comm.put_rows(np.zeros(N))
        return prog.lower(*M.device_arrays(), b, x).as_text()


# ---------------------------------------------------------------------------
# measured schedule constants — shared between entries so cross-program
# pins (same gather count at k=1 and k=8; same site count at f32 and
# bf16) cannot drift apart independently
# ---------------------------------------------------------------------------

#: all_gather op count of the ELL CG program (pc none) — identical at
#: k=1 and k=NRHS: the batched comm contract ships the whole RHS block
#: per gather, op count independent of k
ELL_CG_GATHER_SITES = 2
#: same, jacobi-PC single-RHS programs (plain / f32 / bf16 twins)
ELL_CG_JACOBI_GATHER_SITES = 2
#: same, the guarded (ABFT+rr) jacobi programs at k=1 and k=NRHS
ELL_GUARD_GATHER_SITES = 3
#: same, the batched jacobi mixed-precision twins (f32 vs bf16)
ELL_CG_MANY_JACOBI_GATHER_SITES = 2
#: same, the batched pipelined programs at k=1 and k=NRHS
ELL_PIPECG_MANY_GATHER_SITES = 4
#: same, the s-step (s=4) programs: single-RHS, k=1, and k=NRHS all
#: gather once per operator apply in the basis build — 2s+1 sites
ELL_SSTEP_GATHER_SITES = 9
#: whole-program all_reduce count: the guarded jacobi CG program may
#: never exceed the PLAIN one (ABFT partials ride stacked psums), and
#: replacement on/off must not change the count (the verifier lives in
#: the every-N conditional branch, traced either way)
ELL_CG_JACOBI_TOTAL_REDUCES = 6
ELL_GUARD_TOTAL_REDUCES = 5
#: guarded batched program: same total at k=1 and k=NRHS
ELL_GUARD_MANY_TOTAL_REDUCES = 5
#: DIA open-chain halo: ppermute site count and total element volume —
#: shared by the f32/bf16 twins, whose BYTE budgets then differ only by
#: the declared element width (the halved-bytes pin, declaratively).
#: The tridiagonal halo is ONE boundary row each way per SpMV: 4 sites,
#: 1 element each
DIA_PPERMUTE_SITES = 4
DIA_PPERMUTE_ELEMS = 4
#: stencil z-plane halo twins, same structure
STENCIL_PPERMUTE_SITES = 4
STENCIL_PPERMUTE_ELEMS = 1024


def _elt_bytes(elt):
    from .utils.hlo import ELT_BYTES
    return ELT_BYTES[elt]


# ---------------------------------------------------------------------------
# dependency sets for --changed-files selection
# ---------------------------------------------------------------------------

_PKG = "mpi_petsc4py_example_tpu"
_KSP_DEPS = (f"{_PKG}/solvers/krylov.py", f"{_PKG}/solvers/cg_plans.py",
             f"{_PKG}/ops/spmv.py")
_GUARD_DEPS = _KSP_DEPS + (f"{_PKG}/resilience/abft.py",)
_DIA_DEPS = _KSP_DEPS + (f"{_PKG}/models/generators.py",)
_STENCIL_DEPS = _KSP_DEPS + (f"{_PKG}/models/stencil.py",
                             f"{_PKG}/ops/pallas_stencil.py")
_MEGA_DEPS = _KSP_DEPS + (f"{_PKG}/solvers/megasolve.py",)
_MEGA_STENCIL_DEPS = _MEGA_DEPS + (f"{_PKG}/models/stencil.py",
                                   f"{_PKG}/ops/pallas_stencil.py")
_PERSISTENT_DEPS = _MEGA_DEPS + (f"{_PKG}/serving/persistent.py",)
_EPS_DEPS = (f"{_PKG}/solvers/eps.py", f"{_PKG}/ops/spmv.py")

_F64 = frozenset({"f64"})
_F64F32 = frozenset({"f64", "f32"})


def _n_pad():
    # 512 % 8 == 0 on the 8-device grid — padding is the identity, and
    # the registry pins literal element counts
    return N


# ---------------------------------------------------------------------------
# the registry
# ---------------------------------------------------------------------------


def _contracts():
    import jax.numpy as jnp
    n = _n_pad()
    C = ProgramContract
    return (
        # ----- ELL SpMV volume (the VecScatter analog) -----
        C(name="ksp/cg/ell", kind="ksp",
          description="classic CG, ELL operator, no PC: every "
                      "all-gather is exactly one padded vector (the "
                      "SpMV x-gather) — anything larger is a "
                      "replication regression",
          build=lambda comm: lower_ksp(comm),
          gather_sites=ELL_CG_GATHER_SITES, gather_elems=n,
          reduce_dtypes=_F64,
          deps=_KSP_DEPS),
        C(name="ksp/cg/dia", kind="ksp",
          description="classic CG on a banded (DIA) operator: NO "
                      "all-gather at all — the open-chain ppermute "
                      "halo exchange is the whole VecScatter",
          build=lambda comm: lower_ksp(comm, operator="dia"),
          forbid_gathers=True, ppermute_sites_min=2,
          deps=_DIA_DEPS),
        # ----- reduce-site schedules: 3 / 2 / 1 -----
        C(name="ksp/cg/ell-jacobi", kind="ksp",
          description="classic CG (jacobi): the 3-site per-iteration "
                      "schedule, and the whole-program reduce count "
                      "the guarded program must not exceed",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi"),
          reduce_site_chain=(3,),
          total_reduce_sites=ELL_CG_JACOBI_TOTAL_REDUCES,
          gather_sites=ELL_CG_JACOBI_GATHER_SITES, gather_elems=n,
          deps=_KSP_DEPS),
        C(name="ksp/cg-guard/ell", kind="ksp",
          description="guarded classic CG (ABFT, replacement OFF): "
                      "2-site stacked-phase schedule; total reduce "
                      "count below the plain program's (the guard "
                      "stacks rz and ||r|| into one psum)",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       guard=True),
          reduce_site_chain=(2,),
          total_reduce_sites=ELL_GUARD_TOTAL_REDUCES,
          deps=_GUARD_DEPS),
        C(name="ksp/cg-guard-rr/ell", kind="ksp",
          description="guarded classic CG with periodic replacement "
                      "ON: identical total reduce count to rr-off "
                      "(the verifier lives in the every-N conditional "
                      "branch) and vector-sized gathers only",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       guard=True, rr=True),
          reduce_site_chain=(2,),
          total_reduce_sites=ELL_GUARD_TOTAL_REDUCES,
          gather_sites=ELL_GUARD_GATHER_SITES, gather_elems=n,
          deps=_GUARD_DEPS),
        C(name="ksp/pipecg/ell", kind="ksp",
          description="pipelined CG: exactly ONE psum site per "
                      "iteration (the communication-hiding contract)",
          build=lambda comm: lower_ksp(comm, ksp_type="pipecg",
                                       pc_type="jacobi"),
          reduce_site_chain=(1,),
          deps=_KSP_DEPS),
        C(name="ksp/pipecg-guard-rr/ell", kind="ksp",
          description="guarded pipelined CG keeps the 1-site schedule "
                      "— ABFT partials ride the same stacked psum",
          build=lambda comm: lower_ksp(comm, ksp_type="pipecg",
                                       pc_type="jacobi", guard=True,
                                       rr=True),
          reduce_site_chain=(1,),
          deps=_GUARD_DEPS),
        C(name="ksp/cg/stencil", kind="ksp",
          description="classic CG on the matrix-free stencil: 2 sites "
                      "(fused matvec+dot psum, residual-norm psum)",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       operator="stencil"),
          reduce_site_chain=(2,),
          deps=_STENCIL_DEPS),
        C(name="ksp/pipecg/stencil", kind="ksp",
          description="grid-carry stencil pipelined CG honors the "
                      "1-site contract",
          build=lambda comm: lower_ksp(comm, ksp_type="pipecg",
                                       pc_type="jacobi",
                                       operator="stencil"),
          reduce_site_chain=(1,),
          deps=_STENCIL_DEPS),
        # ----- s-step (communication-avoiding) programs -----
        C(name="ksp/sstep-s2/ell", kind="ksp",
          description="s-step CG (s=2): ONE stacked Gram psum per "
                      "s-block",
          build=lambda comm: lower_ksp(comm, ksp_type="sstep",
                                       pc_type="jacobi", sstep_s=2),
          reduce_site_chain=(1,),
          deps=_KSP_DEPS),
        C(name="ksp/sstep-s4/ell", kind="ksp",
          description="s-step CG (s=4): ONE stacked Gram psum per "
                      "s-block; basis-build gathers stay vector-sized "
                      "(an s·n-bytes basis gather is the regression)",
          build=lambda comm: lower_ksp(comm, ksp_type="sstep",
                                       pc_type="jacobi", sstep_s=4),
          reduce_site_chain=(1,), gather_elems=n,
          gather_sites=ELL_SSTEP_GATHER_SITES,
          deps=_KSP_DEPS),
        C(name="ksp/sstep-s8/ell", kind="ksp",
          description="s-step CG (s=8): ONE stacked Gram psum per "
                      "s-block",
          build=lambda comm: lower_ksp(comm, ksp_type="sstep",
                                       pc_type="jacobi", sstep_s=8),
          reduce_site_chain=(1,),
          deps=_KSP_DEPS),
        C(name="ksp/sstep-guard-rr/ell", kind="ksp",
          description="guarded s-step keeps the one-Gram-psum block "
                      "schedule — ABFT partials ride the same stack",
          build=lambda comm: lower_ksp(comm, ksp_type="sstep",
                                       pc_type="jacobi", guard=True,
                                       rr=True, sstep_s=4),
          reduce_site_chain=(1,),
          deps=_GUARD_DEPS),
        # ----- batched (multi-RHS) comm contract -----
        C(name="ksp_many/cg/ell/k1", kind="ksp_many",
          description="batched CG at nrhs=1: the gather-op-count "
                      "anchor the k=8 program must match",
          build=lambda comm: lower_ksp(comm, nrhs=1),
          gather_sites=ELL_CG_GATHER_SITES,
          gather_elems=n,
          deps=_KSP_DEPS),
        C(name="ksp_many/cg/ell/k8", kind="ksp_many",
          description="batched CG at nrhs=8: SAME gather op count as "
                      "k=1, each gather ships the whole k-wide block",
          build=lambda comm: lower_ksp(comm, nrhs=NRHS),
          reduce_site_chain=(2,),
          gather_sites=ELL_CG_GATHER_SITES,
          gather_elems=n * NRHS,
          deps=_KSP_DEPS),
        C(name="ksp_many/cg/dia/k8", kind="ksp_many",
          description="batched banded CG keeps the zero-gather "
                      "ppermute VecScatter",
          build=lambda comm: lower_ksp(comm, operator="dia",
                                       nrhs=NRHS),
          forbid_gathers=True, ppermute_sites_min=2,
          deps=_DIA_DEPS),
        C(name="ksp_many/cg-guard-rr/ell/k1", kind="ksp_many",
          description="guarded batched CG at nrhs=1: anchor for the "
                      "k-independent gather count and reduce total",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       guard=True, rr=True, nrhs=1),
          gather_sites=ELL_GUARD_GATHER_SITES, gather_elems=n,
          total_reduce_sites=ELL_GUARD_MANY_TOTAL_REDUCES,
          deps=_GUARD_DEPS),
        C(name="ksp_many/cg-guard-rr/ell/k8", kind="ksp_many",
          description="mask-aware per-column guarding keeps the "
                      "batched comm contract: gather count and reduce "
                      "total equal to k=1, bytes scale with k",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       guard=True, rr=True,
                                       nrhs=NRHS),
          gather_sites=ELL_GUARD_GATHER_SITES,
          gather_elems=n * NRHS,
          total_reduce_sites=ELL_GUARD_MANY_TOTAL_REDUCES,
          deps=_GUARD_DEPS),
        C(name="ksp_many/pipecg/ell/k1", kind="ksp_many",
          description="batched pipelined CG at nrhs=1: gather-count "
                      "anchor",
          build=lambda comm: lower_ksp(comm, ksp_type="pipecg",
                                       pc_type="jacobi", nrhs=1),
          gather_sites=ELL_PIPECG_MANY_GATHER_SITES, gather_elems=n,
          deps=_KSP_DEPS),
        C(name="ksp_many/pipecg/ell/k8", kind="ksp_many",
          description="batched pipelined CG keeps ONE reduce site per "
                      "iteration and the k=1 gather op count",
          build=lambda comm: lower_ksp(comm, ksp_type="pipecg",
                                       pc_type="jacobi", nrhs=NRHS),
          reduce_site_chain=(1,),
          gather_sites=ELL_PIPECG_MANY_GATHER_SITES,
          gather_elems=n * NRHS,
          deps=_KSP_DEPS),
        C(name="ksp_many/sstep/ell/k1", kind="ksp_many",
          description="batched s-step at nrhs=1: gather-count anchor",
          build=lambda comm: lower_ksp(comm, ksp_type="sstep",
                                       pc_type="jacobi", nrhs=1,
                                       sstep_s=4),
          gather_sites=ELL_SSTEP_GATHER_SITES, gather_elems=n,
          deps=_KSP_DEPS),
        C(name="ksp_many/sstep/ell/k8", kind="ksp_many",
          description="batched s-step keeps ONE Gram psum per block "
                      "and the k=1 gather op count",
          build=lambda comm: lower_ksp(comm, ksp_type="sstep",
                                       pc_type="jacobi", nrhs=NRHS,
                                       sstep_s=4),
          reduce_site_chain=(1,),
          gather_sites=ELL_SSTEP_GATHER_SITES,
          gather_elems=n * NRHS,
          deps=_KSP_DEPS),
        # ----- mixed-precision byte budgets -----
        C(name="ksp/cg/ell-jacobi/f32", kind="ksp",
          description="f32 CG: the full-width byte anchor of the "
                      "halved-bf16 pin (same site count, 4 B/elem)",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       dtype=jnp.float32),
          gather_sites=ELL_CG_JACOBI_GATHER_SITES,
          gather_elems=n, gather_bytes=n * 4,
          deps=_KSP_DEPS),
        C(name="ksp/cg/ell-jacobi/bf16", kind="ksp",
          description="bf16 CG ships HALF the f32 gather bytes at the "
                      "SAME sites, and keeps the 3-site schedule",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       dtype=jnp.bfloat16),
          reduce_site_chain=(3,),
          reduce_dtypes=frozenset({"f32"}),
          gather_sites=ELL_CG_JACOBI_GATHER_SITES,
          gather_elems=n, gather_bytes=n * 2,
          deps=_KSP_DEPS),
        C(name="ksp/cg-guard-rr/ell/bf16", kind="ksp",
          description="the guarded 2-site schedule survives the bf16 "
                      "plan",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       dtype=jnp.bfloat16, guard=True,
                                       rr=True),
          reduce_site_chain=(2,),
          deps=_GUARD_DEPS),
        C(name="ksp/pipecg/ell/bf16", kind="ksp",
          description="the pipelined 1-site schedule survives the "
                      "bf16 plan",
          build=lambda comm: lower_ksp(comm, ksp_type="pipecg",
                                       pc_type="jacobi",
                                       dtype=jnp.bfloat16),
          reduce_site_chain=(1,),
          deps=_KSP_DEPS),
        C(name="ksp/pipecg-guard-rr/ell/bf16", kind="ksp",
          description="the guarded pipelined 1-site schedule survives "
                      "the bf16 plan",
          build=lambda comm: lower_ksp(comm, ksp_type="pipecg",
                                       pc_type="jacobi",
                                       dtype=jnp.bfloat16, guard=True,
                                       rr=True),
          reduce_site_chain=(1,),
          deps=_GUARD_DEPS),
        C(name="ksp/cg/dia/f32", kind="ksp",
          description="f32 banded CG: the ppermute halo byte anchor "
                      "(zero gathers; total bytes = elems x 4)",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       operator="dia",
                                       dtype=jnp.float32),
          forbid_gathers=True,
          ppermute_sites=DIA_PPERMUTE_SITES,
          ppermute_total_bytes=DIA_PPERMUTE_ELEMS * 4,
          deps=_DIA_DEPS),
        C(name="ksp/cg/dia/bf16", kind="ksp",
          description="bf16 banded CG ships bf16 boundary rows: half "
                      "the f32 halo bytes at the same site count, "
                      "still zero gathers",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       operator="dia",
                                       dtype=jnp.bfloat16),
          forbid_gathers=True,
          ppermute_sites=DIA_PPERMUTE_SITES,
          ppermute_total_bytes=DIA_PPERMUTE_ELEMS * 2,
          deps=_DIA_DEPS),
        C(name="ksp/cg/stencil/f32", kind="ksp",
          description="f32 stencil CG: z-plane halo byte anchor",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       operator="stencil",
                                       dtype=jnp.float32),
          ppermute_sites=STENCIL_PPERMUTE_SITES,
          ppermute_total_bytes=STENCIL_PPERMUTE_ELEMS * 4,
          deps=_STENCIL_DEPS),
        C(name="ksp/cg/stencil/bf16", kind="ksp",
          description="bf16 stencil CG moves storage-dtype planes: "
                      "half the f32 halo bytes at the same sites",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       operator="stencil",
                                       dtype=jnp.bfloat16),
          ppermute_sites=STENCIL_PPERMUTE_SITES,
          ppermute_total_bytes=STENCIL_PPERMUTE_ELEMS * 2,
          deps=_STENCIL_DEPS),
        C(name="ksp_many/cg/ell-jacobi/k8/f32", kind="ksp_many",
          description="f32 batched CG: byte anchor of the batched "
                      "bf16 pin",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       dtype=jnp.float32, nrhs=NRHS),
          gather_sites=ELL_CG_MANY_JACOBI_GATHER_SITES,
          gather_elems=n * NRHS, gather_bytes=n * NRHS * 4,
          deps=_KSP_DEPS),
        C(name="ksp_many/cg/ell-jacobi/k8/bf16", kind="ksp_many",
          description="bf16 batched CG keeps the k-independent gather "
                      "count AND the halved per-byte width",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       dtype=jnp.bfloat16, nrhs=NRHS),
          gather_sites=ELL_CG_MANY_JACOBI_GATHER_SITES,
          gather_elems=n * NRHS, gather_bytes=n * NRHS * 2,
          deps=_KSP_DEPS),
        # ----- donation -----
        C(name="ksp/cg/ell-donated", kind="ksp",
          description="donated CG program: the x0 argument carries a "
                      "buffer-donation marker (the zero-extra-HBM "
                      "repeat-solve contract) — a pruned/lost donation "
                      "silently doubles solve residency",
          build=lambda comm: lower_ksp(comm, pc_type="jacobi",
                                       donate=True),
          min_donated_args=1,
          deps=_KSP_DEPS),
        # ----- fused megasolve programs: [outer, inner] chains -----
        C(name="megasolve/cg", kind="megasolve",
          description="fused whole-solve classic CG: inner loop keeps "
                      "the 3-site schedule, outer refinement costs 3 "
                      "init reductions + the fp64 exit-gate psum; "
                      "every gather stays one padded vector",
          build=lambda comm: lower_megasolve(comm, "cg"),
          reduce_site_chain=(4, 3), gather_elems=n,
          deps=_MEGA_DEPS),
        C(name="megasolve/cg-guard-rr/ell", kind="megasolve",
          description="fused guarded CG keeps the 2-site inner "
                      "schedule; outer = the guard's stacked init "
                      "psums + the exit gate",
          build=lambda comm: lower_megasolve(comm, "cg", guard=True,
                                             rr=True),
          reduce_site_chain=(3, 2),
          deps=_MEGA_DEPS + (f"{_PKG}/resilience/abft.py",)),
        C(name="megasolve/pipecg", kind="megasolve",
          description="fused pipelined CG keeps the ONE-site inner "
                      "contract; outer = bnorm + rn0 + the "
                      "lag-correcting final true norm + the exit gate",
          build=lambda comm: lower_megasolve(comm, "pipecg"),
          reduce_site_chain=(4, 1),
          deps=_MEGA_DEPS),
        C(name="megasolve/sstep", kind="megasolve",
          description="fused s-step: ONE Gram psum per s-block "
                      "inside, bnorm + rn0 + final exact norm + fp64 "
                      "exit gate outside",
          build=lambda comm: lower_megasolve(comm, "sstep"),
          reduce_site_chain=(4, 1),
          deps=_MEGA_DEPS),
        C(name="megasolve_many/cg/k1", kind="megasolve_many",
          description="batched fused CG at nrhs=1 keeps the 2-phase "
                      "pduo plan's inner count, independent of nrhs",
          build=lambda comm: lower_megasolve(comm, "cg", nrhs=1),
          reduce_site_chain=(4, 2),
          deps=_MEGA_DEPS),
        C(name="megasolve_many/cg/k8", kind="megasolve_many",
          description="batched fused CG at nrhs=8: same [4, 2] chain "
                      "as nrhs=1",
          build=lambda comm: lower_megasolve(comm, "cg", nrhs=NRHS),
          reduce_site_chain=(4, 2),
          deps=_MEGA_DEPS),
        C(name="megasolve/cg-stencil", kind="megasolve",
          description="fused megasolve with the stencil fused-dot "
                      "inner fast path: the Pallas kernel folds "
                      "<p, Ap> into the SpMV pass, so the inner chain "
                      "drops from the flat-apply plan's 3 sites to 2, "
                      "and the halo channel replaces every gather",
          build=lambda comm: lower_megasolve(
              comm, "cg", operator="stencil", stencil_fastpath=True),
          reduce_site_chain=(4, 2), forbid_gathers=True,
          deps=_MEGA_STENCIL_DEPS),
        C(name="megasolve_many/cg-stencil/k8", kind="megasolve_many",
          description="batched stencil fast path at nrhs=8 keeps the "
                      "[4, 2] chain with zero gathers — per-column "
                      "fused dots ride the same kernel pass",
          build=lambda comm: lower_megasolve(
              comm, "cg", nrhs=NRHS, operator="stencil",
              stencil_fastpath=True),
          reduce_site_chain=(4, 2), forbid_gathers=True,
          deps=_MEGA_STENCIL_DEPS),
        # ----- persistent serving programs -----
        C(name="persistent_serve/cg/k8", kind="persistent_serve",
          description="the resident multi-request serving program: "
                      "megasolve_many's [4, 2] schedule under per-slot "
                      "(Q,)-shaped tolerance operands, with the X0 "
                      "slot buffer donated (the double-buffer launch "
                      "discipline) — a lost donation doubles the "
                      "resident slot memory every launch",
          build=lambda comm: lower_persistent(comm),
          reduce_site_chain=(4, 2), min_donated_args=1,
          deps=_PERSISTENT_DEPS),
        C(name="persistent_serve/cg-stencil/k8",
          kind="persistent_serve",
          description="persistent serving over the stencil fused-dot "
                      "fast path: per-slot tolerances + donation + "
                      "the gather-free halo channel in one program",
          build=lambda comm: lower_persistent(
              comm, operator="stencil", stencil_fastpath=True),
          reduce_site_chain=(4, 2), min_donated_args=1,
          forbid_gathers=True,
          deps=_PERSISTENT_DEPS + (f"{_PKG}/models/stencil.py",
                                   f"{_PKG}/ops/pallas_stencil.py")),
        # ----- fused EPS programs -----
        C(name="seedfacto/ell", kind="seedfacto",
          description="seed+factorization: the only gather is the "
                      "SpMV x-gather; the (ncv+1, n_pad) basis V "
                      "stays sharded (a V gather is (ncv+1)x the "
                      "budget)",
          build=lower_seedfacto,
          gather_elems=n, gather_sites_max=2,
          deps=_EPS_DEPS),
        C(name="restartfacto/ell", kind="restartfacto",
          description="thick-restart compression + continuation: the "
                      "basis compression is a sharded matmul — "
                      "vector-sized gathers only, V never replicated",
          build=lower_restartfacto,
          gather_elems_max=n, gather_sites_max=2,
          deps=_EPS_DEPS),
        # ----- asynchronous multisplitting programs -----
        C(name="multisplit_block/cg/ell", kind="multisplit_block",
          description="inner-block program of the async tier: the "
                      "block KSP's full 3-site CG schedule rides a "
                      "1-DEVICE subcomm, so every collective is a "
                      "singleton-group no-op — ZERO outer (cross-"
                      "device) collectives per async step; a lowering "
                      "that picks up the global mesh axis would "
                      "reintroduce the synchronous stall the tier "
                      "exists to remove",
          build=lambda comm: lower_multisplit_block(comm),
          reduce_site_chain=(3,),
          total_reduce_sites=ELL_CG_JACOBI_TOTAL_REDUCES,
          gather_elems=N // 8, reduce_dtypes=_F64,
          deps=_KSP_DEPS + (f"{_PKG}/solvers/multisplit.py",)),
        C(name="multisplit_residual/ell", kind="multisplit_residual",
          description="consistent-cut residual check: the async "
                      "tier's ONLY cross-device collective — exactly "
                      "ONE fp64 psum over the full mesh, paid per "
                      "convergence CHECK (never per iteration), plus "
                      "the one vector-sized SpMV x-gather",
          build=lower_multisplit_residual,
          total_reduce_sites=1, reduce_dtypes=_F64,
          gather_sites=1, gather_elems=n,
          deps=(f"{_PKG}/solvers/multisplit.py",
                f"{_PKG}/ops/spmv.py")),
        C(name="heploop/dia", kind="heploop",
          description="whole-solve HEP loop on the banded operator: "
                      "at most vector-sized gathers, never the "
                      "basis/projected blocks (the O(1)-sync fused "
                      "loop's point)",
          build=lower_heploop,
          gather_elems_max=n, gather_sites_max=3,
          deps=_EPS_DEPS + (f"{_PKG}/models/generators.py",)),
    )


@functools.lru_cache(maxsize=1)
def contracts() -> tuple:
    """The full registry, validated: unique names, known kinds."""
    out = _contracts()
    names = [c.name for c in out]
    assert len(set(names)) == len(names), "duplicate contract names"
    for c in out:
        assert c.kind in PROGRAM_KINDS, (c.name, c.kind)
    return out


def get_contracts(names=None, kinds=None) -> tuple:
    """Registry subset by exact name and/or kind (None = no filter)."""
    out = contracts()
    if names is not None:
        wanted = set(names)
        unknown = wanted - {c.name for c in out}
        if unknown:
            raise KeyError(f"unknown contract name(s): {sorted(unknown)}")
        out = tuple(c for c in out if c.name in wanted)
    if kinds is not None:
        out = tuple(c for c in out if c.kind in set(kinds))
    return out
