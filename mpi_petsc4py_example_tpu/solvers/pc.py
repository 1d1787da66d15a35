"""Preconditioners — TPU-native equivalent of PETSc ``PC`` (SURVEY.md N4/N5).

Reference usage: ``ksp.getPC(); pc.setType('lu');
pc.setFactorSolverType('mumps')`` (``test.py:40-43``). Types provided:

* ``none``   — identity.
* ``jacobi`` — inverse-diagonal scaling; a sharded elementwise multiply.
* ``bjacobi``— block Jacobi: each mesh device owns its local diagonal block's
  inverse (the TPU analog of PETSc's per-rank PCBJACOBI+LU); apply is a
  batched dense matvec on the MXU. Blocks past the dense cap on five-point
  DIA operators take PETSc's default ILU(0) sub-solve (solvers/bjilu.py).
* ``lu`` / ``cholesky`` — full direct factorization. This is the MUMPS-slot
  replacement (``test.py:43``): no multifrontal sparse direct solver exists
  for TPU (SURVEY.md §7.4), so direct solves factorize on the host in fp64
  (LAPACK) and apply on device as a dense matmul; KSPPREONLY adds iterative
  refinement. Exact for reference-scale problems; large problems should
  prefer an iterative KSP with a strong PC.
* ``sor`` / ``ssor`` — processor-local block SSOR (PETSc's parallel PCSOR
  semantics), applied exactly as a precomputed dense inverse (``-pc_sor_omega``).
* ``ilu`` / ``icc`` — per-device block incomplete factorization (scipy
  ``spilu`` setup, dense (LU)⁻¹ apply; ``-pc_factor_fill``). ``icc`` is an
  open alias of the same unsymmetric incomplete-LU path.
* ``asm`` — restricted additive Schwarz with row-overlap windows
  (``-pc_asm_overlap``, default 1), per-device window solves.
* ``mg``  — geometric multigrid V-cycle for structured stencil operators.

Note on factorization placement: XLA:TPU implements LuDecomposition only
for F32/C64 (observed on v5e), so fp64/complex factorizations happen on
host and the device applies triangular-solve-free dense products. fp32
operators on TPU take a *device* setup path for ``bjacobi``
(``-pc_setup_device``, default auto): the dense diagonal blocks ship as-is
and a batched MXU LU + Newton polish builds the inverses on chip —
orders of magnitude faster than the single-core host LAPACK sweep, same
shipped bytes, quality-gated with automatic host fallback.

Each PC exposes (a) sharded device arrays and (b) a *local* apply closure
used inside the jit-compiled shard_map solver bodies, so preconditioning
fuses into the same XLA program as the Krylov iteration.
"""

from __future__ import annotations

import itertools
from collections import Counter
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.mat import Mat
from ..parallel.mesh import DeviceComm
from ..ops.spmv import widened_einsum
from ..utils.dtypes import host_dtype, is_complex, real_eps
from jax.sharding import PartitionSpec as P

PC_TYPES = ("none", "jacobi", "bjacobi", "lu", "cholesky", "mg",
            "sor", "ssor", "ilu", "icc", "asm", "gamg", "amg",
            "shell", "composite")

_COMPOSITE_TYPES = ("additive", "multiplicative")

# global shell-apply counter: program caches key on it, so two PC instances
# with different shell functions never collide (same scheme as ShellMat)
_shell_uid = itertools.count(1)


class PC:
    """Preconditioner object, petsc4py-``PC``-shaped."""

    def __init__(self, comm=None):
        self.comm = comm
        self._type = "none"
        self._factor_solver_type = "tpu-dense"
        self._mat: Mat | None = None
        self._arrays = ()
        self._built_for = None
        self._factor_mode = "dense"  # 'dense' | 'crtri' | 'crband' |
                                     # 'hostlu' (set in set_up for
                                     # lu/cholesky: banded operators past
                                     # the dense cap use scalar/block
                                     # parallel cyclic reduction,
                                     # solvers/tridiag.py; irreducible
                                     # sparsity past every device cap
                                     # factorizes on HOST, _build_host_splu)
        self._hostlu = None          # (SuperLU factor, fp64 csr) in hostlu
        self.sor_omega = 1.0        # -pc_sor_omega (PETSc default 1)
        self.asm_overlap = 1        # -pc_asm_overlap (PETSc default 1)
        self.factor_fill = 10.0     # -pc_factor_fill (spilu fill_factor)
        self.gamg_threshold = 0.0   # -pc_gamg_threshold (PCGAMG default 0)
        self.gamg_coarse_size = 64  # -pc_gamg_coarse_eq_limit analog
        self.gamg_max_levels = 10   # -pc_mg_levels analog
        self.mg_smoother = "chebyshev"  # -pc_mg_smooth_type: 'chebyshev'
                                    # (Chebyshev-root omega schedule, round
                                    # 5) | 'jacobi' (fixed omega = 2/3)
        self.bjacobi_blocks = 0     # -pc_bjacobi_blocks (0 = one per device,
                                    # auto-split past the dense cap)
        self.setup_device = "auto"  # -pc_setup_device: 'auto' | '1' | '0' —
                                    # where block inversions run ('auto' =
                                    # device for fp32 on TPU, host LAPACK
                                    # otherwise; see _want_device_setup)
        self.setup_mode = None      # observability: 'device' | 'host' once
                                    # a placement-capable kind is set up
        self.setup_breakdown = None  # device-mode phase split (extract_s /
                                     # invert_s), for the benchmark artifact
        self.sub_solve = None       # bjacobi's block solve: 'dense' |
                                    # 'ilu0' (solvers/bjilu.py)
        self.sub_blocks = 0         # ... and its block count, all devices
        self._amg = None
        # PCSHELL: user apply (full-vector jax-traceable callable) + a uid so
        # compiled-program caches distinguish different shell functions
        self._shell_apply = None
        self._shell_apply_t = None
        self._shell_uid = 0
        # PCCOMPOSITE: child PCs + combination type
        self.composite_type = "additive"   # PETSc's PC_COMPOSITE_ADDITIVE
        self._sub_pcs: list[PC] = []

    # ---- petsc4py-shaped configuration -------------------------------------
    def set_type(self, pc_type: str):
        pc_type = str(pc_type).lower()
        if pc_type not in PC_TYPES:
            raise ValueError(f"unknown PC type {pc_type!r}; "
                             f"available: {PC_TYPES}")
        if pc_type != self._type:
            self._type = pc_type
            self._built_for = None
        return self

    setType = set_type

    def get_type(self) -> str:
        return self._type

    getType = get_type

    def set_factor_solver_type(self, name: str):
        """Accepts the reference's solver strings ('mumps', 'superlu', ...).

        All map to the TPU dense factorization — recorded for introspection.
        """
        self._factor_solver_type = str(name)
        return self

    setFactorSolverType = set_factor_solver_type

    # ---- PCSHELL (user-defined preconditioner) ------------------------------
    def set_shell_apply(self, fn):
        """PCShellSetApply analog: ``z = fn(r)`` on the full global residual.

        ``fn`` must be jax-traceable (jnp ops only) — it is inlined into the
        compiled shard_map solver program, running replicated per device.
        """
        self._shell_apply = fn
        self._shell_uid = next(_shell_uid)
        self._built_for = None
        return self

    setShellApply = set_shell_apply

    def set_shell_apply_transpose(self, fn):
        """PCShellSetApplyTranspose analog: ``z = fn(r)`` for ``Mᵀ`` —
        enables KSPBICG with a shell preconditioner."""
        self._shell_apply_t = fn
        self._shell_uid = next(_shell_uid)
        self._built_for = None
        return self

    setShellApplyTranspose = set_shell_apply_transpose

    # ---- PCCOMPOSITE (combination of preconditioners) -----------------------
    def set_composite_type(self, ctype: str):
        """'additive' (z = Σ Mᵢr) or 'multiplicative' (Gauss-Seidel-style
        sweeps with residual updates between children — needs the operator)."""
        ctype = str(ctype).lower()
        if ctype not in _COMPOSITE_TYPES:
            raise ValueError(f"unknown composite type {ctype!r}; "
                             f"available: {_COMPOSITE_TYPES}")
        if ctype != self.composite_type:
            self.composite_type = ctype
            self._built_for = None
        return self

    setCompositeType = set_composite_type

    def set_composite_pcs(self, *types):
        """Create the child PCs from type names (PCCompositeAddPCType)."""
        if len(types) == 1 and isinstance(types[0], (list, tuple)):
            types = tuple(types[0])
        self._sub_pcs = []
        for t in types:
            self.add_composite_pc(t)
        return self

    setCompositePCs = set_composite_pcs

    def add_composite_pc(self, pc_type: str):
        child = PC(self.comm)
        child.set_type(pc_type)
        self._sub_pcs.append(child)
        self._built_for = None
        return child

    addCompositePC = add_composite_pc

    def get_composite_pc(self, i: int) -> "PC":
        """Child PC ``i`` — tune its options before ``set_up``."""
        return self._sub_pcs[i]

    getCompositePC = get_composite_pc

    def set_operators(self, mat: Mat):
        if mat is not self._mat:
            self._mat = mat
            self._built_for = None
        return self

    def _tunables_key(self):
        """Every tunable baked into the built arrays, recursively through
        composite children — the rebuild-detection part of the setup key."""
        return (self._type, self.sor_omega, self.asm_overlap,
                self.factor_fill, self.gamg_threshold,
                self.gamg_coarse_size, self.gamg_max_levels,
                self.mg_smoother, self.bjacobi_blocks, self.setup_device,
                self._shell_uid,
                self.composite_type,
                tuple(c._tunables_key() for c in self._sub_pcs))

    # ---- setup: build sharded device-side data ------------------------------
    def set_up(self, mat: Mat | None = None):
        if mat is not None:
            self.set_operators(mat)
        mat = self._mat
        if mat is None:
            raise RuntimeError("PC.set_up: no operator set")
        # tunables are baked into the built arrays — they are part of the
        # key, as is the matrix's mutation counter (axpy/shift/zero_rows
        # rebuild the operator in place without changing its identity)
        build_key = (mat, getattr(mat, "_state", 0), self._tunables_key())
        if self._built_for == build_key:
            return self
        from ..telemetry import spans as _telemetry
        with _telemetry.span("pc.setup", pc_type=self._type,
                             n=int(mat.shape[0])) as sp:
            self._set_up_build(mat, build_key)
            if self.sub_solve is not None:
                sp.set_attrs(sub_solve=self.sub_solve,
                             blocks=self.sub_blocks)
            return self

    def _set_up_build(self, mat, build_key):
        """The actual factor build/placement (the ``pc.setup`` span body
        — for 'mg'/'gamg' this is the multigrid hierarchy construction,
        the MG entry point a trace itemizes)."""
        comm = mat.comm
        t = self._type
        # a rebuild must not pin a previous hostlu factorization (SuperLU
        # factor + fp64 CSR can be hundreds of MB) whatever mode it
        # resolves to now; setup_mode likewise reflects only THIS build
        self._hostlu = None
        self.setup_mode = None
        self.setup_breakdown = None
        self.sub_solve = None
        self.sub_blocks = 0
        if t == "none":
            self._arrays = ()
        elif t == "jacobi":
            diag = mat.diagonal()
            inv = np.where(diag != 0, 1.0 / np.where(diag == 0, 1.0, diag), 0.0)
            self._arrays = (comm.put_rows(inv.astype(mat.dtype)),)
        elif t == "bjacobi":
            self._arrays = _build_bjacobi(comm, mat, self.bjacobi_blocks,
                                          self.setup_device, owner=self)
        elif t in ("sor", "ssor"):
            self._arrays = _build_block_ssor(comm, mat, self.sor_omega)
        elif t in ("ilu", "icc"):
            self._arrays = _build_block_ilu(comm, mat, self.factor_fill)
        elif t == "asm":
            self._arrays = _build_asm(comm, mat, self.asm_overlap)
        elif t in ("lu", "cholesky"):
            if t == "cholesky" and hasattr(mat, "to_scipy"):
                # PETSc's cholesky assumes a symmetric (complex: Hermitian)
                # operator (crtri's transpose-apply reuse depends on it).
                # Tolerance-based: ulp-level assembly asymmetry must not
                # reject an SPD operator that factorizes fine.
                S = mat.to_scipy()
                D = (S - S.conj().T).tocsr()
                scale = abs(S).max() or 1.0
                # tolerance scales with the operator dtype: fp32 assembly
                # carries ~eps-relative accumulation asymmetry that must not
                # reject a legitimately symmetric operator
                rel = max(1e-10, 100 * real_eps(mat.dtype))
                if D.nnz and abs(D).max() > rel * scale:
                    raise ValueError(
                        "PC 'cholesky' needs a symmetric (Hermitian) "
                        "operator — use pc 'lu' for unsymmetric matrices")
            offs = set(getattr(mat, "dia_offsets", ()) or ())
            bw = max((abs(int(o)) for o in offs), default=0)
            n = mat.shape[0]
            if n > _DENSE_CAP and offs and offs <= {-1, 0, 1}:
                self._arrays = _build_tridiag_cr(comm, mat)
                self._factor_mode = "crtri"
            elif (n > _DENSE_CAP and offs and 1 < bw
                    and _bcr_fits(n, bw)):
                # banded in its given ordering: block cyclic reduction —
                # bw x bw blocks cover every offset in [-bw..bw]
                self._arrays = _build_banded_bcr(
                    comm, mat, bw, setup_device=self.setup_device,
                    owner=self)
                self._factor_mode = "crband"
            elif n > _DENSE_CAP and hasattr(mat, "to_scipy"):
                # everything else past the dense cap — general sparsity OR
                # a band too wide as given: the MUMPS slot's fill-reducing-
                # ordering move. An RCM bandwidth-reducing permutation
                # routes reducible sparsity into the banded block-CR
                # machinery (PARITY.md 'Direct solves' table); dispatch is
                # on REDUCIBILITY, never on how the matrix was stored.
                perm, bw_rcm, A_perm = _rcm_bandwidth(mat)
                if _bcr_fits(n, max(bw_rcm, 2)):
                    self._arrays = _build_banded_bcr(
                        comm, mat, max(bw_rcm, 2), perm=perm, A_perm=A_perm,
                        setup_device=self.setup_device, owner=self)
                    self._factor_mode = "crband"
                else:
                    # irreducible sparsity past every device-direct cap:
                    # factorize on HOST with scipy's SuperLU (no less
                    # faithful than the reference, whose MUMPS is itself a
                    # CPU library behind test.py:43 [external]); the solve
                    # applies host-side under KSP 'preonly' (see
                    # KSP._solve_hostlu and PARITY.md 'Direct solves')
                    self._arrays = ()
                    self._hostlu = _build_host_splu(mat, t)
                    self._factor_mode = "hostlu"
            else:
                self._arrays = _build_dense_lu(
                    comm, mat, setup_device=self.setup_device, owner=self)
                self._factor_mode = "dense"
        elif t in ("gamg", "amg"):
            from .amg import AMGHierarchy
            if not hasattr(mat, "to_scipy"):
                raise ValueError(
                    "PC 'gamg' needs an assembled matrix (Mat) to build the "
                    "aggregation hierarchy; matrix-free stencil operators "
                    "should use the geometric 'mg'")
            self._amg = AMGHierarchy(
                comm, mat.to_scipy(), mat.dtype,
                threshold=self.gamg_threshold,
                max_levels=self.gamg_max_levels,
                coarse_size=self.gamg_coarse_size)
            self._arrays = self._amg.device_arrays()
        elif t == "mg":
            if not all(hasattr(mat, a) for a in ("nx", "ny", "nz")):
                raise ValueError(
                    "PC 'mg' is the geometric multigrid V-cycle for "
                    "structured stencil operators (models.StencilPoisson3D)")
            self._arrays = ()
        elif t == "shell":
            if self._shell_apply is None:
                raise RuntimeError(
                    "PC 'shell' has no apply function — call "
                    "set_shell_apply(fn) first")
            self._arrays = ()
        elif t == "composite":
            if not self._sub_pcs:
                raise RuntimeError(
                    "PC 'composite' has no children — call "
                    "set_composite_pcs('jacobi', 'sor', ...) first")
            arrays = []
            for child in self._sub_pcs:
                child.set_up(mat)
                arrays.extend(child.device_arrays())
            if self.composite_type == "multiplicative":
                # the residual updates between children need A; ship the
                # operator's (already-device-resident) arrays along — same
                # buffers, no copy
                arrays.extend(mat.device_arrays())
            self._arrays = tuple(arrays)
        self._built_for = build_key
        return self

    setUp = set_up

    # ---- what the KSP solver factory consumes -------------------------------
    @property
    def kind(self) -> str:
        t = self._type
        if t in ("lu", "cholesky") and self._factor_mode in (
                "crtri", "crband", "hostlu"):
            return self._factor_mode
        if t == "cholesky":
            return "lu"
        if t == "amg":
            return "gamg"
        if t == "bjacobi" and self.sub_solve == "ilu0":
            return "bjacobi_ilu0"
        # sor/ssor/ilu/icc all apply as one per-device dense block matvec —
        # the same kernel shape as block Jacobi, different block algebra
        if t in ("sor", "ssor", "ilu", "icc"):
            return "bjacobi"
        return t

    def device_arrays(self) -> tuple:
        return self._arrays

    def program_key(self):
        """Part of the compiled-solver cache key: everything baked into the
        local_apply closure beyond ``kind`` (ASM overlap, shell fn identity,
        composite structure)."""
        if self.kind == "asm":
            return (self.kind, int(self.asm_overlap))
        if self.kind == "gamg":
            return self._amg.program_key()
        if self.kind == "crtri":
            # sweep count is baked into the apply loop
            return ("crtri", int(self._arrays[0].shape[0]))
        if self.kind == "crband":
            # (S, N, b) and the perm presence are baked into the apply loop
            return ("crband", len(self._arrays)) + tuple(
                int(s) for s in self._arrays[0].shape[:3])
        if self.kind == "mg":
            # the smoother's omega schedule is baked into the V-cycle
            return ("mg", self.mg_smoother)
        if self.kind == "shell":
            return ("shell", self._shell_uid)
        if self.kind == "composite":
            # multiplicative bakes the preconditioning matrix's spmv closure
            # (static DIA offsets, array count) into the apply — key on it
            mat_key = (self._mat.program_key()
                       if (self.composite_type == "multiplicative"
                           and self._mat is not None) else ())
            return (("composite", self.composite_type, mat_key)
                    + tuple(c.program_key() for c in self._sub_pcs))
        return (self.kind,)

    def in_specs(self, axis: str) -> tuple:
        """shard_map in_specs matching :meth:`device_arrays`."""
        k = self.kind
        if k in ("none", "mg"):
            return ()
        if k == "jacobi":
            return (P(axis),)
        if k in ("bjacobi", "bjacobi_ilu0"):
            return (P(axis),)
        if k == "asm":
            return (P(axis),)
        if k == "lu":
            return (P(),)
        if k in ("crtri", "crband"):
            # replicated sweep arrays + diagonal (+ RCM perm/iperm when
            # the factorization was reordered)
            return tuple(P() for _ in self._arrays)
        if k == "gamg":
            return self._amg.in_specs()
        if k == "shell":
            return ()
        if k == "composite":
            specs = []
            for child in self._sub_pcs:
                specs.extend(child.in_specs(axis))
            if self.composite_type == "multiplicative":
                specs.extend(self._mat.op_specs(axis))
            return tuple(specs)
        raise AssertionError(k)

    def local_apply(self, comm: DeviceComm, n: int):
        """Return ``apply(pc_arrays_local, r_local) -> z_local``.

        Runs *inside* shard_map: ``pc_arrays_local`` are this device's shards
        of :meth:`device_arrays`.
        """
        k = self.kind
        axis = comm.axis
        lsize = comm.local_size(n)

        if k == "hostlu":
            raise ValueError(
                "PC 'lu'/'cholesky' fell back to the host sparse-LU mode "
                "(irreducible sparsity past the dense/banded device caps); "
                "the factor applies on HOST, which an in-program iterative "
                "apply cannot call — use KSP 'preonly' (the reference's "
                "MUMPS configuration, test.py:38-43), or an iterative KSP "
                "with pc 'gamg'/'bjacobi' (PARITY.md 'Direct solves')")
        if k == "none":
            return lambda arrs, r: r
        if k == "jacobi":
            return lambda arrs, r: arrs[0] * r
        if k == "bjacobi":
            def apply(arrs, r):
                binv = arrs[0]  # this device's (nb, bs, bs) block inverses
                nb, bs = binv.shape[0], binv.shape[1]
                # nb > 1 (-pc_bjacobi_blocks): one batched MXU matmul.
                # Low-precision factor STORAGE (bf16, the mixed-precision
                # plan's PC channel) contracts in f32 via widened_einsum.
                return widened_einsum("bij,bj->bi", binv,
                                      r.reshape(nb, bs),
                                      comm.platform).reshape(-1)
            return apply
        if k == "bjacobi_ilu0":
            from .bjilu import apply
            interpret = comm.platform != "tpu"
            return lambda arrs, r: apply(arrs, r, interpret)
        if k == "asm":
            ov = int(self.asm_overlap)
            ndev = comm.size
            fwd = [(i, (i + 1) % ndev) for i in range(ndev)]
            bwd = [(i, (i - 1) % ndev) for i in range(ndev)]

            def apply(arrs, r):
                winv = arrs[0]   # (1, lsize+2ov, lsize+2ov) window inverse
                if ov:
                    # ring halo exchange: only the ov edge rows move (vs an
                    # O(n) all_gather). Wrapped halos at the global
                    # boundaries hit identity-padded, fully-decoupled window
                    # slots, so their content never reaches owned rows.
                    left = lax.ppermute(r[lsize - ov:], axis, fwd)
                    right = lax.ppermute(r[:ov], axis, bwd)
                    r_win = jnp.concatenate([left, r, right])
                else:
                    r_win = r
                z_win = winv[0] @ r_win
                # restricted additive Schwarz (PETSc's default): keep only
                # the owned interior — no overlap summation, no extra comm
                return lax.slice_in_dim(z_win, ov, ov + lsize)
            return apply
        if k == "lu":
            def apply(arrs, r):
                minv = arrs[0]  # replicated (n_pad, n_pad) inverse
                r_full = lax.all_gather(r, axis, tiled=True)
                z_full = widened_einsum("ij,j->i", minv, r_full,
                                        comm.platform)
                i = lax.axis_index(axis)
                return lax.dynamic_slice_in_dim(z_full, i * lsize, lsize)
            return apply
        if k == "crtri":
            from .tridiag import pcr_apply
            n_pad = comm.padded_size(n)

            def apply(arrs, r):
                alphas, gammas, bfin = arrs
                r_full = lax.all_gather(r, axis, tiled=True)
                x = pcr_apply(r_full[:n], alphas, gammas, bfin)
                if n_pad > n:     # padding slots pass through as zero
                    x = jnp.concatenate(
                        [x, jnp.zeros((n_pad - n,), x.dtype)])
                i = lax.axis_index(axis)
                return lax.dynamic_slice_in_dim(x, i * lsize, lsize)
            return apply
        if k == "crband":
            from .tridiag import bpcr_apply
            n_pad = comm.padded_size(n)

            def apply(arrs, r):
                alphas, gammas, binv = arrs[:3]
                Nb = binv.shape[0] * binv.shape[1]
                r_full = lax.all_gather(r, axis, tiled=True)
                d = r_full[:n]
                if len(arrs) == 5:     # RCM-reordered: solve P A Pᵀ y = P r
                    d = jnp.take(d, arrs[3])
                if Nb > n:        # identity-padded tail block rows
                    d = jnp.concatenate(
                        [d, jnp.zeros((Nb - n,), d.dtype)])
                x = bpcr_apply(d, alphas, gammas, binv)[:n]
                if len(arrs) == 5:     # x = Pᵀ y
                    x = jnp.take(x, arrs[4])
                if n_pad > n:
                    x = jnp.concatenate(
                        [x, jnp.zeros((n_pad - n,), x.dtype)])
                i = lax.axis_index(axis)
                return lax.dynamic_slice_in_dim(x, i * lsize, lsize)
            return apply
        if k == "gamg":
            return self._amg.local_apply(comm)
        if k == "shell":
            from ..parallel.mesh import full_vector_local_apply
            shell = full_vector_local_apply(self._shell_apply, comm, n)
            return lambda arrs, r: shell(r)
        if k == "composite":
            subs = [(c.local_apply(comm, n), len(c.device_arrays()))
                    for c in self._sub_pcs]
            if self.composite_type == "additive":
                def apply(arrs, r):
                    z = jnp.zeros_like(r)
                    i = 0
                    for ap, na in subs:
                        z = z + ap(arrs[i:i + na], r)
                        i += na
                    return z
                return apply
            # multiplicative: z ← z + Mᵢ (r - A z) sweeps; the operator's
            # arrays ride at the tail of the PC array tuple (see set_up)
            spmv = self._mat.local_spmv(comm)
            nmat = len(self._mat.device_arrays())

            def apply(arrs, r):
                mat_arrs = arrs[len(arrs) - nmat:] if nmat else ()
                z = None
                i = 0
                for ap, na in subs:
                    sub = arrs[i:i + na]
                    i += na
                    if z is None:
                        z = ap(sub, r)
                    else:
                        z = z + ap(sub, r - spmv(mat_arrs, z))
                return z
            return apply
        if k == "mg":
            from .mg import make_vcycle
            op = self._mat
            # z-slab-decomposed V-cycle: runs in the SAME shard_map program,
            # halo planes ride ppermute rings (solvers/mg.py docstring);
            # only the tiny coarse tail is gathered
            vcycle = make_vcycle(op.nz, op.ny, op.nx, axis=axis,
                                 ndev=comm.size, platform=comm.platform,
                                 smoother=self.mg_smoother)
            return lambda arrs, r: vcycle(r)
        raise AssertionError(k)

    def local_apply_many(self, comm: DeviceComm, n: int):
        """Batched apply ``apply(pc_arrays_local, R_local (lsize, nrhs))
        -> Z_local`` for the multi-RHS solve path, or None when this PC
        kind has no batched form (the caller then falls back to
        per-column sequential solves — solvers/ksp.KSP.solve_many).

        The diagonal kinds broadcast over the trailing RHS axis; the MXU
        block kinds (bjacobi and the sor/ssor/ilu/icc family that shares
        its kernel shape) take the trailing axis straight through the
        batched matmul; bjacobi's ILU(0) blocks sweep each column,
        batched; dense lu gathers the whole RHS block in ONE
        collective. Per-apply collective count never grows with k.
        """
        k = self.kind
        axis = comm.axis
        lsize = comm.local_size(n)
        if k == "none":
            return lambda arrs, R: R
        if k == "jacobi":
            return lambda arrs, R: arrs[0][:, None] * R
        if k == "bjacobi":
            def apply(arrs, R):
                binv = arrs[0]   # (nb, bs, bs) block inverses
                nb, bs = binv.shape[0], binv.shape[1]
                # one batched MXU matmul per apply, k columns at a time
                # (bf16 factor storage contracts in f32, like the
                # single-RHS apply)
                return widened_einsum(
                    "bij,bjc->bic", binv, R.reshape(nb, bs, R.shape[1]),
                    comm.platform).reshape(-1, R.shape[1])
            return apply
        if k == "bjacobi_ilu0":
            from .bjilu import apply_many
            interpret = comm.platform != "tpu"
            return lambda arrs, R: apply_many(arrs, R, interpret)
        if k == "lu":
            def apply(arrs, R):
                minv = arrs[0]   # replicated (n_pad, n_pad) inverse
                R_full = lax.all_gather(R, axis, tiled=True)
                Z_full = widened_einsum("ij,jc->ic", minv, R_full,
                                        comm.platform)
                i = lax.axis_index(axis)
                return lax.dynamic_slice_in_dim(Z_full, i * lsize, lsize)
            return apply
        return None

    def local_apply_grid3d(self, comm: DeviceComm):
        """3D-native apply for the stencil-CG fast path, or None.

        ``apply3(pc_arrays_local, r_slab (lz,ny,nx)) -> z_slab`` — lets the
        fast path keep its loop state in the operator's grid shape (flat↔3D
        reshapes inside a while_loop body materialize full-array copies;
        see cg_stencil_kernel). Only 'mg' has a non-trivial 3D form; the
        diagonal kinds collapse to scalars there instead.
        """
        if self.kind != "mg":
            return None
        from .mg import make_vcycle3d
        op = self._mat
        cycle = make_vcycle3d(op.nz, op.ny, op.nx, axis=comm.axis,
                              ndev=comm.size, platform=comm.platform,
                              smoother=self.mg_smoother)
        return lambda arrs, r: cycle(r)

    def local_apply_transpose(self, comm: DeviceComm, n: int):
        """``apply_t(pc_arrays_local, r_local) -> z_local`` for ``Mᵀ``
        (PETSc's PCApplyTranspose slot — KSPBICG's shadow recurrence).

        Returns None when the type provides no transpose apply. Diagonal
        applies (none/jacobi) are symmetric and reuse the forward closure;
        block kinds (bjacobi/sor/ssor/ilu/icc) and lu/cholesky transpose
        their shipped explicit inverses ((B⁻¹)ᵀ = (Bᵀ)⁻¹ — one transposed
        batched matvec); bjacobi's ILU(0) blocks run their two sweeps on
        the shifted factors of ``(LU)ᵀ`` (``bjilu.transpose``);
        composite-additive sums its children's transposes;
        shell uses the user's ``set_shell_apply_transpose`` function.
        mg is symmetric by construction (R = (1/2)Pᵀ, equal pre/post
        smoothing) so its forward apply is reused;
        asm/gamg/composite-multiplicative provide none, as does lu in
        cyclic-reduction mode (the PCR sweeps factorize A, not Aᵀ; shipping
        a second factorization for the rare transpose user would double the
        replicated setup memory — recorded in PARITY.md).
        """
        k = self.kind
        axis = comm.axis
        lsize = comm.local_size(n)
        if k in ("none", "jacobi"):
            return self.local_apply(comm, n)      # diagonal: symmetric
        if k == "mg":
            # the V-cycle is a symmetric operator by construction
            # (R = (1/2)Pᵀ + equal-count Jacobi smoothing, solvers/mg.py;
            # tests/test_mg_slab.py::test_vcycle_is_symmetric) — the forward
            # apply IS the transpose apply
            return self.local_apply(comm, n)
        if k in ("crtri", "crband") and self._type == "cholesky":
            # cholesky's contract is a symmetric (complex: Hermitian)
            # operator. Real: M = M^T, the forward PCR apply IS the
            # transpose apply. Complex Hermitian: M^T = conj(M), so
            # M^T r = conj(M(conj(r))) — still no second factorization
            # (lu makes no symmetry promise -> None).
            fwd = self.local_apply(comm, n)
            if self._mat is not None and is_complex(self._mat.dtype):
                return lambda arrs, r: jnp.conj(fwd(arrs, jnp.conj(r)))
            return fwd
        if k == "bjacobi":
            def apply_t(arrs, r):
                binv = arrs[0]  # (nb, bs, bs) explicit block inverses
                nb, bs = binv.shape[0], binv.shape[1]
                return jnp.einsum("bij,bi->bj", binv,
                                  r.reshape(nb, bs)).reshape(-1)
            return apply_t
        if k == "bjacobi_ilu0":
            from .bjilu import apply, transpose
            interpret = comm.platform != "tpu"
            return lambda arrs, r: apply((transpose(arrs[0]),), r,
                                         interpret)
        if k == "lu":
            def apply_t(arrs, r):
                minv = arrs[0]  # replicated (n_pad, n_pad) inverse of A
                r_full = lax.all_gather(r, axis, tiled=True)
                z_full = minv.T @ r_full
                i = lax.axis_index(axis)
                return lax.dynamic_slice_in_dim(z_full, i * lsize, lsize)
            return apply_t
        if k == "shell":
            if self._shell_apply_t is None:
                return None
            from ..parallel.mesh import full_vector_local_apply
            shell_t = full_vector_local_apply(self._shell_apply_t, comm, n)
            return lambda arrs, r: shell_t(r)
        if k == "composite" and self.composite_type == "additive":
            subs = [(c.local_apply_transpose(comm, n),
                     len(c.device_arrays())) for c in self._sub_pcs]
            if any(ap is None for ap, _ in subs):
                return None
            def apply_t(arrs, r):
                z = jnp.zeros_like(r)
                i = 0
                for ap, na in subs:
                    z = z + ap(arrs[i:i + na], r)
                    i += na
                return z
            return apply_t
        return None     # asm/gamg/composite-multiplicative: no transpose

    def __repr__(self):
        return f"PC(type={self._type!r}, factor={self._factor_solver_type!r})"


_DENSE_CAP = 16384  # host O(n^3) factorization bound for direct paths
_AUTO_BLOCK_TARGET = 2048  # bjacobi auto-split block size (memory-frugal)


def _per_device_inverse(A, n, lsize, ndev, block_inv, host_dt=np.float64):
    """(ndev, lsize, lsize) stack of per-device block inverses.

    ``block_inv(csr_block) -> dense inverse``; out-of-range / padding rows
    get identity so padded vector slots pass through unchanged.
    """
    inv = np.zeros((ndev, lsize, lsize), dtype=host_dt)
    for d in range(ndev):
        rs, re = d * lsize, min((d + 1) * lsize, n)
        inv[d] = np.eye(lsize)
        if rs < n:
            m = re - rs
            inv[d, :m, :m] = block_inv(A[rs:re, rs:re])
    return inv


def _bjacobi_block_count(lsize: int, ndev: int, blocks: int) -> int:
    """Blocks per device for PCBJACOBI.

    ``blocks`` is the PETSc-style *total* block count (``-pc_bjacobi_blocks``;
    0 = default). PETSc defaults to one block per process; here the default
    additionally auto-splits when the per-device block would exceed the dense
    factorization cap (the TPU analog has no sparse local LU to fall back on,
    SURVEY.md §7.4). Blocks must tile the local rows evenly (uniform padded
    layout), so the count snaps to a divisor of ``lsize``.
    """
    if blocks < 0:
        blocks = 0   # PETSC_DECIDE (-1) and friends: let the library choose
    if blocks:
        if blocks % ndev:
            raise ValueError(
                f"-pc_bjacobi_blocks {blocks} must be a multiple of the "
                f"device count {ndev}")
        nb = blocks // ndev
        if lsize % nb:
            raise ValueError(
                f"-pc_bjacobi_blocks: {nb} blocks/device must divide the "
                f"local row count {lsize}")
        return nb
    if lsize <= _DENSE_CAP:
        return 1
    # auto-split: target much smaller blocks than the hard cap — the blocks
    # densify (O(bs²) memory each, O(bs³) host factorization), so past the
    # cap we want many MXU-friendly blocks, not a few enormous ones
    nb = -(-lsize // _AUTO_BLOCK_TARGET)
    # snap up to a divisor of lsize, but don't degenerate: if no divisor
    # keeps blocks >= ~cap/8 rows (e.g. lsize prime), the split is useless
    while lsize % nb and lsize // nb > _AUTO_BLOCK_TARGET // 8:
        nb += 1
    if lsize % nb:
        raise ValueError(
            f"PC 'bjacobi' cannot auto-split {lsize} local rows into even "
            "dense blocks — set -pc_bjacobi_blocks explicitly or use pc "
            "'jacobi'/'gamg'")
    return nb


def _build_bjacobi(comm: DeviceComm, mat: Mat, blocks: int = 0,
                   setup_device: str = "auto", owner: "PC | None" = None):
    """Per-device inverses of the local diagonal block(s).

    Shipped as explicit inverses so the device-side apply is one batched
    dense matvec on the MXU. With ``-pc_bjacobi_blocks`` (or past the dense
    cap) each device holds several smaller blocks instead of one
    ``lsize`` × ``lsize`` one.

    Where the inversion itself runs is ``-pc_setup_device``-controlled
    (:func:`_want_device_setup`): the device path ships the raw dense
    blocks (the same bytes the host path ships as inverses) and inverts
    them as one batched MXU LU + two Newton polish steps (:func:
    `_device_inverse_blocks`) — on the round-4 cfg4 benchmark this replaces
    a 17.5 s single-core host LAPACK sweep with ~1.5 s of device work.
    The host fp64 LAPACK sweep remains both the quality-gate fallback
    (counted in :data:`gate_fallbacks`) and the complex path. A device
    compile or runtime error propagates: it is never hidden behind the
    host path.

    Where a dense block would pass the dense cap (with the block count
    left to the library: more rows on a device than the cap), a real
    five-point DIA operator takes PETSc's default sub-solve instead:
    ILU(0) blocks of whole grid lines, as many as ``-pc_bjacobi_blocks``
    asks or about ``bjilu.BLOCK_ROWS`` rows each, factored on the host
    and applied on the device (solvers/bjilu.py, PC kind
    ``bjacobi_ilu0``). ``owner.sub_solve`` says which ran.
    """
    import scipy.linalg
    _require_assembled(mat, "bjacobi")
    n = mat.shape[0]
    lsize = comm.local_size(n)
    asked = (_bjacobi_block_count(lsize, comm.size, int(blocks))
             if int(blocks) > 0 else 0)
    if lsize // max(asked, 1) > _DENSE_CAP:
        from . import bjilu
        built = bjilu.build(comm, mat, asked)
        if built is not None:
            stack, info = built
            if owner is not None:
                owner.setup_mode = "host"
                owner.setup_breakdown = info
                owner.sub_solve, owner.sub_blocks = "ilu0", info["blocks"]
            return (stack,)
    nb = asked or _bjacobi_block_count(lsize, comm.size, 0)
    if owner is not None:
        owner.sub_solve, owner.sub_blocks = "dense", comm.size * nb
    if lsize // nb > _DENSE_CAP:
        raise ValueError(
            f"PC 'bjacobi' blocks are dense ({lsize // nb}x{lsize // nb}); "
            "too large — raise -pc_bjacobi_blocks, use more devices, or pc "
            "'jacobi'/'gamg' (SURVEY.md §7.4)")
    bs = lsize // nb
    dense = None
    if _want_device_setup(comm, mat.dtype, setup_device, f64_ok=True):
        import time
        t0 = time.perf_counter()
        # NOT named `blocks`: that is the int option parameter above, and
        # shadowing it with the (M, bs, bs) stack invited confusing the
        # two on any reorder (ADVICE r5)
        blk_stack = None
        if (getattr(mat, "ell_cols", None) is not None
                and mat.ell_cols.shape[0] == bs * comm.size * nb):
            # extract the diagonal blocks FROM the device-resident ELL —
            # zero new bytes ship (the dense stack is ~0.5 GB at cfg4
            # scale, for data the device already holds); note no
            # to_scipy() either, which would host-fetch the whole ELL
            blk_stack = _ell_diag_blocks(mat.ell_cols, mat.ell_vals, bs, n)
        if blk_stack is None:
            blk_stack = _dense_diag_blocks(mat.to_scipy().tocsr(), n, bs,
                                           comm.size * nb,
                                           np.dtype(mat.dtype))
            dense = blk_stack
        t1 = time.perf_counter()
        shipped = _device_inverse_blocks(comm, blk_stack)
        if shipped is not None:
            if owner is not None:
                owner.setup_mode = "device"   # observability (view/bench)
                # extract = block assembly (on device via _ell_diag_blocks,
                # or host+ship); invert = program load + the batched MXU
                # inversion itself
                owner.setup_breakdown = {
                    "extract_s": round(t1 - t0, 4),
                    "invert_s": round(time.perf_counter() - t1, 4)}
            return (shipped,)
    if owner is not None:
        owner.setup_mode = "host"
        owner.setup_breakdown = None
    host_dt = host_dtype(mat.dtype)
    if dense is not None:
        # quality-gate fallback: reuse the extracted stack (its
        # values ARE the operator-dtype CSR values — casting up loses
        # nothing) instead of re-walking the CSR
        inv = np.stack([scipy.linalg.inv(blk.astype(host_dt))
                        for blk in dense])
    else:
        inv = _per_device_inverse(
            mat.to_scipy().tocsr(), n, bs, comm.size * nb,
            lambda B: scipy.linalg.inv(B.toarray().astype(host_dt)),
            host_dt=host_dt)
    return _ship_blocks(comm, inv, mat.dtype)


def _want_device_setup(comm: DeviceComm, dtype, setup_device,
                       f64_ok: bool = False) -> bool:
    """Resolve ``-pc_setup_device`` ('auto'/'1'/'0').

    auto = device only on a TPU mesh, where the batched MXU work beats the
    single-core host LAPACK sweep by orders of magnitude. Callers pass
    ``f64_ok`` when they have an fp64-capable device program — XLA:TPU
    has no F64/C128 LuDecomposition (module docstring), so fp64 paths
    seed each inverse from an F32 LU and Newton-polish in emulated f64
    (``_inv_polish_seeded``, ``tridiag._bpcr_device_factor``); bjacobi,
    dense-lu, and block-PCR all do. Complex stays off auto (no complex
    device factorization has been run on a chip). On CPU meshes the
    "device" inversion IS host LAPACK, so there is nothing to win.
    """
    s = str(setup_device).lower()
    if s in ("0", "false", "host", "no"):
        return False
    if s in ("1", "true", "device", "yes"):
        return True
    if s != "auto":
        raise ValueError(
            f"-pc_setup_device {setup_device!r}: expected 'auto', '0' or '1'")
    if comm.platform != "tpu":
        return False
    d = np.dtype(dtype)
    return d == np.float32 or (f64_ok and d == np.float64)


def _dense_diag_blocks(A, n: int, bs: int, nblocks: int, dt) -> np.ndarray:
    """(nblocks, bs, bs) dense diagonal-block stack of the host CSR ``A``;
    out-of-range / padding rows get identity (inverts to identity, so
    padded vector slots pass through unchanged)."""
    return _per_device_inverse(A, n, bs, nblocks,
                               lambda B: B.toarray(), host_dt=dt)


_DEVICE_INV_GATE = 1e-2  # post-polish ||I - B X||_max acceptance bound


def _polish_and_gate(B, X, eye):
    # two Newton polish steps X ← X + X(I − BX): each squares the LU/seed
    # roundoff residual; 2 batched MXU matmuls per step
    X = X + X @ (eye - B @ X)
    X = X + X @ (eye - B @ X)
    # NaN-proof gate: XLA's max-reduce DROPS NaNs (NaN comparisons are
    # false, so the accumulator survives) — a singular block's all-NaN
    # inverse would otherwise report q = 0
    q = jnp.where(jnp.all(jnp.isfinite(X)),
                  jnp.max(jnp.abs(eye - B @ X)), jnp.inf)
    return X, q


@jax.jit
def _inv_polish(B):
    """Batched native-dtype inverse + Newton polish + NaN-proof quality
    scalar (module-level jit: compiled once per (shape, dtype), not per
    PC setup). Used for dtypes whose LU the backend implements natively
    (fp32/c64 on TPU; everything on CPU)."""
    eye = jnp.eye(B.shape[-1], dtype=B.dtype)
    return _polish_and_gate(B, jnp.linalg.inv(B), eye)


@jax.jit
def _inv_polish_seeded(B):
    """Batched inverse for f64/c128 on TPU, where XLA implements no
    F64/C128 LuDecomposition: seed each inverse from an F32 (C64) LU and
    Newton-polish in the full dtype — XLA:TPU emulates f64 dots at
    near-f32 MXU throughput, and each polish step squares the ~1e-2 seed
    residual toward the f64 rounding floor (same trick as
    tridiag._bpcr_device_factor, where it measures ~1e-9 quality)."""
    seed_dt = jnp.complex64 if jnp.iscomplexobj(B) else jnp.float32
    eye = jnp.eye(B.shape[-1], dtype=B.dtype)
    X = jnp.linalg.inv(B.astype(seed_dt)).astype(B.dtype)
    # one extra polish pair vs the native path: the seed starts ~5 digits
    # worse, and two more cheap matmul pairs buy the rest of the floor
    X = X + X @ (eye - B @ X)
    X = X + X @ (eye - B @ X)
    return _polish_and_gate(B, X, eye)


# bytes of blocks each device inverts at once: the emulated-f64 LU seed and
# polish hold ~48x their operand in temporaries on TPU (a whole 262,144-row
# convdiff2d(512) stack at once — 4 GiB of f64 — compiled to 104 GiB for
# one v5e chip; slabs of one 2048² block compile to 9.2 GiB in all), so
# the stack streams through in slabs this size
_INV_SLAB_BYTES = 32 << 20

_BLOCKWISE_PROGRAMS: dict = {}


def _inv_blockwise(comm: DeviceComm, B, inv_fn):
    """``inv_fn`` over the axis-0-sharded (M, bs, bs) stack ``B``, each
    device inverting its own blocks one slab of :data:`_INV_SLAB_BYTES`
    at a time, so device memory is bounded by the slab and not by the
    stack. ``B`` is donated: the inverse overwrites it slab by slab.
    Returns ``(X, q)`` with ``q`` the worst block's gate value."""
    bs = B.shape[-1]
    batch = max(1, _INV_SLAB_BYTES // (bs * bs * np.dtype(B.dtype).itemsize))
    key = (comm.mesh, comm.axis, inv_fn, batch)
    prog = _BLOCKWISE_PROGRAMS.get(key)
    if prog is None:
        axis = comm.axis

        def local(b):
            # slab i's inverse overwrites slab i in place: the stack's
            # own buffer is the output, so only one slab's temporaries
            # are ever live
            m = b.shape[0]
            step = min(batch, m)
            while m % step:
                step -= 1

            def slab(i, carry):
                b, q = carry
                x, qi = inv_fn(lax.dynamic_slice_in_dim(b, i * step, step))
                # f32: XLA:TPU lowers only sum all-reduces in f64, and
                # the gate (1e-2, or inf) needs no more
                return (lax.dynamic_update_slice_in_dim(b, x, i * step, 0),
                        jnp.maximum(q, qi.astype(jnp.float32)))

            X, q = lax.fori_loop(0, m // step, slab,
                                 (b, jnp.float32(0.0)))
            return X, lax.pmax(q, axis)

        prog = jax.jit(comm.shard_map(local, in_specs=(P(axis),),
                                      out_specs=(P(axis), P())),
                       donate_argnums=0)
        _BLOCKWISE_PROGRAMS[key] = prog
    return prog(B)


def _device_inverse_blocks(comm: DeviceComm, blocks: np.ndarray):
    """Batched block inversion ON the mesh devices.

    ``blocks``: (M, bs, bs) host stack in the operator dtype, M divisible
    by the device count. Ships the stack axis-0-sharded and runs
    :func:`_inv_polish` (batched LU + two Newton polish steps) slab by
    slab on each device (:func:`_inv_blockwise`), so the polished fp32
    inverse lands at the same ~eps32 quantization quality
    the host path reaches by fp64-factorizing and casting. Returns the
    sharded (M, bs, bs) inverse stack, or ``None`` when the post-polish
    gate ``max|I − BX| ≤ 1e-2`` fails (singular or too ill-conditioned
    for the apply dtype) — callers then fall back to the pivot-quality
    host fp64 path, which raises the proper error for genuinely singular
    blocks. A compile or runtime error of the device program propagates.
    """
    return _run_device_inverse(
        comm, lambda: (comm.put_axis0(blocks)
                       if isinstance(blocks, np.ndarray)
                       else jax.device_put(blocks, comm.row_sharding)),
        "block", blockwise=True)


# quality-gate rejections of a device PC setup, by kind ("block", "dense",
# "bpcr") — the one sanctioned device->host fallback, counted so a run can
# report it (chip_smoke.py prints it)
gate_fallbacks: Counter = Counter()


def _run_device_inverse(comm: DeviceComm, place, what: str,
                        blockwise: bool = False):
    """Shared device-inversion driver: place the operand (``place`` is a
    thunk), pick the native vs F32-seeded program (:func:`_inv_polish` /
    :func:`_inv_polish_seeded`), run, and apply the NaN-proof quality
    gate. Returns the inverse, or ``None`` when the gate rejects it
    (counted in :data:`gate_fallbacks`; callers fall back to host
    LAPACK). One place to change the gate/selection rule for BOTH the
    bjacobi and dense-lu paths. ``blockwise`` inverts an (M, bs, bs)
    stack slab by slab on each device (:func:`_inv_blockwise`)."""
    B = place()
    wide = np.dtype(B.dtype) in (np.float64, np.complex128)
    inv_fn = (_inv_polish_seeded
              if wide and comm.platform == "tpu" else _inv_polish)
    X, q = (_inv_blockwise(comm, B, inv_fn) if blockwise else inv_fn(B))
    q = float(q)   # sync: setup-time only, one scalar
    if not np.isfinite(q) or q > _DEVICE_INV_GATE:
        gate_fallbacks[what] += 1
        return None
    return X


def _require_assembled(mat, pc_name: str):
    """Block/direct PCs factorize host CSR — matrix-free operators can't."""
    if not hasattr(mat, "to_scipy"):
        raise ValueError(
            f"PC {pc_name!r} factorizes the assembled matrix; matrix-free "
            f"operators ({type(mat).__name__}) work with pc 'none'/'jacobi'/"
            "'shell'/'mg' instead")


def _local_dense_blocks(comm: DeviceComm, mat: Mat, pc_name: str):
    """Host scipy CSR + per-device uniform (rs, re) row windows.

    Shared setup for every block preconditioner; enforces the dense-block
    size cap (SURVEY.md §7.4 — local factorizations densify).
    """
    _require_assembled(mat, pc_name)
    n = mat.shape[0]
    lsize = comm.local_size(n)
    if lsize > _DENSE_CAP:
        raise ValueError(
            f"PC {pc_name!r} local blocks are dense ({lsize}x{lsize}); too "
            "large — use more devices or pc 'jacobi'/'mg' (SURVEY.md §7.4)")
    return mat.to_scipy().tocsr(), n, lsize


def _ship_blocks(comm: DeviceComm, blocks: np.ndarray, dtype):
    return (comm.put_axis0(blocks.astype(dtype)),)


def _build_block_ssor(comm: DeviceComm, mat: Mat, omega: float):
    """Per-device block SSOR: M = (D/ω+L) (D/ω)⁻¹ (D/ω+U) · ω/(2-ω).

    PETSc's parallel PCSOR is processor-local sweeps (block-Jacobi outside,
    SOR inside) — same semantics here, with the local sweep applied
    *exactly*: the SSOR matrix inverse is precomputed on host and applied
    as one dense matvec on the MXU (triangular solves are sequential and
    hostile to the TPU vector unit; an explicit inverse is one fused
    matmul).
    """
    import scipy.linalg
    if not 0.0 < omega < 2.0:
        raise ValueError(f"SOR omega must be in (0, 2), got {omega}")
    A, n, lsize = _local_dense_blocks(comm, mat, "sor")
    host_dt = host_dtype(mat.dtype)

    def ssor_inv(B):
        Ad = B.toarray().astype(host_dt)
        D = np.diag(Ad).copy()
        D[D == 0] = 1.0
        Dw = np.diag(D / omega)
        M = ((Dw + np.tril(Ad, -1)) @ np.diag(omega / D)
             @ (Dw + np.triu(Ad, 1)) / (2.0 - omega))
        return scipy.linalg.inv(M)

    inv = _per_device_inverse(A, n, lsize, comm.size, ssor_inv,
                              host_dt=host_dt)
    return _ship_blocks(comm, inv, mat.dtype)


def _build_block_ilu(comm: DeviceComm, mat: Mat, fill: float):
    """Per-device block ILU (PCILU; PCICC is an open alias of this path —
    the incomplete factors come from unsymmetric ``spilu`` either way, and
    both densify to an explicit (LU)⁻¹ for a one-matmul MXU apply (device
    triangular solves are serial; the block is dense-capped anyway).
    """
    import scipy.linalg
    import scipy.sparse as sp
    import scipy.sparse.linalg as spla
    A, n, lsize = _local_dense_blocks(comm, mat, "ilu")
    host_dt = host_dtype(mat.dtype)

    def ilu_inv(B):
        Ad = sp.csc_matrix(B).astype(host_dt)
        try:
            f = spla.spilu(Ad, fill_factor=fill, drop_tol=1e-5)
            return f.solve(np.eye(Ad.shape[0], dtype=host_dt))
        except RuntimeError:        # singular pivot — fall back to exact
            return scipy.linalg.inv(Ad.toarray())

    inv = _per_device_inverse(A, n, lsize, comm.size, ilu_inv,
                              host_dt=host_dt)
    return _ship_blocks(comm, inv, mat.dtype)


def _build_asm(comm: DeviceComm, mat: Mat, overlap: int):
    """Restricted additive Schwarz (PCASM, PC_ASM_RESTRICT default).

    Each device factorizes its row window extended by ``overlap`` rows on
    each side; the apply solves on the window and keeps the owned interior.
    Window rows outside the global range use identity padding.
    """
    import scipy.linalg
    ov = int(overlap)
    if ov < 0:
        raise ValueError(f"asm overlap must be >= 0, got {overlap}")
    A, n, lsize = _local_dense_blocks(comm, mat, "asm")
    if ov > lsize:
        raise ValueError(
            f"asm overlap {ov} exceeds the local block size {lsize} "
            "(halo exchange is single-neighbor)")
    ndev = comm.size
    w = lsize + 2 * ov
    host_dt = host_dtype(mat.dtype)
    inv = np.zeros((ndev, w, w), dtype=host_dt)
    for d in range(ndev):
        rs = d * lsize - ov
        block = np.eye(w, dtype=host_dt)
        lo, hi = max(rs, 0), min(rs + w, n)
        if lo < hi:
            block[lo - rs:hi - rs, lo - rs:hi - rs] = \
                A[lo:hi, lo:hi].toarray()
        inv[d] = scipy.linalg.inv(block)
    return _ship_blocks(comm, inv, mat.dtype)


_CR_CAP = 1 << 23  # replicated (S, n) sweep arrays: ~2.7 GB fp64 at 8.4M rows

# Block-cyclic-reduction memory/traffic model (the written-down rule the
# round-3 VERDICT asked for): the factorization stores two (S, N, b, b)
# sweep-coefficient stacks plus one (N, b, b) reduced-diagonal inverse,
# S = ceil(log2 N), N = ceil(n/b) — i.e. (2S+1)·N·b² ≈ (2·log2(n/b)+1)·n·b
# elements, REPLICATED per device (every sweep touches all blocks). Each
# solve streams those elements once: the apply cost is S+1 batched
# (N,b,b)×(N,b) MXU products. The caps below bound the replicated
# footprint to ~2.4 GB fp64 per device; past them, banded-direct stops
# paying against MG/GAMG-preconditioned CG (measured table: PARITY.md
# 'Direct solves').
_BCR_ELEM_CAP = 3 * 10 ** 8
_BCR_MAX_BW = 512  # block CR bandwidth cap: b×b blocks must stay MXU-sized


def _bcr_elements(n: int, b: int) -> int:
    """Elements the block-CR factorization stores for (n, bandwidth b)."""
    N = -(-n // b)
    S = max(1, int(np.ceil(np.log2(N)))) if N > 1 else 1
    return (2 * S + 1) * N * b * b


def _bcr_fits(n: int, b: int) -> bool:
    return 1 < b <= _BCR_MAX_BW and _bcr_elements(n, b) <= _BCR_ELEM_CAP


def _build_host_splu(mat: Mat, pc_type: str):
    """Host sparse LU — the MUMPS slot's irreducible-sparsity closing move.

    The reference direct-solves ARBITRARY sparsity through MUMPS
    (``test.py:43`` [external]) — a CPU library invoked from Python, so a
    host factorization here is exactly as faithful. scipy's SuperLU
    (COLAMD fill-reducing ordering + partial pivoting) factorizes in fp64
    (complex128 for complex operators) regardless of the device dtype;
    the apply happens host-side under KSP 'preonly' (KSP._solve_hostlu) —
    one gather + one factor solve + one scatter, the same host round trip
    MUMPS pays. Cost honestly measured in PARITY.md 'Direct solves'."""
    from scipy.sparse.linalg import splu
    _require_assembled(mat, pc_type)
    A = mat.to_scipy()
    dt = (np.complex128 if np.issubdtype(A.dtype, np.complexfloating)
          else np.float64)
    A64 = A.astype(dt).tocsc()
    # hand back the SAME csc used for factorization (csc @ vector works) —
    # a separate csr copy would double the persistent host footprint
    return splu(A64), A64


def _rcm_bandwidth(mat: Mat):
    """Reverse-Cuthill-McKee ordering, the bandwidth it achieves, and the
    permuted matrix (returned so the builder never re-permutes).

    The fill/bandwidth-reducing-ordering half of the MUMPS slot
    (reference ``test.py:41-43`` [external] — MUMPS runs AMD/METIS before
    factorizing): a symmetric permutation that clusters the sparsity
    around the diagonal so general reducible sparsity becomes banded.
    """
    from scipy.sparse.csgraph import reverse_cuthill_mckee
    A = mat.to_scipy().tocsr()
    perm = np.asarray(reverse_cuthill_mckee(A, symmetric_mode=False),
                      dtype=np.int64)
    Ap = A[perm][:, perm].tocsr()
    coo = Ap.tocoo()
    bw = int(np.max(np.abs(coo.row - coo.col))) if coo.nnz else 0
    return perm, bw, Ap


def _build_banded_bcr(comm: DeviceComm, mat: Mat, bw: int, perm=None,
                      A_perm=None, setup_device: str = "auto",
                      owner: "PC | None" = None):
    """Block-cyclic-reduction factorization of a banded operator with
    bandwidth ``1 < bw`` fitting :func:`_bcr_fits` — the MUMPS-slot direct
    path past the dense cap (pentadiagonal Poisson lines, coupled
    tridiagonal families, RCM-reordered grids; reference ``test.py:41-43``).

    Host fp64/complex128 setup with batched b×b LAPACK inverses (pivoted
    within blocks, pivotless across — guarded by the probe solve); the
    device apply is ``ceil(log2 N)`` sweeps of two batched (N, b, b)×(N, b)
    MXU products (solvers/tridiag.py::bpcr_apply).

    With ``perm`` (an RCM ordering from :func:`_rcm_bandwidth`; pass its
    ``A_perm`` too so the permutation isn't recomputed) the factorization
    is of ``A[perm][:, perm]`` and the apply conjugates by the
    permutation; the returned array tuple then carries the permutation
    and its inverse as trailing int32 arrays.
    """
    from .tridiag import banded_to_blocks, bpcr_setup, bpcr_setup_device_csr
    _require_assembled(mat, "lu")
    if perm is not None:
        A = (A_perm if A_perm is not None
             else mat.to_scipy().tocsr()[perm][:, perm].tocsr())
    else:
        A = mat.to_scipy().tocsr()
    dt = mat.dtype
    out = None
    if _want_device_setup(comm, dt, setup_device, f64_ok=True):
        timings: dict = {}
        dev = bpcr_setup_device_csr(A, bw, comm, dt, timings=timings)
        if dev is not None:
            out = dev
            if owner is not None:
                owner.setup_mode = "device"
                owner.setup_breakdown = timings
    if out is None:
        if owner is not None:
            owner.setup_mode = "host"
            owner.setup_breakdown = None
        Ab, Bb, Cb = banded_to_blocks(A, bw)
        alphas, gammas, binv = bpcr_setup(Ab, Bb, Cb, apply_dtype=dt)
        out = (comm.put_replicated(alphas.astype(dt)),
               comm.put_replicated(gammas.astype(dt)),
               comm.put_replicated(binv.astype(dt)))
    if perm is not None:
        iperm = np.argsort(perm)
        out += (comm.put_replicated(perm.astype(np.int32)),
                comm.put_replicated(iperm.astype(np.int32)))
    return out


def _build_tridiag_cr(comm: DeviceComm, mat: Mat):
    """Parallel-cyclic-reduction factorization of a tridiagonal operator —
    the scalable direct path the dense cap excluded (MUMPS slot for exactly
    the banded family ``test2.py:6-18`` ships; SURVEY.md §7.4-1).

    Host fp64 setup once (the MUMPS symbolic+numeric analog at setUp,
    reference stack §3.1); the device apply is ``ceil(log2 n)`` shifted
    fused multiply-add sweeps over the gathered rhs (solvers/tridiag.py).
    """
    from .tridiag import pcr_setup
    _require_assembled(mat, "lu")
    n = mat.shape[0]
    if n > _CR_CAP:
        raise ValueError(
            f"PC 'lu' (cyclic reduction) replicates ceil(log2 n) sweep "
            f"arrays; n={n} exceeds the {_CR_CAP} cap — use an iterative "
            "KSP with pc 'jacobi'/'gamg' instead")
    A = mat.to_scipy().tocsr()
    host_dt = host_dtype(mat.dtype)
    a = np.concatenate([[0.0], np.asarray(A.diagonal(-1))]).astype(host_dt)
    b = np.asarray(A.diagonal(0), dtype=host_dt)
    c = np.concatenate([np.asarray(A.diagonal(1)), [0.0]]).astype(host_dt)
    alphas, gammas, bfin = pcr_setup(a, b, c, apply_dtype=mat.dtype)
    dt = mat.dtype
    return (comm.put_replicated(alphas.astype(dt)),
            comm.put_replicated(gammas.astype(dt)),
            comm.put_replicated(bfin.astype(dt)))


def _build_dense_lu(comm: DeviceComm, mat: Mat,
                    setup_device: str = "auto", owner: "PC | None" = None):
    """Replicated dense inverse of the full operator (the MUMPS-slot path).

    By default the factorization runs on host LAPACK in fp64 (XLA:TPU has
    no f64 LuDecomposition) and the device applies the (padded) inverse
    as one matmul; accuracy is recovered by iterative refinement in
    KSPPREONLY. On TPU meshes ``-pc_setup_device`` (auto for real
    fp32/fp64) inverts ON the chip instead — fp64 via the F32-LU-seeded
    f64-Newton-polish program (:func:`_inv_polish_seeded`), turning an
    O(n³) single-core host factorization into seconds of MXU work —
    quality-gated with automatic host fallback.
    """
    import scipy.linalg
    _require_assembled(mat, "lu")
    n = mat.shape[0]
    if n > _DENSE_CAP:
        raise ValueError(
            f"PC 'lu' densifies general operators; n={n} is too large — "
            f"banded (or RCM-reducible) operators take the (block) "
            f"cyclic-reduction direct path automatically while "
            f"(2*ceil(log2(n/b))+1)*n*b <= {_BCR_ELEM_CAP:.0e} elements "
            f"and b <= {_BCR_MAX_BW} (PARITY.md 'Direct solves'); "
            "otherwise use an iterative KSP with pc 'bjacobi'/'jacobi' "
            "instead (SURVEY.md §7.4)")
    n_pad = comm.padded_size(n)
    if (_want_device_setup(comm, mat.dtype, setup_device, f64_ok=True)
            and getattr(mat, "ell_cols", None) is not None
            and mat.ell_cols.shape[0] == n_pad):
        import time
        t0 = time.perf_counter()
        # densify FROM the device-resident ELL arrays: zero new bytes ship
        Ad = _densify_ell(mat.ell_cols, mat.ell_vals, n)
        t1 = time.perf_counter()
        X = _device_inverse_dense(comm, Ad, n)
        if X is not None:
            if owner is not None:
                owner.setup_mode = "device"
                owner.setup_breakdown = {
                    "extract_s": round(t1 - t0, 4),
                    "invert_s": round(time.perf_counter() - t1, 4)}
            return (X,)
    if owner is not None:
        owner.setup_mode = "host"
        owner.setup_breakdown = None
    host_dt = host_dtype(mat.dtype)
    A = mat.to_scipy().toarray().astype(host_dt)
    inv = scipy.linalg.inv(A)
    inv_pad = np.zeros((n_pad, n_pad), dtype=host_dt)
    inv_pad[:n, :n] = inv
    return (comm.put_replicated(inv_pad.astype(mat.dtype)),)


@jax.jit
def _densify_ell(cols, vals, n):
    """(n_pad, K) ELL → (n_pad, n_pad) dense with identity pad rows —
    device-side densification for the dense-lu setup. ELL padding slots
    carry value 0, so their scatter-adds are no-ops wherever they point."""
    n_pad = cols.shape[0]
    rows = jnp.broadcast_to(jnp.arange(n_pad)[:, None], cols.shape)
    X = jnp.zeros((n_pad, n_pad), vals.dtype).at[rows, cols].add(vals)
    i = jnp.arange(n_pad)
    return X.at[i, i].add(
        jnp.where(i >= n, jnp.ones((), vals.dtype), jnp.zeros((), vals.dtype)))


@partial(jax.jit, static_argnums=(2,))
def _ell_diag_blocks(cols, vals, bs, n):
    """(n_pad, K) ELL → (n_pad/bs, bs, bs) dense diagonal-block stack, on
    device — the bjacobi analog of :func:`_densify_ell` (the host path
    extracts the same blocks from CSR and ships them; at cfg4 scale that
    is ~0.5 GB for data the device already holds).
    Off-block entries mask to a scatter dump row; padding/out-of-range
    rows get identity diagonals (pass-through, as everywhere else)."""
    n_pad = cols.shape[0]
    M = n_pad // bs
    r = jnp.broadcast_to(jnp.arange(n_pad)[:, None], cols.shape)
    blk = r // bs
    cc = cols - blk * bs
    inside = (cc >= 0) & (cc < bs) & (r < n)
    # masked entries scatter into an extra dump block (index M)
    blk_s = jnp.where(inside, blk, M)
    rr = r % bs
    cc_s = jnp.where(inside, cc, 0)
    X = jnp.zeros((M + 1, bs, bs), vals.dtype).at[blk_s, rr, cc_s].add(
        jnp.where(inside, vals, jnp.zeros((), vals.dtype)))[:M]
    # identity diagonal for padding rows (r >= n)
    i = jnp.arange(n_pad)
    pad = jnp.where(i >= n, jnp.ones((), vals.dtype),
                    jnp.zeros((), vals.dtype))
    return X.at[i // bs, i % bs, i % bs].add(pad)


@jax.jit
def _mask_pad(X, n):
    """Zero the pad block of the inverse (host dense-lu convention: padded
    slots must not feed back into real rows). ``n`` traced — one program
    per shape/dtype."""
    i = jnp.arange(X.shape[-1])
    keep = i < n
    return jnp.where(keep[:, None] & keep[None, :], X,
                     jnp.zeros((), X.dtype))


def _device_inverse_dense(comm: DeviceComm, Ad, n: int):
    """Full dense inverse on the mesh devices (replicated, like the host
    path's shipped inverse). ``Ad`` may be a host array (shipped) or an
    already-on-device array (resharded in place — the `_densify_ell`
    route). Same gating/fallback contract as
    :func:`_device_inverse_blocks`."""
    X = _run_device_inverse(
        comm, lambda: (comm.put_replicated(Ad)
                       if isinstance(Ad, np.ndarray)
                       else jax.device_put(Ad, comm.replicated_sharding)),
        "dense")
    return None if X is None else _mask_pad(X, n)
