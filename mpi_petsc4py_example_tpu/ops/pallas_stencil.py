"""Pallas TPU kernel for the 7-point Poisson stencil apply.

The stencil SpMV is the framework's hot op (every CG iteration, BASELINE
configs 1/5). The jnp formulation materializes six padded temporaries per
apply (~6 extra HBM passes); this kernel streams the slab HBM → VMEM in
z-chunks with double-buffered async DMA and computes the full stencil in one
VMEM-resident pass. The two z-halo planes (already exchanged over ICI via
``ppermute``) are passed as separate arrays and DMA'd straight into the
chunk scratch — no concatenated "extended slab" copy in HBM, so traffic is
exactly read(u) + write(y) + two planes.

Layout contract (matches models.stencil.StencilPoisson3D): the local slab is
``(lz, ny, nx)`` x-fastest; ``halo_lo``/``halo_hi`` are the neighbour planes
``(1, ny, nx)`` below/above (zero at the global Dirichlet boundaries).
Dirichlet boundaries in x/y are realized by shifting with zero fill inside
the kernel.

Falls back to the pure-jnp path on non-TPU backends (models/stencil.py).

Every ``pallas_call`` passes ``name=``: the custom call's name in the HLO
and so in the device trace, the kernel function's own name. The
multigrid kernels take a static ``name`` (that name by default), and
solvers/mg.py adds the level (``stencil3d_smooth_pair_pallas_l0``), so a
trace splits the V-cycle's kernel time by level.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pipeline_depth() -> int:
    """Static DMA pipeline depth (banks per stream) for the z-chunk
    kernels. Depth 2 (double buffering) is the default; the
    ``TPU_SOLVE_STENCIL_NBUF`` env knob exposes deeper pipelines (3-4) for
    DMA retuning sweeps — a deeper pipeline trades VMEM chunk depth for
    more DMAs in flight."""
    try:
        depth = int(os.environ.get("TPU_SOLVE_STENCIL_NBUF", "2"))
    except ValueError:
        return 2
    return min(max(depth, 2), 4)


def _compute_dtype(dtype):
    """VPU arithmetic dtype for a storage dtype: sub-f32 storage (bf16)
    computes in f32 — loads upconvert for free, only the DMA'd bytes stay
    half-width (the whole point of the bf16-storage pipeline) — while
    f32 keeps today's path bit for bit."""
    dt = jnp.dtype(dtype)
    return jnp.dtype(jnp.float32) if dt.itemsize < 4 else dt


def resident_zdepth(ny: int, nx: int, dtype, streams: int = 2,
                    nbuf: int | None = None, ncols: int = 1) -> int:
    """The deepest z-chunk the VMEM plan keeps resident for one
    ``(ny, nx)`` plane geometry at a given STORAGE dtype — the
    resident-size probe of the mixed-precision plans: bf16
    storage halves the plane bytes, so the planned depth (and with it
    the largest grid that stays VMEM-resident) exactly doubles vs f32.
    Mirrors :func:`_pick_chunk`'s budget arithmetic without the
    divides-lz snapping."""
    nbuf = nbuf or _pipeline_depth()
    plane = ny * nx * jnp.dtype(dtype).itemsize * ncols
    vmem_budget = _vmem_plan(_tpu_device_kind())[1]
    return max(1, int((vmem_budget // plane - 2 * nbuf) // (streams * nbuf)))


def _shift_x(u, step):
    """u shifted along the last (x) axis with zero fill."""
    if step == -1:
        return jnp.pad(u[:, :, :-1], ((0, 0), (0, 0), (1, 0)))
    return jnp.pad(u[:, :, 1:], ((0, 0), (0, 0), (0, 1)))


def _shift_y(u, step):
    if step == -1:
        return jnp.pad(u[:, :-1, :], ((0, 0), (1, 0), (0, 0)))
    return jnp.pad(u[:, 1:, :], ((0, 0), (0, 1), (0, 0)))


def _stencil_kernel(u_ref, lo_ref, hi_ref, out_ref, chunk, nchunks,
                    dot_ref=None, f_ref=None, combine=None, nbuf=2):
    """Grid-free kernel: ``nbuf``-deep z-chunk pipeline, manual DMA.

    Per chunk ``c`` the scratch holds planes ``[z0-1, z0+chunk+1)`` of the
    extended slab. INTERIOR chunks (``0 < c < nchunks-1``) fill their bank
    with ONE wide contiguous HBM→VMEM copy of all ``chunk+2`` planes —
    round-6 DMA re-geometry: the 3-way split (center + two 1-plane edge
    copies) issued 3× the DMA descriptors for the same bytes, and the
    1-plane edge copies are the narrowest transfers. Only the
    two boundary chunks still split, because their edge plane lives in a
    different array (the halo) than the center. All index/constant dtypes
    are pinned to i32/f32 explicitly: with x64 enabled, bare Python
    literals trace as i64/f64, which Mosaic cannot lower.

    With ``f_ref`` a second array streams through its own banks (center
    planes only — no neighbours needed) and ``combine(u, y, f) -> out``
    post-processes the stencil product while everything is VMEM-resident:
    one streamed pass for a whole damped-Jacobi sweep or residual, instead
    of a stencil pass plus an XLA elementwise pass over 3 more arrays.

    ``nbuf`` is the pipeline depth (banks per stream): 2 = classic double
    buffering; 3-4 keep more DMAs in flight at the cost of shallower
    chunks (the ``TPU_SOLVE_STENCIL_NBUF`` retuning knob).
    """
    def process(sc, osc, sem_c, sem_lo, sem_hi, sem_out, fsc=None,
                sem_f=None):
        cdt = _compute_dtype(out_ref.dtype)
        six = jnp.asarray(6.0, cdt)
        one = jnp.int32(1)

        # an interior chunk exists only at nchunks >= 3 — the wide-copy
        # code must not be EMITTED otherwise (its (chunk+2)-plane slice
        # would exceed the u array statically)
        has_interior = nchunks >= 3

        def start_in(c, slot):
            """Kick off the input DMA(s) for chunk ``c`` into bank ``slot``."""
            z0 = c * jnp.int32(chunk)
            edge = (c == 0) | (c == nchunks - 1)

            if has_interior:
                # interior: one contiguous (chunk+2)-plane window of u
                @pl.when(~edge)
                def _():
                    pltpu.make_async_copy(
                        u_ref.at[pl.ds(z0 - one, chunk + 2)], sc.at[slot],
                        sem_c.at[slot]).start()

            @pl.when(edge)
            def _():
                pltpu.make_async_copy(
                    u_ref.at[pl.ds(z0, chunk)],
                    sc.at[slot, pl.ds(one, chunk)], sem_c.at[slot]).start()
            # lower edge plane: u[z0-1], or halo_lo for the first chunk
            @pl.when(c == 0)
            def _():
                pltpu.make_async_copy(lo_ref, sc.at[slot, pl.ds(0, 1)],
                                      sem_lo.at[slot]).start()

            @pl.when(edge & (c > 0))
            def _():
                pltpu.make_async_copy(u_ref.at[pl.ds(z0 - one, 1)],
                                      sc.at[slot, pl.ds(0, 1)],
                                      sem_lo.at[slot]).start()
            # upper edge plane: u[z0+chunk], or halo_hi for the last chunk
            @pl.when(c == nchunks - 1)
            def _():
                pltpu.make_async_copy(
                    hi_ref, sc.at[slot, pl.ds(jnp.int32(chunk + 1), 1)],
                    sem_hi.at[slot]).start()

            @pl.when(edge & (c < nchunks - 1))
            def _():
                pltpu.make_async_copy(
                    u_ref.at[pl.ds(z0 + jnp.int32(chunk), 1)],
                    sc.at[slot, pl.ds(jnp.int32(chunk + 1), 1)],
                    sem_hi.at[slot]).start()
            if f_ref is not None:
                pltpu.make_async_copy(f_ref.at[pl.ds(z0, chunk)],
                                      fsc.at[slot], sem_f.at[slot]).start()

        def wait_in(c, slot):
            # matching waits for the start_in copies (shapes must agree
            # with the started transfer on each semaphore)
            edge = (c == 0) | (c == nchunks - 1)

            if has_interior:
                @pl.when(~edge)
                def _():
                    pltpu.make_async_copy(
                        u_ref.at[pl.ds(0, chunk + 2)], sc.at[slot],
                        sem_c.at[slot]).wait()

            @pl.when(edge)
            def _():
                pltpu.make_async_copy(
                    u_ref.at[pl.ds(0, chunk)],
                    sc.at[slot, pl.ds(one, chunk)], sem_c.at[slot]).wait()
                pltpu.make_async_copy(lo_ref, sc.at[slot, pl.ds(0, 1)],
                                      sem_lo.at[slot]).wait()
                pltpu.make_async_copy(
                    hi_ref, sc.at[slot, pl.ds(jnp.int32(chunk + 1), 1)],
                    sem_hi.at[slot]).wait()
            if f_ref is not None:
                pltpu.make_async_copy(f_ref.at[pl.ds(0, chunk)],
                                      fsc.at[slot], sem_f.at[slot]).wait()

        # prologue: fill the first nbuf-1 input banks so the steady state
        # keeps nbuf-1 input DMAs in flight (depth 2 = the classic
        # one-ahead double buffer; deeper depths are the whole point of
        # the nbuf knob — without this the extra banks would never be
        # in flight and only shrink the chunk)
        for k in range(min(nbuf - 1, nchunks)):
            start_in(jnp.int32(k), jnp.int32(k))

        def body(c, carry):
            slot = lax_rem(c)

            # steady state: chunk c+nbuf-1 into the bank chunk c-1 just
            # freed (fori_loop bodies are sequential, so its compute is
            # complete)
            @pl.when(c + jnp.int32(nbuf - 1) < nchunks)
            def _():
                start_in(c + jnp.int32(nbuf - 1),
                         lax_rem(c + jnp.int32(nbuf - 1)))

            wait_in(c, slot)
            buf = sc[slot].astype(cdt)   # bf16 storage upconverts here
            u = buf[1:-1]          # (chunk, ny, nx) center planes
            zm = buf[:-2]
            zp = buf[2:]
            y = (six * u - zm - zp
                 - _shift_y(u, -1) - _shift_y(u, +1)
                 - _shift_x(u, -1) - _shift_x(u, +1))
            out = (y if combine is None
                   else combine(u, y, None if f_ref is None else fsc[slot]))
            # wait for the output DMA that used this osc bank nbuf chunks ago
            @pl.when(c >= nbuf)
            def _():
                pltpu.make_async_copy(
                    osc.at[slot], out_ref.at[pl.ds(0, chunk)],
                    sem_out.at[slot]).wait()
            osc[slot] = out.astype(out_ref.dtype)
            pltpu.make_async_copy(
                osc.at[slot],
                out_ref.at[pl.ds(c * jnp.int32(chunk), chunk)],
                sem_out.at[slot]).start()
            if dot_ref is None:
                return carry
            # fused <u, A u> partial: u and y are both VMEM-resident right
            # here — the reduction costs no extra HBM pass (the separate
            # pdot(p, Ap) it replaces re-reads both from HBM)
            return carry + jnp.sum(u * y)

        def lax_rem(c):
            return jax.lax.rem(c, jnp.int32(nbuf))

        carry0 = (jnp.int32(0) if dot_ref is None
                  else jnp.asarray(0.0, cdt))
        acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(nchunks), body,
                                carry0)
        if dot_ref is not None:
            dot_ref[0] = acc
        # drain the in-flight output DMAs of the last (up to) nbuf chunks,
        # oldest first — chunk last-d exists only when nchunks > d
        last = jnp.int32(nchunks - 1)
        for d in range(nbuf - 1, 0, -1):
            @pl.when(jnp.int32(nchunks) >= d + 1)
            def _(d=d):
                pltpu.make_async_copy(
                    osc.at[lax_rem(last - jnp.int32(d))],
                    out_ref.at[pl.ds(0, chunk)],
                    sem_out.at[lax_rem(last - jnp.int32(d))]).wait()

        pltpu.make_async_copy(
            osc.at[lax_rem(last)], out_ref.at[pl.ds(0, chunk)],
            sem_out.at[lax_rem(last)]).wait()

    ny, nx = out_ref.shape[1], out_ref.shape[2]
    scratch = [
        pltpu.VMEM((nbuf, chunk + 2, ny, nx), out_ref.dtype),
        pltpu.VMEM((nbuf, chunk, ny, nx), out_ref.dtype),
        pltpu.SemaphoreType.DMA((nbuf,)),
        pltpu.SemaphoreType.DMA((nbuf,)),
        pltpu.SemaphoreType.DMA((nbuf,)),
        pltpu.SemaphoreType.DMA((nbuf,)),
    ]
    if f_ref is not None:
        scratch += [pltpu.VMEM((nbuf, chunk, ny, nx), out_ref.dtype),
                    pltpu.SemaphoreType.DMA((nbuf,))]
    pl.run_scoped(process, *scratch)


# Scoped-VMEM plan for the DMA pipeline. Mosaic's default per-kernel limit
# (~16MB) forces chunk=1 on 1MB planes (512² fp32), where every plane is
# DMA'd ~3x (as a center plane and as both neighbours' edge planes) —
# measured 7.3 HBM passes per apply at 512³ vs ~2.4 with real chunk depth.
# The kernel therefore asks Mosaic for a higher limit and plans its scratch
# against a budget — BOTH derived from the device generation's physical
# VMEM (requesting 64MB unconditionally would fail to compile on 16MB-VMEM
# generations; ADVICE r4).
#
# Measured at 512³ fp32 (1MB planes) on v5e (128MB VMEM): chunk=1 (old
# 16MB default) 7.3 HBM passes/apply; chunk=8 (64MB limit / 48MB budget)
# 5.0-5.2; chunk=16 (96MB limit) 7.1 — more VMEM pressure hurts past
# chunk 8, so half-of-VMEM capped at 64MB is the sweet spot.

# Physical VMEM per TensorCore, keyed by the exact ``device_kind`` JAX
# reports. v5e ("TPU v5 lite"): 128 MiB — "How to Scale Your Model"
# (jax-ml scaling book), TPU chapter; the v5e:2x2 described-chip compiles
# in tests/test_chip_compile.py accept the 64 MiB limit planned from it.
# A TPU generation missing here is an error, never a guessed size.
_VMEM_BYTES = {"TPU v5 lite": 128 << 20}
# interpret mode / CPU meshes (device_kind None) plan for the v5e part, so
# host-side tests exercise the production chunk geometry
_VMEM_HOST_PLAN = _VMEM_BYTES["TPU v5 lite"]


@functools.lru_cache(maxsize=None)
def _vmem_plan(device_kind: str | None):
    """(mosaic_limit_or_None, scratch_budget) for a device generation.

    The limit is half the physical VMEM capped at 64MB (the measured sweet
    spot on 128MB parts); the budget is 3/4 of the limit, leaving headroom
    for Mosaic's own temporaries. A limit at or below Mosaic's ~16MB
    default is not requested and the chunk plan just adapts.
    ``device_kind=None`` (interpret mode / CPU meshes) keeps the v5e plan;
    a TPU ``device_kind`` not in ``_VMEM_BYTES`` raises ``ValueError``.
    """
    if device_kind is None:
        vmem = _VMEM_HOST_PLAN
    elif device_kind in _VMEM_BYTES:
        vmem = _VMEM_BYTES[device_kind]
    else:
        raise ValueError(
            f"no VMEM size known for TPU device_kind {device_kind!r}; add "
            "it to ops/pallas_stencil._VMEM_BYTES with its source")
    limit = min(64 << 20, vmem // 2)
    budget = (limit * 3) // 4
    return (limit if limit > (16 << 20) else None), budget


def _tpu_device_kind():
    try:
        d = jax.devices()[0]
        return d.device_kind if d.platform == "tpu" else None
    except RuntimeError:    # uninitialized/absent backend
        return None


def _vmem_limit_params(interpret: bool):
    """compiler_params carrying the per-generation VMEM limit (or None)."""
    if interpret:
        return None
    limit, _ = _vmem_plan(_tpu_device_kind())
    return pltpu.CompilerParams(vmem_limit_bytes=limit) if limit else None


def _pick_chunk(lz: int, itemsize: int, ny: int, nx: int,
                max_chunk: int | None, streams: int = 2,
                nbuf: int = 2, ncols: int = 1):
    """z-chunk that divides ``lz`` and keeps the scratch banks
    (= streams*nbuf*chunk + 2*nbuf planes, each ``ncols`` columns wide;
    ``streams`` is 2 for u+out, or 3 with an f-array; ``nbuf`` the
    pipeline depth) inside the device generation's scratch budget — the
    one pipeline geometry all entry points share.

    ``ncols`` is the multi-RHS width: the batched kernels keep all k
    columns of each plane VMEM-resident, so the chunk plan shrinks the
    z-depth by the same factor (a k=8 batch at 512² planes plans chunks
    8x shallower, same total scratch).
    """
    plane = ny * nx * itemsize * ncols
    vmem_budget = _vmem_plan(_tpu_device_kind())[1]
    budget = int((vmem_budget // plane - 2 * nbuf) // (streams * nbuf))
    if max_chunk is not None:
        budget = min(budget, max_chunk)   # test hook: force multi-chunk paths
    chunk = max(1, min(lz, budget))
    while lz % chunk:
        chunk -= 1
    return chunk, lz // chunk


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def stencil3d_apply_pallas(u, halo_lo, halo_hi, lz: int, ny: int, nx: int,
                           interpret: bool = False,
                           max_chunk: int | None = None,
                           nbuf: int | None = None):
    """Apply the 7-point stencil to the local slab ``u`` of shape
    ``(lz, ny, nx)`` with neighbour planes ``halo_lo``/``halo_hi`` of shape
    ``(1, ny, nx)``. Returns the (lz, ny, nx) result.

    ``interpret=True`` runs the kernel through the Pallas interpreter on any
    backend — used by CI to pin the DMA pipeline's correctness off-TPU.
    ``nbuf`` overrides the pipeline depth (default: the
    ``TPU_SOLVE_STENCIL_NBUF`` plan, see :func:`_pipeline_depth`).
    """
    nbuf = nbuf or _pipeline_depth()
    chunk, nchunks = _pick_chunk(lz, u.dtype.itemsize, ny, nx, max_chunk,
                                 nbuf=nbuf)
    kernel = functools.partial(_stencil_kernel, chunk=chunk, nchunks=nchunks,
                               nbuf=nbuf)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((lz, ny, nx), u.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name="stencil3d_apply_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, halo_lo, halo_hi)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8))
def stencil3d_dot_pallas(u, halo_lo, halo_hi, lz: int, ny: int, nx: int,
                         interpret: bool = False,
                         max_chunk: int | None = None,
                         nbuf: int | None = None):
    """Fused stencil apply + local dot: returns ``(A u, <u, A u>_local)``.

    Same ``nbuf``-deep DMA pipeline as :func:`stencil3d_apply_pallas`; the
    ``<p, Ap>`` reduction CG needs every iteration is accumulated chunk by
    chunk while both operands are VMEM-resident, saving the two extra HBM
    read passes of a separate dot (the hot-loop fusion SURVEY.md §3.5 calls
    for). The partial is local to the shard — psum it over the mesh axis.
    """
    nbuf = nbuf or _pipeline_depth()
    chunk, nchunks = _pick_chunk(lz, u.dtype.itemsize, ny, nx, max_chunk,
                                 nbuf=nbuf)
    kernel = functools.partial(_stencil_kernel, chunk=chunk, nchunks=nchunks,
                               nbuf=nbuf)

    def kern(u_ref, lo_ref, hi_ref, out_ref, dot_ref):
        kernel(u_ref, lo_ref, hi_ref, out_ref, dot_ref=dot_ref)

    y, dot = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((lz, ny, nx), u.dtype),
                   # the fused <u, Au> partial is the REDUCE channel:
                   # f32 accumulation under bf16 storage
                   jax.ShapeDtypeStruct((1,), _compute_dtype(u.dtype))),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        name="stencil3d_dot_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, halo_lo, halo_hi)
    return y, dot[0]


def _stencil_many_kernel(u_ref, lo_ref, hi_ref, out_ref, chunk, nchunks,
                         nrhs, dot_ref=None, nbuf=2):
    """Multi-RHS z-chunk pipeline: the :func:`_stencil_kernel` DMA
    geometry applied to ``nrhs`` slabs at once.

    ``u_ref``/``out_ref`` are ``(nrhs, lz, ny, nx)``; per chunk the
    scratch banks hold ALL k columns' extended planes, the per-column
    input DMAs are issued back to back (k wide copies per interior chunk
    — each still the full contiguous (chunk+2)-plane window the round-6
    re-geometry established), and the stencil + optional fused per-column
    ``<u_j, A u_j>`` partials run while every column is VMEM-resident.
    The chunk plan must be built with ``_pick_chunk(..., ncols=nrhs)``.
    """
    def process(sc, osc, sem_c, sem_lo, sem_hi, sem_out):
        cdt = _compute_dtype(out_ref.dtype)
        six = jnp.asarray(6.0, cdt)
        one = jnp.int32(1)
        # column indices pinned to i32: with x64 on, a bare Python int
        # index lowers as i64, which Mosaic's memref slices reject (found
        # by the described-chip compile, tests/test_chip_compile.py)
        zero = jnp.int32(0)
        cols = [jnp.int32(j) for j in range(nrhs)]
        has_interior = nchunks >= 3

        def start_in(c, slot):
            z0 = c * jnp.int32(chunk)
            edge = (c == 0) | (c == nchunks - 1)
            for j in cols:
                if has_interior:
                    @pl.when(~edge)
                    def _(j=j):
                        pltpu.make_async_copy(
                            u_ref.at[j, pl.ds(z0 - one, chunk + 2)],
                            sc.at[slot, j], sem_c.at[slot, j]).start()

                @pl.when(edge)
                def _(j=j):
                    pltpu.make_async_copy(
                        u_ref.at[j, pl.ds(z0, chunk)],
                        sc.at[slot, j, pl.ds(one, chunk)],
                        sem_c.at[slot, j]).start()

                @pl.when(c == 0)
                def _(j=j):
                    pltpu.make_async_copy(
                        lo_ref.at[j], sc.at[slot, j, pl.ds(0, 1)],
                        sem_lo.at[slot, j]).start()

                @pl.when(edge & (c > 0))
                def _(j=j):
                    pltpu.make_async_copy(
                        u_ref.at[j, pl.ds(z0 - one, 1)],
                        sc.at[slot, j, pl.ds(0, 1)],
                        sem_lo.at[slot, j]).start()

                @pl.when(c == nchunks - 1)
                def _(j=j):
                    pltpu.make_async_copy(
                        hi_ref.at[j],
                        sc.at[slot, j, pl.ds(jnp.int32(chunk + 1), 1)],
                        sem_hi.at[slot, j]).start()

                @pl.when(edge & (c < nchunks - 1))
                def _(j=j):
                    pltpu.make_async_copy(
                        u_ref.at[j, pl.ds(z0 + jnp.int32(chunk), 1)],
                        sc.at[slot, j, pl.ds(jnp.int32(chunk + 1), 1)],
                        sem_hi.at[slot, j]).start()

        def wait_in(c, slot):
            edge = (c == 0) | (c == nchunks - 1)
            for j in cols:
                if has_interior:
                    @pl.when(~edge)
                    def _(j=j):
                        pltpu.make_async_copy(
                            u_ref.at[zero, pl.ds(0, chunk + 2)], sc.at[slot, j],
                            sem_c.at[slot, j]).wait()

                @pl.when(edge)
                def _(j=j):
                    pltpu.make_async_copy(
                        u_ref.at[zero, pl.ds(0, chunk)],
                        sc.at[slot, j, pl.ds(one, chunk)],
                        sem_c.at[slot, j]).wait()
                    pltpu.make_async_copy(
                        lo_ref.at[zero], sc.at[slot, j, pl.ds(0, 1)],
                        sem_lo.at[slot, j]).wait()
                    pltpu.make_async_copy(
                        hi_ref.at[zero],
                        sc.at[slot, j, pl.ds(jnp.int32(chunk + 1), 1)],
                        sem_hi.at[slot, j]).wait()

        def lax_rem(c):
            return jax.lax.rem(c, jnp.int32(nbuf))

        for k in range(min(nbuf - 1, nchunks)):
            start_in(jnp.int32(k), jnp.int32(k))

        def body(c, carry):
            slot = lax_rem(c)

            @pl.when(c + jnp.int32(nbuf - 1) < nchunks)
            def _():
                start_in(c + jnp.int32(nbuf - 1),
                         lax_rem(c + jnp.int32(nbuf - 1)))

            wait_in(c, slot)
            parts = []
            for j in cols:
                buf = sc[slot, j].astype(cdt)
                u = buf[1:-1]
                y = (six * u - buf[:-2] - buf[2:]
                     - _shift_y(u, -1) - _shift_y(u, +1)
                     - _shift_x(u, -1) - _shift_x(u, +1))

                @pl.when(c >= nbuf)
                def _(j=j):
                    pltpu.make_async_copy(
                        osc.at[slot, j], out_ref.at[j, pl.ds(0, chunk)],
                        sem_out.at[slot, j]).wait()
                osc[slot, j] = y.astype(out_ref.dtype)
                pltpu.make_async_copy(
                    osc.at[slot, j],
                    out_ref.at[j, pl.ds(c * jnp.int32(chunk), chunk)],
                    sem_out.at[slot, j]).start()
                if dot_ref is not None:
                    parts.append(jnp.sum(u * y))
            if dot_ref is None:
                return carry
            return carry + jnp.stack(parts)

        carry0 = (jnp.int32(0) if dot_ref is None
                  else jnp.zeros((nrhs,), cdt))
        acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(nchunks), body,
                                carry0)
        if dot_ref is not None:
            for j in range(nrhs):       # static: acc is a value, not a ref
                dot_ref[j] = acc[j]
        last = jnp.int32(nchunks - 1)
        for d in range(nbuf - 1, 0, -1):
            for j in cols:
                @pl.when(jnp.int32(nchunks) >= d + 1)
                def _(d=d, j=j):
                    pltpu.make_async_copy(
                        osc.at[lax_rem(last - jnp.int32(d)), j],
                        out_ref.at[j, pl.ds(0, chunk)],
                        sem_out.at[lax_rem(last - jnp.int32(d)), j]).wait()
        for j in cols:
            pltpu.make_async_copy(
                osc.at[lax_rem(last), j], out_ref.at[j, pl.ds(0, chunk)],
                sem_out.at[lax_rem(last), j]).wait()

    ny, nx = out_ref.shape[2], out_ref.shape[3]
    scratch = [
        pltpu.VMEM((nbuf, nrhs, chunk + 2, ny, nx), out_ref.dtype),
        pltpu.VMEM((nbuf, nrhs, chunk, ny, nx), out_ref.dtype),
        pltpu.SemaphoreType.DMA((nbuf, nrhs)),
        pltpu.SemaphoreType.DMA((nbuf, nrhs)),
        pltpu.SemaphoreType.DMA((nbuf, nrhs)),
        pltpu.SemaphoreType.DMA((nbuf, nrhs)),
    ]
    pl.run_scoped(process, *scratch)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def stencil3d_apply_many_pallas(u, halo_lo, halo_hi, lz: int, ny: int,
                                nx: int, nrhs: int,
                                interpret: bool = False,
                                max_chunk: int | None = None,
                                nbuf: int | None = None):
    """Apply the 7-point stencil to ``nrhs`` local slabs at once.

    ``u`` is ``(nrhs, lz, ny, nx)``; ``halo_lo``/``halo_hi`` are the
    neighbour plane blocks ``(nrhs, 1, ny, nx)``. The VMEM chunk plan
    accounts for the k resident columns (``_pick_chunk(..., ncols=nrhs)``)
    and the wide-DMA pipeline geometry is shared with the single-RHS
    kernel (see :func:`_stencil_many_kernel`).
    """
    nbuf = nbuf or _pipeline_depth()
    chunk, nchunks = _pick_chunk(lz, u.dtype.itemsize, ny, nx, max_chunk,
                                 nbuf=nbuf, ncols=nrhs)
    kernel = functools.partial(_stencil_many_kernel, chunk=chunk,
                               nchunks=nchunks, nrhs=nrhs, nbuf=nbuf)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((nrhs, lz, ny, nx), u.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name="stencil3d_apply_many_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, halo_lo, halo_hi)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def stencil3d_dot_many_pallas(u, halo_lo, halo_hi, lz: int, ny: int,
                              nx: int, nrhs: int,
                              interpret: bool = False,
                              max_chunk: int | None = None,
                              nbuf: int | None = None):
    """Fused multi-RHS stencil apply + per-column local dots: returns
    ``(A U, partials)`` with ``partials[j] = <u_j, A u_j>`` accumulated
    chunk by chunk while each column is VMEM-resident — the batched CG
    kernel psums the whole (nrhs,) vector in ONE collective."""
    nbuf = nbuf or _pipeline_depth()
    chunk, nchunks = _pick_chunk(lz, u.dtype.itemsize, ny, nx, max_chunk,
                                 nbuf=nbuf, ncols=nrhs)
    kernel = functools.partial(_stencil_many_kernel, chunk=chunk,
                               nchunks=nchunks, nrhs=nrhs, nbuf=nbuf)

    def kern(u_ref, lo_ref, hi_ref, out_ref, dot_ref):
        kernel(u_ref, lo_ref, hi_ref, out_ref, dot_ref=dot_ref)

    y, dot = pl.pallas_call(
        kern,
        out_shape=(jax.ShapeDtypeStruct((nrhs, lz, ny, nx), u.dtype),
                   jax.ShapeDtypeStruct((nrhs,), _compute_dtype(u.dtype))),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=(pl.BlockSpec(memory_space=pl.ANY),
                   pl.BlockSpec(memory_space=pltpu.SMEM)),
        name="stencil3d_dot_many_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, halo_lo, halo_hi)
    return y, dot


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9),
                   static_argnames=("name",))
def stencil3d_smooth_pallas(u, f, halo_lo, halo_hi, lz: int, ny: int,
                            nx: int, omega6: float,
                            interpret: bool = False,
                            max_chunk: int | None = None,
                            *, name: str | None = None):
    """One damped-Jacobi sweep in ONE streamed pass:
    ``u + omega6*(f - A u)``.

    The multigrid smoother's hot op (solvers/mg.py): fusing the update into
    the stencil pipeline reads u (+edges) and f once and writes the new u
    once (~3.3 HBM passes), where stencil-apply + XLA update chain costs
    ~5.5 + 4 passes."""
    chunk, nchunks = _pick_chunk(lz, u.dtype.itemsize, ny, nx, max_chunk,
                                 streams=3)
    # the scalar is built INSIDE the kernel from the static float — a traced
    # closure constant would be rejected by pallas_call
    kernel = functools.partial(
        _stencil_kernel, chunk=chunk, nchunks=nchunks,
        combine=lambda uc, y, fc: uc + jnp.asarray(omega6,
                                                   uc.dtype) * (fc - y))

    def kern(u_ref, lo_ref, hi_ref, f_ref, out_ref):
        kernel(u_ref, lo_ref, hi_ref, out_ref, f_ref=f_ref)

    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((lz, ny, nx), u.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name=name or "stencil3d_smooth_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, halo_lo, halo_hi, f)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8),
                   static_argnames=("name",))
def stencil3d_residual_pallas(u, f, halo_lo, halo_hi, lz: int, ny: int,
                              nx: int, interpret: bool = False,
                              max_chunk: int | None = None,
                              *, name: str | None = None):
    """Residual in ONE streamed pass: ``f - A u`` (the V-cycle's
    pre-restriction residual; same fusion rationale as the smooth sweep)."""
    chunk, nchunks = _pick_chunk(lz, u.dtype.itemsize, ny, nx, max_chunk,
                                 streams=3)
    kernel = functools.partial(
        _stencil_kernel, chunk=chunk, nchunks=nchunks,
        combine=lambda uc, y, fc: fc - y)

    def kern(u_ref, lo_ref, hi_ref, f_ref, out_ref):
        kernel(u_ref, lo_ref, hi_ref, out_ref, f_ref=f_ref)

    return pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((lz, ny, nx), u.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 4,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name=name or "stencil3d_residual_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, halo_lo, halo_hi, f)


def fullrestrict_supported(ny: int, nx: int, dtype,
                           platform: str | None = None) -> bool:
    """Gate for :func:`stencil3d_residual_restrict_pallas`: on top of the
    base kernel support the COARSE planes must stay (8, 128)-tileable —
    ``ny % 16 == 0`` and ``nx % 256 == 0`` (true for the fine levels of
    the production 512³/256³ grids; smaller levels fall back to the
    z-only fusion + y/x einsums)."""
    return (pallas_supported(ny, nx, dtype, platform)
            and ny % 16 == 0 and nx % 256 == 0)


def pallas_supported(ny: int, nx: int, dtype, platform: str | None = None
                     ) -> bool:
    """The kernel wants full (8,128)-tileable planes and TPU devices.

    ``platform`` is the platform of the mesh the op actually runs on
    (``comm.devices[0].platform``) — a CPU-device mesh inside a
    TPU-capable process must NOT take the Mosaic path (ADVICE r4); when
    omitted, falls back to the process default backend."""
    if (platform or jax.default_backend()) != "tpu":
        return False
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.float32):
        return nx % 128 == 0 and ny % 8 == 0
    if dt == jnp.dtype(jnp.bfloat16):
        # bf16 VMEM tiles are (16, 128): the packed native tile — the
        # bf16-STORAGE pipeline (same DMA geometry, half the bytes per
        # plane, so _pick_chunk's resident z-depth doubles; arithmetic
        # upconverts to f32 in VREGs, see _compute_dtype)
        return nx % 128 == 0 and ny % 16 == 0
    return False


def _pick_chunk_zrestrict(lz: int, itemsize: int, ny: int, nx: int,
                          max_chunk: int | None):
    """Even z-chunk dividing ``lz`` for the fused residual+z-restrict
    pipeline: scratch is 2 u-banks (chunk+4 planes), 2 f-banks (chunk+2)
    and 2 half-size out-banks (chunk/2) = 5·chunk + 12 planes. The fused
    prolongation's scratch (~4.25·chunk + 3 planes) fits the same plan;
    on v5e its time a call is the same at chunks 4 to 16 (512² and 256²
    planes)."""
    plane = ny * nx * itemsize
    budget_planes = int(_vmem_plan(_tpu_device_kind())[1] // plane)
    chunk = max(2, min(lz, (budget_planes - 12) // 5))
    if max_chunk is not None:
        chunk = min(chunk, max_chunk)     # test hook: force multi-chunk
    chunk -= chunk % 2
    chunk = max(chunk, 2)
    while chunk > 2 and lz % chunk:
        chunk -= 2
    if lz % chunk:
        raise ValueError(f"fused z-restrict needs an even chunk dividing "
                         f"lz={lz}")
    return chunk, lz // chunk


def _mk_halo2_io(u_ref, f_ref, usc, fsc, sem_u, sem_ul, sem_uh, sem_f,
                 sem_fl, sem_fh, chunk, nchunks):
    """start_in/wait_in pair for the 2-deep-u / 1-deep-f extended-chunk
    DMA pipeline shared by :func:`_resid_zrestrict_kernel` and
    :func:`_double_sweep_kernel`: per chunk c, u planes [z0-2, z0+chunk+2)
    land in a (chunk+4)-plane bank and f planes [z0-1, z0+chunk+1) in a
    (chunk+2)-plane bank, edge DMAs skipped beyond the global ends (the
    callers mask the ghost planes on the VALUE). Requires chunk >= 2 so
    every edge DMA stays in bounds."""
    one = jnp.int32(1)
    two = jnp.int32(2)

    def start_in(c, slot):
        z0 = c * jnp.int32(chunk)
        pltpu.make_async_copy(
            u_ref.at[pl.ds(z0, chunk)],
            usc.at[slot, pl.ds(two, chunk)], sem_u.at[slot]).start()

        @pl.when(c > 0)
        def _():
            pltpu.make_async_copy(
                u_ref.at[pl.ds(z0 - two, 2)],
                usc.at[slot, pl.ds(0, 2)], sem_ul.at[slot]).start()

        @pl.when(c < nchunks - 1)
        def _():
            pltpu.make_async_copy(
                u_ref.at[pl.ds(z0 + jnp.int32(chunk), 2)],
                usc.at[slot, pl.ds(jnp.int32(chunk + 2), 2)],
                sem_uh.at[slot]).start()
        pltpu.make_async_copy(
            f_ref.at[pl.ds(z0, chunk)],
            fsc.at[slot, pl.ds(one, chunk)], sem_f.at[slot]).start()

        @pl.when(c > 0)
        def _():
            pltpu.make_async_copy(
                f_ref.at[pl.ds(z0 - one, 1)],
                fsc.at[slot, pl.ds(0, 1)], sem_fl.at[slot]).start()

        @pl.when(c < nchunks - 1)
        def _():
            pltpu.make_async_copy(
                f_ref.at[pl.ds(z0 + jnp.int32(chunk), 1)],
                fsc.at[slot, pl.ds(jnp.int32(chunk + 1), 1)],
                sem_fh.at[slot]).start()

    def wait_in(c, slot):
        pltpu.make_async_copy(u_ref.at[pl.ds(0, chunk)],
                              usc.at[slot, pl.ds(two, chunk)],
                              sem_u.at[slot]).wait()
        pltpu.make_async_copy(f_ref.at[pl.ds(0, chunk)],
                              fsc.at[slot, pl.ds(one, chunk)],
                              sem_f.at[slot]).wait()

        @pl.when(c > 0)
        def _():
            pltpu.make_async_copy(u_ref.at[pl.ds(0, 2)],
                                  usc.at[slot, pl.ds(0, 2)],
                                  sem_ul.at[slot]).wait()
            pltpu.make_async_copy(f_ref.at[pl.ds(0, 1)],
                                  fsc.at[slot, pl.ds(0, 1)],
                                  sem_fl.at[slot]).wait()

        @pl.when(c < nchunks - 1)
        def _():
            pltpu.make_async_copy(
                u_ref.at[pl.ds(0, 2)],
                usc.at[slot, pl.ds(jnp.int32(chunk + 2), 2)],
                sem_uh.at[slot]).wait()
            pltpu.make_async_copy(
                f_ref.at[pl.ds(0, 1)],
                fsc.at[slot, pl.ds(jnp.int32(chunk + 1), 1)],
                sem_fh.at[slot]).wait()

    return start_in, wait_in


def _halo2_scratch(chunk: int, out_planes: int, ny: int, nx: int, dtype):
    """Scratch list for the 2-deep-halo pipeline kernels: u banks
    (chunk+4), f banks (chunk+2), out banks (``out_planes``), and the
    seven DMA semaphore pairs _mk_halo2_io + the output DMA consume."""
    return [
        pltpu.VMEM((2, chunk + 4, ny, nx), dtype),
        pltpu.VMEM((2, chunk + 2, ny, nx), dtype),
        pltpu.VMEM((2, out_planes, ny, nx), dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]


def _chunk_coarse_z(uext, fext, c, chunk, nchunks, rscale, dtype):
    """z-restricted residual of one extended chunk, shared by the fused
    restriction kernels: from the (chunk+4)-plane u bank and the
    (chunk+2)-plane f bank of chunk ``c``, compute ``r = f - A u`` on the
    (chunk+2) extended planes in VMEM and return the (chunk/2, ny, nx)
    z-restricted coarse planes
    ``coarse[i] = s·(0.75·(r[2i]+r[2i+1]) + 0.25·(r[2i-1]+r[2i+2]))``
    (solvers/mg._r1d weights, zero ghosts)."""
    cc = chunk // 2
    ny, nx = uext.shape[1], uext.shape[2]
    six = jnp.asarray(6.0, dtype)
    # the u planes just below/above the domain are Dirichlet zero
    # ghosts feeding r at the first/last interior plane — stale
    # scratch there is masked on the VALUE (Mosaic rejects
    # compound-indexed scratch stores under cond); the outermost
    # planes (0 / chunk+3) feed only the masked rext end planes
    urow = jax.lax.broadcasted_iota(jnp.int32, (chunk + 4, 1, 1), 0)
    uext = jnp.where((urow <= 1) & (c == 0), 0.0, uext)
    uext = jnp.where((urow >= jnp.int32(chunk + 2))
                     & (c == nchunks - 1), 0.0, uext)
    u = uext[1:-1]                       # planes [z0-1, z0+chunk]
    y = (six * u - uext[:-2] - uext[2:]
         - _shift_y(u, -1) - _shift_y(u, +1)
         - _shift_x(u, -1) - _shift_x(u, +1))
    rext = fext - y                      # (chunk+2, ny, nx)
    # r ghosts beyond the global domain are exactly zero
    zrow = jax.lax.broadcasted_iota(jnp.int32, (chunk + 2, 1, 1), 0)
    rext = jnp.where((zrow == 0) & (c == 0), 0.0, rext)
    rext = jnp.where((zrow == jnp.int32(chunk + 1))
                     & (c == nchunks - 1), 0.0, rext)
    # coarse[j] over rext indices (2j, 2j+1, 2j+2, 2j+3)
    lowpair = rext[:-2].reshape(cc, 2, ny, nx)
    highpair = rext[2:].reshape(cc, 2, ny, nx)
    return jnp.asarray(rscale, dtype) * (
        0.25 * (lowpair[:, 0] + highpair[:, 1])
        + 0.75 * (lowpair[:, 1] + highpair[:, 0]))


def _resid_zrestrict_kernel(u_ref, f_ref, out_ref, chunk, nchunks, rscale):
    """Fused ``r = f - A u`` + one-axis z-restriction, manual-DMA pipeline.

    Round-5 V-cycle optimization: the fine residual never touches HBM —
    each chunk computes r on (chunk+2) extended planes in VMEM and writes
    only the (chunk/2) z-restricted coarse planes (see
    :func:`_chunk_coarse_z`), saving the r write + the z-einsum's r read
    (~2 fine HBM passes per cycle). SINGLE-DEVICE slabs only: the ghost
    planes are the global Dirichlet zeros; a sharded slab would need
    2-deep u halos (the separate residual+restrict passes keep the
    1-plane exchange there).
    """
    ny, nx = out_ref.shape[1], out_ref.shape[2]
    cc = chunk // 2

    def process(usc, fsc, osc, sem_u, sem_ul, sem_uh, sem_f, sem_fl,
                sem_fh, sem_out):
        start_in, wait_in = _mk_halo2_io(
            u_ref, f_ref, usc, fsc, sem_u, sem_ul, sem_uh, sem_f,
            sem_fl, sem_fh, chunk, nchunks)

        def lax_rem(c):
            return jax.lax.rem(c, jnp.int32(2))

        start_in(jnp.int32(0), jnp.int32(0))

        def body(c, carry):
            slot = lax_rem(c)
            nslot = lax_rem(c + 1)

            @pl.when(c + 1 < nchunks)
            def _():
                start_in(c + 1, nslot)

            wait_in(c, slot)
            coarse = _chunk_coarse_z(usc[slot], fsc[slot], c, chunk,
                                     nchunks, rscale, out_ref.dtype)

            @pl.when(c >= 2)
            def _():
                pltpu.make_async_copy(
                    osc.at[slot], out_ref.at[pl.ds(0, cc)],
                    sem_out.at[slot]).wait()
            osc[slot] = coarse
            pltpu.make_async_copy(
                osc.at[slot], out_ref.at[pl.ds(c * jnp.int32(cc), cc)],
                sem_out.at[slot]).start()
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(nchunks), body,
                          jnp.int32(0))
        last = jnp.int32(nchunks - 1)

        @pl.when(jnp.int32(nchunks) >= 2)
        def _():
            pltpu.make_async_copy(
                osc.at[lax_rem(last + 1)], out_ref.at[pl.ds(0, cc)],
                sem_out.at[lax_rem(last + 1)]).wait()

        pltpu.make_async_copy(
            osc.at[lax_rem(last)], out_ref.at[pl.ds(0, cc)],
            sem_out.at[lax_rem(last)]).wait()

    pl.run_scoped(process, *_halo2_scratch(chunk, cc, ny, nx,
                                           out_ref.dtype))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7),
                   static_argnames=("name",))
def stencil3d_residual_zrestrict_pallas(u, f, lz: int, ny: int, nx: int,
                                        rscale: float,
                                        interpret: bool = False,
                                        max_chunk: int | None = None,
                                        *, name: str | None = None):
    """Fused residual + one-axis z-restriction for SINGLE-DEVICE slabs:
    ``zrestrict(f - A u)`` with solvers/mg._r1d's weights and zero ghosts,
    returning the (lz/2, ny, nx) coarse array without ever writing the
    fine residual to HBM (see _resid_zrestrict_kernel)."""
    chunk, nchunks = _pick_chunk_zrestrict(lz, u.dtype.itemsize, ny, nx,
                                           max_chunk)
    kernel = functools.partial(_resid_zrestrict_kernel, chunk=chunk,
                               nchunks=nchunks, rscale=rscale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((lz // 2, ny, nx), u.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name=name or "stencil3d_residual_zrestrict_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, f)


def _resid_restrict3_kernel(u_ref, f_ref, wyt_ref, wx_ref, out_ref, chunk,
                            nchunks, rscale):
    """Fused ``r = f - A u`` + FULL 3-axis restriction (round 6): the
    coarse RHS is produced from the same VMEM-resident fine chunks as the
    residual itself — neither the fine residual NOR any intermediate
    (half-restricted) array ever touches HBM.

    Per chunk: the z-restricted coarse planes come from
    :func:`_chunk_coarse_z`; the y/x restrictions are then two MXU matmuls
    per coarse plane with the banded transfer matrices (``wyt`` is the
    (ny/2, ny) TRANSPOSED one-axis restriction matrix, ``wx`` the
    (nx, nx/2) one — solvers/mg._tmat, weights identical to the einsum
    path), statically unrolled over the chunk's coarse planes while the
    z-restricted values are still in VMEM. The kernel writes only
    (chunk/2, ny/2, nx/2) — 1/8 of a fine pass — where the round-5 z-only
    fusion still wrote and re-read the (lz/2, ny, nx) intermediate
    (~1 fine pass of extra traffic per V-cycle at 512³).

    SINGLE-DEVICE slabs only, like the z-only variant (the zero Dirichlet
    ghosts are built in).
    """
    ny, nx = u_ref.shape[1], u_ref.shape[2]
    cc = chunk // 2
    nyc, nxc = out_ref.shape[1], out_ref.shape[2]

    def process(usc, fsc, osc, sem_u, sem_ul, sem_uh, sem_f, sem_fl,
                sem_fh, sem_out):
        start_in, wait_in = _mk_halo2_io(
            u_ref, f_ref, usc, fsc, sem_u, sem_ul, sem_uh, sem_f,
            sem_fl, sem_fh, chunk, nchunks)

        def lax_rem(c):
            return jax.lax.rem(c, jnp.int32(2))

        start_in(jnp.int32(0), jnp.int32(0))

        def body(c, carry):
            slot = lax_rem(c)
            nslot = lax_rem(c + 1)

            @pl.when(c + 1 < nchunks)
            def _():
                start_in(c + 1, nslot)

            wait_in(c, slot)
            dt = out_ref.dtype
            coarse_z = _chunk_coarse_z(usc[slot], fsc[slot], c, chunk,
                                       nchunks, rscale, dt)
            wyt = wyt_ref[...]               # (nyc, ny)
            wx = wx_ref[...]                 # (nx, nxc)
            # per-plane (nyc,ny)@(ny,nx)@(nx,nxc) — static unroll keeps
            # every operand a clean rank-2 MXU shape (a batched 3-D
            # contraction would need relayout transposes Mosaic handles
            # poorly on the minor dims)
            planes = []
            for j in range(cc):
                t = jax.lax.dot(wyt, coarse_z[j],
                                preferred_element_type=dt)
                planes.append(jax.lax.dot(t, wx,
                                          preferred_element_type=dt))
            out = jnp.stack(planes)

            @pl.when(c >= 2)
            def _():
                pltpu.make_async_copy(
                    osc.at[slot], out_ref.at[pl.ds(0, cc)],
                    sem_out.at[slot]).wait()
            osc[slot] = out
            pltpu.make_async_copy(
                osc.at[slot], out_ref.at[pl.ds(c * jnp.int32(cc), cc)],
                sem_out.at[slot]).start()
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(nchunks), body,
                          jnp.int32(0))
        last = jnp.int32(nchunks - 1)

        @pl.when(jnp.int32(nchunks) >= 2)
        def _():
            pltpu.make_async_copy(
                osc.at[lax_rem(last + 1)], out_ref.at[pl.ds(0, cc)],
                sem_out.at[lax_rem(last + 1)]).wait()

        pltpu.make_async_copy(
            osc.at[lax_rem(last)], out_ref.at[pl.ds(0, cc)],
            sem_out.at[lax_rem(last)]).wait()

    scratch = [
        pltpu.VMEM((2, chunk + 4, ny, nx), out_ref.dtype),
        pltpu.VMEM((2, chunk + 2, ny, nx), out_ref.dtype),
        pltpu.VMEM((2, cc, nyc, nxc), out_ref.dtype),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    pl.run_scoped(process, *scratch)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8, 9),
                   static_argnames=("name",))
def stencil3d_residual_restrict_pallas(u, f, wyt, wx, lz: int, ny: int,
                                       nx: int, rscale: float,
                                       interpret: bool = False,
                                       max_chunk: int | None = None,
                                       *, name: str | None = None):
    """Fused residual + FULL 3-axis restriction for SINGLE-DEVICE slabs:
    ``restrict(f - A u)`` with solvers/mg's transfer weights and zero
    ghosts, returning the (lz/2, ny/2, nx/2) coarse RHS without the fine
    residual or any intermediate ever touching HBM (see
    :func:`_resid_restrict3_kernel`). ``wyt``/``wx`` are the transposed-y
    and x one-axis restriction matrices (mg._tmat(ny).T / mg._tmat(nx))."""
    if lz % 2 or ny % 2 or nx % 2:
        raise ValueError(f"fused 3-axis restriction needs even dims, got "
                         f"({lz}, {ny}, {nx})")
    chunk, nchunks = _pick_chunk_zrestrict(lz, u.dtype.itemsize, ny, nx,
                                           max_chunk)
    kernel = functools.partial(_resid_restrict3_kernel, chunk=chunk,
                               nchunks=nchunks, rscale=rscale)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((lz // 2, ny // 2, nx // 2),
                                       u.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  # the two small transfer matrices ride the automatic
                  # VMEM staging (≤ ~0.5 MB each at 512³)
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name=name or "stencil3d_residual_restrict_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, f, wyt, wx)


def _prolong_add_kernel(u_ref, e_ref, wy_ref, wxt_ref, out_ref, chunk,
                        nchunks):
    """Fused ``u + P e``, manual-DMA z-chunk pipeline: the upward leg's
    counterpart of :func:`_resid_restrict3_kernel`.

    Per coarse plane ``e[i]`` the y/x prolongation is two MXU matmuls,
    ``Q[i] = wy @ e[i] @ wxt`` (``wy`` the (ny, ny/2) and ``wxt`` the
    (nx/2, nx) one-axis prolongation matrices); z is then the VPU pair
    ``fine[2i] = 0.75·Q[i] + 0.25·Q[i-1]``,
    ``fine[2i+1] = 0.75·Q[i] + 0.25·Q[i+1]``, added to u and written.
    Fine chunk ``c`` (coarse planes ``i0 = c·chunk/2`` on) needs
    ``Q[i0-1 .. i0+chunk/2]``: the two lowest are the previous chunk's
    two highest, carried in VMEM, so each coarse plane is multiplied
    once. The chunk's coarse bank holds ``e[i0+1 .. i0+chunk/2]``, whose
    top plane lies beyond the domain on the last chunk: that DMA is
    skipped and the plane masked to the zero ghost on the VALUE; chunk 0
    takes ``e[0]`` in the prologue, with ``Q[-1] = 0``. Neither the
    correction nor an intermediate touches HBM: read u and e, write u.
    SINGLE-DEVICE slabs only (zero Dirichlet ghosts built in).
    """
    ny, nx = u_ref.shape[1], u_ref.shape[2]
    nyc, nxc = e_ref.shape[1], e_ref.shape[2]
    cc = chunk // 2
    dt = out_ref.dtype

    def process(usc, esc, osc, e0, car, sem_u, sem_e, sem_eh, sem_0,
                sem_out):
        one = jnp.int32(1)

        def lax_rem(c):
            return jax.lax.rem(c, jnp.int32(2))

        def start_in(c, slot):
            z0 = c * jnp.int32(chunk)
            i0 = c * jnp.int32(cc)
            pltpu.make_async_copy(u_ref.at[pl.ds(z0, chunk)], usc.at[slot],
                                  sem_u.at[slot]).start()
            if cc > 1:
                pltpu.make_async_copy(
                    e_ref.at[pl.ds(i0 + one, cc - 1)],
                    esc.at[slot, pl.ds(0, cc - 1)], sem_e.at[slot]).start()

            @pl.when(c < nchunks - 1)
            def _():
                pltpu.make_async_copy(
                    e_ref.at[pl.ds(i0 + jnp.int32(cc), 1)],
                    esc.at[slot, pl.ds(jnp.int32(cc - 1), 1)],
                    sem_eh.at[slot]).start()

        def wait_in(c, slot):
            pltpu.make_async_copy(u_ref.at[pl.ds(0, chunk)], usc.at[slot],
                                  sem_u.at[slot]).wait()
            if cc > 1:
                pltpu.make_async_copy(
                    e_ref.at[pl.ds(0, cc - 1)],
                    esc.at[slot, pl.ds(0, cc - 1)], sem_e.at[slot]).wait()

            @pl.when(c < nchunks - 1)
            def _():
                pltpu.make_async_copy(
                    e_ref.at[pl.ds(0, 1)],
                    esc.at[slot, pl.ds(jnp.int32(cc - 1), 1)],
                    sem_eh.at[slot]).wait()

        def pyx(plane):
            # fp32 contract precision, as the einsum path: Mosaic's default
            # f32 dot is one bf16 pass (measured on v5e: P e off by 1.5e-3
            # relative, and CG+MG at 512³ took 9 iterations, not 8)
            hi = jax.lax.Precision.HIGHEST
            t = jax.lax.dot(wy_ref[...], plane, precision=hi,
                            preferred_element_type=dt)
            return jax.lax.dot(t, wxt_ref[...], precision=hi,
                               preferred_element_type=dt)

        start_in(jnp.int32(0), jnp.int32(0))
        first = pltpu.make_async_copy(e_ref.at[pl.ds(0, 1)], e0, sem_0)
        first.start()
        first.wait()
        car[0] = jnp.zeros((ny, nx), dt)        # Q[-1], the zero ghost
        car[1] = pyx(e0[0])                     # Q[0]
        quarter = jnp.asarray(0.25, dt)
        three = jnp.asarray(0.75, dt)

        def body(c, carry):
            slot = lax_rem(c)

            @pl.when(c + 1 < nchunks)
            def _():
                start_in(c + 1, lax_rem(c + 1))

            wait_in(c, slot)

            @pl.when(c >= 2)
            def _():
                pltpu.make_async_copy(
                    osc.at[slot], out_ref.at[pl.ds(0, chunk)],
                    sem_out.at[slot]).wait()
            qm, q0 = car[0], car[1]
            for k in range(cc):         # coarse plane i0 + k
                top = esc[slot, k]
                if k == cc - 1:         # e[lzc] lies beyond the domain
                    top = jnp.where(c == nchunks - 1, 0.0, top)
                qp = pyx(top)
                osc[slot, 2 * k] = usc[slot, 2 * k] + (three * q0
                                                       + quarter * qm)
                osc[slot, 2 * k + 1] = usc[slot, 2 * k + 1] + (
                    three * q0 + quarter * qp)
                qm, q0 = q0, qp
            car[0] = qm
            car[1] = q0
            pltpu.make_async_copy(
                osc.at[slot], out_ref.at[pl.ds(c * jnp.int32(chunk), chunk)],
                sem_out.at[slot]).start()
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(nchunks), body,
                          jnp.int32(0))
        last = jnp.int32(nchunks - 1)

        @pl.when(jnp.int32(nchunks) >= 2)
        def _():
            pltpu.make_async_copy(
                osc.at[lax_rem(last + 1)], out_ref.at[pl.ds(0, chunk)],
                sem_out.at[lax_rem(last + 1)]).wait()

        pltpu.make_async_copy(
            osc.at[lax_rem(last)], out_ref.at[pl.ds(0, chunk)],
            sem_out.at[lax_rem(last)]).wait()

    scratch = [
        pltpu.VMEM((2, chunk, ny, nx), dt),
        pltpu.VMEM((2, cc, nyc, nxc), dt),
        pltpu.VMEM((2, chunk, ny, nx), dt),
        pltpu.VMEM((1, nyc, nxc), dt),
        pltpu.VMEM((2, ny, nx), dt),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA((2,)),
        pltpu.SemaphoreType.DMA(()),
        pltpu.SemaphoreType.DMA((2,)),
    ]
    pl.run_scoped(process, *scratch)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7, 8),
                   static_argnames=("name",))
def stencil3d_prolong_add_pallas(u, e_c, wy, wxt, lz: int, ny: int, nx: int,
                                 interpret: bool = False,
                                 max_chunk: int | None = None,
                                 *, name: str | None = None):
    """Fused prolongation + correction for SINGLE-DEVICE slabs:
    ``u + P e_c`` with solvers/mg's transfer weights and zero ghosts, for
    the (lz/2, ny/2, nx/2) coarse correction ``e_c``, without the
    prolonged correction or any intermediate touching HBM (see
    :func:`_prolong_add_kernel`). ``wy``/``wxt`` are the y and transposed
    x one-axis prolongation matrices (mg._tmat(ny, dt, 1.0) /
    mg._tmat(nx, dt, 1.0).T)."""
    if lz % 2 or ny % 2 or nx % 2:
        raise ValueError(f"fused prolongation needs even dims, got "
                         f"({lz}, {ny}, {nx})")
    chunk, nchunks = _pick_chunk_zrestrict(lz, u.dtype.itemsize, ny, nx,
                                           max_chunk)
    kernel = functools.partial(_prolong_add_kernel, chunk=chunk,
                               nchunks=nchunks)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((lz, ny, nx), u.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY),
                  # the two small transfer matrices ride the automatic
                  # VMEM staging, as in the restriction kernel
                  pl.BlockSpec(memory_space=pltpu.VMEM),
                  pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name=name or "stencil3d_prolong_add_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, e_c, wy, wxt)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6, 7),
                   static_argnames=("name",))
def stencil3d_smooth0_pair_pallas(f, lz: int, ny: int, nx: int,
                                  w1: float, w2: float,
                                  interpret: bool = False,
                                  max_chunk: int | None = None,
                                  *, name: str | None = None):
    """TWO damped-Jacobi sweeps from a ZERO initial guess in ONE streamed
    pass (round 5; single-device slabs, zero Dirichlet ghosts):

        u1 = w1 f;   u2 = u1 + w2 (f - A u1) = (w1 + w2) f - w1 w2 (A f)

    — algebraically one stencil apply on ``f`` itself, so the existing
    apply pipeline serves with a combine. Reads f (+edge planes) once,
    writes u once (~2.3 HBM passes) where the separate path pays an XLA
    elementwise pass for u1 plus a full fused sweep (~5+ passes).
    ``w1``/``w2`` are the ω/6 factors of the two sweeps (mg.cheby_omegas
    order; the factors commute so order doesn't matter).
    """
    chunk, nchunks = _pick_chunk(lz, f.dtype.itemsize, ny, nx, max_chunk)
    kernel = functools.partial(
        _stencil_kernel, chunk=chunk, nchunks=nchunks,
        combine=lambda fc, y, _unused: (
            jnp.asarray(w1 + w2, fc.dtype) * fc
            - jnp.asarray(w1 * w2, fc.dtype) * y))
    z = jnp.zeros((1, ny, nx), f.dtype)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((lz, ny, nx), f.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name=name or "stencil3d_smooth0_pair_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(f, z, z)


def _double_sweep_kernel(u_ref, f_ref, out_ref, chunk, nchunks, w1, w2):
    """TWO damped-Jacobi sweeps in one streamed pass (nonzero guess):
    ``u2 = S_{w2}(S_{w1}(u))`` with ``S_w(v) = v + w (f - A v)``.

    Same chunk+4/chunk+2 extended-plane geometry as
    :func:`_resid_zrestrict_kernel` (shared _mk_halo2_io pipeline): u1 is
    computed on (chunk+2) planes in VMEM, the second sweep then needs only
    the center chunk. Ghost planes beyond the global domain stay EXACTLY
    zero through both sweeps (Dirichlet), realized by masking u1's end
    planes. SINGLE-DEVICE slabs only (2-deep halos otherwise).
    Traffic: read u+f (+edges) once, write u2 once (~3.2 fine passes) vs
    two separate fused sweeps (~6.6).
    """
    ny, nx = out_ref.shape[1], out_ref.shape[2]

    def process(usc, fsc, osc, sem_u, sem_ul, sem_uh, sem_f, sem_fl,
                sem_fh, sem_out):
        six = jnp.asarray(6.0, out_ref.dtype)
        start_in, wait_in = _mk_halo2_io(
            u_ref, f_ref, usc, fsc, sem_u, sem_ul, sem_uh, sem_f,
            sem_fl, sem_fh, chunk, nchunks)

        def lax_rem(c):
            return jax.lax.rem(c, jnp.int32(2))

        def stencil(v):
            """A v on the interior planes of an extended array (len-2)."""
            vc = v[1:-1]
            return (six * vc - v[:-2] - v[2:]
                    - _shift_y(vc, -1) - _shift_y(vc, +1)
                    - _shift_x(vc, -1) - _shift_x(vc, +1))

        start_in(jnp.int32(0), jnp.int32(0))

        def body(c, carry):
            slot = lax_rem(c)
            nslot = lax_rem(c + 1)

            @pl.when(c + 1 < nchunks)
            def _():
                start_in(c + 1, nslot)

            wait_in(c, slot)
            uext = usc[slot]                     # (chunk+4, ny, nx)
            urow = jax.lax.broadcasted_iota(jnp.int32,
                                            (chunk + 4, 1, 1), 0)
            uext = jnp.where((urow <= 1) & (c == 0), 0.0, uext)
            uext = jnp.where((urow >= jnp.int32(chunk + 2))
                             & (c == nchunks - 1), 0.0, uext)
            fext = fsc[slot]                     # (chunk+2, ny, nx)
            # sweep 1 on planes [z0-1, z0+chunk]
            u1 = uext[1:-1] + jnp.asarray(w1, uext.dtype) * (
                fext - stencil(uext))
            # ghosts beyond the domain stay exactly zero through the sweep
            zrow = jax.lax.broadcasted_iota(jnp.int32,
                                            (chunk + 2, 1, 1), 0)
            u1 = jnp.where((zrow == 0) & (c == 0), 0.0, u1)
            u1 = jnp.where((zrow == jnp.int32(chunk + 1))
                           & (c == nchunks - 1), 0.0, u1)
            # sweep 2 on the center chunk
            u2 = u1[1:-1] + jnp.asarray(w2, u1.dtype) * (
                fext[1:-1] - stencil(u1))

            @pl.when(c >= 2)
            def _():
                pltpu.make_async_copy(
                    osc.at[slot], out_ref.at[pl.ds(0, chunk)],
                    sem_out.at[slot]).wait()
            osc[slot] = u2
            pltpu.make_async_copy(
                osc.at[slot],
                out_ref.at[pl.ds(c * jnp.int32(chunk), chunk)],
                sem_out.at[slot]).start()
            return carry

        jax.lax.fori_loop(jnp.int32(0), jnp.int32(nchunks), body,
                          jnp.int32(0))
        last = jnp.int32(nchunks - 1)

        @pl.when(jnp.int32(nchunks) >= 2)
        def _():
            pltpu.make_async_copy(
                osc.at[lax_rem(last + 1)], out_ref.at[pl.ds(0, chunk)],
                sem_out.at[lax_rem(last + 1)]).wait()

        pltpu.make_async_copy(
            osc.at[lax_rem(last)], out_ref.at[pl.ds(0, chunk)],
            sem_out.at[lax_rem(last)]).wait()

    pl.run_scoped(process, *_halo2_scratch(chunk, chunk, ny, nx,
                                           out_ref.dtype))


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6, 7, 8),
                   static_argnames=("name",))
def stencil3d_smooth_pair_pallas(u, f, lz: int, ny: int, nx: int,
                                 w1: float, w2: float,
                                 interpret: bool = False,
                                 max_chunk: int | None = None,
                                 *, name: str | None = None):
    """Two damped-Jacobi sweeps from a NONZERO guess in one streamed pass
    (see _double_sweep_kernel). ``w1``/``w2`` are the sweeps' ω/6.

    Raises ValueError when no z-chunk >= 2 divides ``lz`` within the VMEM
    budget (chunk=1 would put the 2-deep edge DMAs out of bounds) — the
    caller (mg._smooth) falls back to two separate fused sweeps."""
    # scratch is 2·(chunk+4 + chunk+2 + chunk) = 6·chunk + 12 planes
    plane = ny * nx * u.dtype.itemsize
    budget_planes = int(_vmem_plan(_tpu_device_kind())[1] // plane)
    chunk = min(lz, max((budget_planes - 12) // 6, 0))
    if max_chunk is not None:
        chunk = min(chunk, max_chunk)
    while chunk >= 2 and lz % chunk:
        chunk -= 1
    if chunk < 2:
        raise ValueError(
            f"double-sweep kernel needs a z-chunk >= 2 dividing lz={lz} "
            "within the VMEM budget (2-deep halo DMAs)")
    kernel = functools.partial(_double_sweep_kernel, chunk=chunk,
                               nchunks=lz // chunk, w1=w1, w2=w2)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((lz, ny, nx), u.dtype),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 2,
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        name=name or "stencil3d_smooth_pair_pallas",
        compiler_params=_vmem_limit_params(interpret),
        interpret=interpret,
    )(u, f)
