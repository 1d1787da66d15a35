"""Device-mesh communication substrate — the framework's MPI replacement.

The reference distributes work over an ``mpi4py`` communicator (OpenMPI;
reference ``test.py:55-57``, ``environment.yaml:4``).  Here the communicator is
a 1-D :class:`jax.sharding.Mesh` over TPU chips: data placement happens through
``NamedSharding`` (XLA moves bytes over PCIe/ICI/DCN), and solver-internal
collectives (the reference's library-internal ``MPI_Allreduce`` for dots and
``VecScatter`` halo exchanges) become ``lax.psum`` / ``lax.all_gather`` /
``lax.ppermute`` inside ``shard_map``-decorated, jit-compiled programs.

No rank-conditional code: every helper is SPMD. A 1-device mesh degenerates
cleanly (collectives become no-ops under XLA).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..resilience import faults as _faults

ROW_AXIS = "rows"


def faulted_psum(x, axis: str):
    """``lax.psum`` with the ``comm.psum`` fault point applied at TRACE
    time (resilience/faults.py): 'drop' elides the reduction — every shard
    keeps its local partial, a lost allreduce — and 'corrupt' poisons the
    reduced value (NaN for inexact dtypes, bit-flip for integers). With no
    fault plan armed this IS ``lax.psum``; programs traced while a psum
    fault could fire are cache-isolated via ``faults.trace_key()`` in the
    solver program cache key (solvers/krylov.py). The one injectable-psum
    implementation — DeviceComm.psum and the solver-loop reductions both
    route through it.
    """
    fault = _faults.triggered("comm.psum")
    if fault is None:
        return lax.psum(x, axis)
    if fault.kind == "drop":
        return x
    y = lax.psum(x, axis)
    if jnp.issubdtype(jnp.result_type(y), jnp.inexact):
        return y * jnp.asarray(jnp.nan, jnp.result_type(y))
    return ~y


class DeviceComm:
    """A communicator-shaped object wrapping a 1-D device mesh.

    Plays the role the ``comm`` argument plays in the reference wrapper API
    (``petsc_funcs.py:5,13`` take ``comm`` first) — the facade keeps that
    argument slot, now carrying a mesh instead of an MPI communicator.
    """

    def __init__(self, mesh: Mesh | None = None, axis: str = ROW_AXIS,
                 devices=None, n_devices: int | None = None):
        if mesh is None:
            if devices is None:
                from ..utils.phases import stamp
                stamp("backend_init_begin")  # first jax.devices() initializes
                devices = jax.devices()      # the backend
                stamp("backend_init_end")
                if n_devices is not None:
                    devices = devices[:n_devices]
            mesh = Mesh(np.asarray(devices), (axis,))
        self.mesh = mesh
        self.axis = axis
        # device ids of the mesh members, precomputed for the hot-path
        # lost-device guards (resilience/faults.check_lost / mesh_fault)
        self.device_ids = tuple(int(d.id) for d in self.mesh.devices.ravel())

    # ---- MPI-communicator-shaped info --------------------------------------
    @property
    def size(self) -> int:
        """Number of shards — the analog of ``comm.Get_size()``."""
        return self.mesh.shape[self.axis]

    @property
    def devices(self):
        return list(self.mesh.devices.ravel())

    @property
    def platform(self) -> str:
        """Platform of the mesh's devices ('cpu'/'tpu') — kernel fast-path
        gates key on THIS, not the process default backend: a CPU-device
        mesh in a TPU-capable process must take the CPU paths (ADVICE r4)."""
        return self.mesh.devices.ravel()[0].platform

    def __repr__(self):
        return f"DeviceComm(size={self.size}, axis={self.axis!r})"

    def fingerprint(self) -> dict:
        """Plain-data mesh descriptor for cross-host exchange (the
        transport hello/stats payload — serving/remote.py): platform,
        shard count and member device ids. Deliberately carries NO
        device handles, so it pickles across processes; the elastic
        checkpoint format never encodes a mesh size, and this is how a
        peer still learns (and reports) what geometry is serving."""
        return {"platform": self.platform, "size": int(self.size),
                "device_ids": list(self.device_ids)}

    # ---- shardings ---------------------------------------------------------
    @property
    def row_sharding(self) -> NamedSharding:
        """Shard the leading axis across the mesh (1-D row-block layout)."""
        return NamedSharding(self.mesh, P(self.axis))

    @property
    def replicated_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def spec(self, *axes) -> P:
        return P(*axes)

    # ---- padded row-block layout -------------------------------------------
    # Internal layout is uniform: every device owns exactly ``local_size(n)``
    # rows, the global arrays padded with zeros to ``padded_size(n)``. User
    # visible (possibly uneven, PETSc-style) ownership ranges are maintained
    # by the callers (see parallel.partition / the facade).
    def local_size(self, n: int) -> int:
        return -(-n // self.size)

    def padded_size(self, n: int) -> int:
        return self.local_size(n) * self.size

    def pad_rows(self, arr: np.ndarray, n: int | None = None) -> np.ndarray:
        """Zero-pad the leading axis of a host array to ``padded_size``."""
        n = arr.shape[0] if n is None else n
        n_pad = self.padded_size(n)
        if arr.shape[0] == n_pad:
            return arr
        pad = [(0, n_pad - arr.shape[0])] + [(0, 0)] * (arr.ndim - 1)
        return np.pad(arr, pad)

    @property
    def multiprocess(self) -> bool:
        """True when the mesh spans several controller processes (DCN mode:
        ``jax.distributed.initialize`` ran and devices belong to more than
        one host — the reference's multi-node ``mpirun`` analog)."""
        return jax.process_count() > 1

    def _put(self, arr, sharding) -> jax.Array:
        """SPMD data placement: every process holds the same host array (the
        reference's replicated-driver model); single-process uses one
        ``device_put``, multi-process builds the global array from the
        per-process addressable pieces."""
        _faults.check("comm.put")     # injectable placement failure
        _faults.check_lost(self.device_ids)   # mesh holds a LOST device?
        if not self.multiprocess:
            # may_alias=False: CPU device_put is otherwise ZERO-COPY — the
            # device array aliases the caller's numpy memory (sharded
            # placement aliases interior SLICES), so mutating the source
            # array after placement would silently change device data.
            # Owned copies match TPU put semantics (host->HBM always
            # copies).
            return jax.device_put(arr, sharding, may_alias=False)
        return jax.make_array_from_callback(
            arr.shape, sharding, lambda idx: arr[idx])

    def put_rows(self, arr, dtype=None) -> jax.Array:
        """Host array -> device array sharded on the leading (row) axis.

        This is the TPU-native replacement for the reference's hand-written
        scatter protocol (pickled lengths + 4 buffered ``Send``s,
        ``test.py:101-106``): one ``device_put`` with a ``NamedSharding`` and
        the runtime moves each block to its device (over PCIe/ICI; across
        hosts each process places only its addressable shards).
        """
        arr = np.asarray(arr, dtype=dtype)
        arr = self.pad_rows(arr)
        return self._put(arr, self.row_sharding)

    def put_rows_many(self, arrs) -> list:
        """Batch variant of :meth:`put_rows`: ONE placement call for
        several (already dtype-final) row-sharded arrays.

        Sequential per-array ``device_put``s pay the runtime's fixed
        dispatch cost once EACH (three placements — ELL cols, ELL vals,
        DIA vals — per assembled matrix). A single ``jax.device_put``
        over the list lets the runtime pipeline one transfer.
        """
        host = [self.pad_rows(np.asarray(a)) for a in arrs]
        if not self.multiprocess:
            # one placement call -> ONE 'comm.put' fault check (the
            # multiprocess path checks inside _put per array — no extra
            # check here, or injected schedules would double-count)
            _faults.check("comm.put")
            _faults.check_lost(self.device_ids)
            # owned buffers, same reason as _put
            return jax.device_put(host, self.row_sharding, may_alias=False)
        return [self._put(a, self.row_sharding) for a in host]

    def put_axis0(self, arr, dtype=None) -> jax.Array:
        """Axis-0 sharding WITHOUT row padding (pre-shaped block stacks)."""
        return self._put(np.asarray(arr, dtype=dtype), self.row_sharding)

    def put_replicated(self, arr, dtype=None) -> jax.Array:
        """Host array -> replicated device array (the analog of ``bcast``)."""
        return self._put(np.asarray(arr, dtype=dtype),
                         self.replicated_sharding)

    def put_spec(self, arr, spec: P, dtype=None) -> jax.Array:
        """Host array -> device array with an arbitrary PartitionSpec."""
        return self._put(np.asarray(arr, dtype=dtype),
                         NamedSharding(self.mesh, spec))

    def host_fetch(self, x) -> np.ndarray:
        """Device array -> full host copy on EVERY process (the
        counts-correct ``Gatherv``+``bcast``). Single-process is one D2H
        copy; multi-process gathers the remote shards over DCN."""
        if not self.multiprocess or getattr(x, "is_fully_addressable", True):
            out = np.asarray(x)
        else:
            from jax.experimental import multihost_utils
            out = np.asarray(multihost_utils.process_allgather(x, tiled=True))
        fault = _faults.triggered("comm.fetch")
        if fault is not None:
            if fault.kind == "unavailable":
                raise fault.error()
            out = out.copy()
            if fault.kind == "drop":      # a lost gather contribution
                out[...] = 0
            elif out.size:                # 'corrupt': poison one element
                flat = out.reshape(-1)
                flat[0] = (np.nan if np.issubdtype(out.dtype, np.inexact)
                           else ~flat[0])
        return out

    # ---- collective helpers (usable INSIDE shard_map) ----------------------
    def psum(self, x):
        """Sum across the mesh — the analog of ``MPI_Allreduce(SUM)``.
        Injectable via the ``comm.psum`` fault point (:func:`faulted_psum`).
        """
        return faulted_psum(x, self.axis)

    def pmax(self, x):
        return lax.pmax(x, self.axis)

    def all_gather(self, x, axis: int = 0):
        """Concatenate shards — the general VecScatter replacement."""
        return lax.all_gather(x, self.axis, axis=axis, tiled=True)

    def shift(self, x, step: int = 1):
        """Ring ``ppermute`` — neighbor/halo exchange for stencil SpMV."""
        n = self.size
        perm = [(i, (i + step) % n) for i in range(n)]
        return lax.ppermute(x, self.axis, perm=perm)

    def device_index(self):
        """This shard's index — the in-SPMD analog of ``comm.Get_rank()``."""
        return lax.axis_index(self.axis)

    # ---- SPMD program construction -----------------------------------------
    def shard_map(self, fn, in_specs, out_specs, check_vma: bool = False):
        """Wrap ``fn`` (written over *local* shards) as an SPMD program."""
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=check_vma)


def full_vector_local_apply(fn, comm: DeviceComm, n: int):
    """Lift ``y = fn(x)`` on the full global vector to a shard-local apply.

    Returns ``apply(x_local) -> y_local`` for use inside shard_map bodies:
    all-gathers the sharded vector, applies ``fn`` replicated per device on
    the unpadded length-``n`` view, and hands back this device's row block.
    Shared by shell operators (core.shell.ShellMat) and PCSHELL.
    """
    axis = comm.axis
    lsize = comm.local_size(n)
    n_pad = lsize * comm.size

    def apply(x_local):
        x_full = lax.all_gather(x_local, axis, tiled=True)
        y = fn(x_full[:n] if n_pad != n else x_full)
        ypad = jnp.pad(y, (0, n_pad - n)) if n_pad != n else y
        i = lax.axis_index(axis)
        return lax.dynamic_slice_in_dim(ypad, i * lsize, lsize)

    return apply


def init_multihost(coordinator_address: str, num_processes: int,
                   process_id: int, **kw) -> DeviceComm:
    """Join a multi-controller job and return the global communicator.

    The DCN analog of launching under ``mpirun -n N`` across nodes
    (reference L1, SURVEY.md §5.8): every controller process calls this with
    the same coordinator address; afterwards ``jax.devices()`` spans all
    hosts and the returned :class:`DeviceComm` is the global 1-D mesh.
    Collectives inside compiled solver programs ride ICI within a host/pod
    and DCN across — placement is unchanged framework code either way.
    """
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id, **kw)
    comm = DeviceComm()
    set_default_comm(comm)
    return comm


_default_comm: DeviceComm | None = None


def get_default_comm() -> DeviceComm:
    """Process-wide default communicator (all visible devices, 1-D mesh)."""
    global _default_comm
    if _default_comm is None:
        _default_comm = DeviceComm()
    return _default_comm


def set_default_comm(comm: DeviceComm | None):
    global _default_comm
    _default_comm = comm


def as_comm(comm) -> DeviceComm:
    """Coerce ``None`` / a Mesh / a DeviceComm into a DeviceComm."""
    if comm is None:
        return get_default_comm()
    if isinstance(comm, DeviceComm):
        return comm
    if isinstance(comm, Mesh):
        return DeviceComm(mesh=comm, axis=comm.axis_names[0])
    # Facade communicator objects (compat.mpi4py) carry a DeviceComm.
    dc = getattr(comm, "device_comm", None)
    if dc is not None:
        return dc if isinstance(dc, DeviceComm) else as_comm(dc)
    raise TypeError(f"cannot interpret {comm!r} as a DeviceComm")
