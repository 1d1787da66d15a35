"""AOT program export/deserialize — fresh-process cold-start cutter.

A fresh process (the reference's test.py runs one solve per process)
pays Python tracing and MLIR lowering for every program it builds: the
single-RHS and batched KSP solve programs (solvers/krylov.py), the fused
megasolve programs and the fixed-shape EPS programs. The XLA
compilation cache only helps a warm *machine*; it serves the backend
compile, not the trace and lower in front of it.

``jax.export`` serializes the traced/lowered StableHLO (with its sharding
annotations and its Pallas ``tpu_custom_call`` kernels) once; a later
process deserializes the blob and jits the restored call, skipping
Python tracing and lowering entirely. Backend compilation of the
restored StableHLO still runs, and is served by the persistent XLA
compilation cache where configured — the two caches compose: the
exporting process runs the same deserialized module, so it fills the
compile cache with the entry later processes look up.

Cache layout: one ``<sha256>.jaxexport`` blob per (program kind, program
key, mesh topology, package source, jax version) under
``TPU_SOLVE_AOT_DIR`` (default ``<checkout>/.tpu_solve_cache/aot``, a
fixed path like the XLA cache's). Writes are atomic (tmp +
``os.replace``, the checkpoint.py discipline). Every load/export failure
falls back silently to the traced program — AOT is an optimization,
never a correctness dependency. ``TPU_SOLVE_AOT=0`` disables the whole
path.
"""

from __future__ import annotations

import contextlib
import functools
import hashlib
import os
import tempfile

import jax
import jax.export  # noqa: F401 — not re-exported from the bare jax module


PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# environment knobs a program's trace reads (ops/pallas_stencil.py's DMA
# pipeline depth): their values are part of every blob key
_TRACE_ENV = ("TPU_SOLVE_STENCIL_NBUF",)


@functools.lru_cache(maxsize=None)
def package_fingerprint(root: str = PACKAGE_DIR) -> str:
    """sha256 of every ``.py`` file under the package, by relative path
    and bytes, computed once per process: part of every blob key. A
    program traces code from many modules (the Krylov loop, the PC apply,
    the operator's SpMV, the Pallas kernels), so an edit anywhere in the
    package misses the cache and never serves a stale program. An
    unreadable file (a frozen app) hashes its path instead."""
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for f in sorted(filenames):
            if not f.endswith(".py"):
                continue
            path = os.path.join(dirpath, f)
            h.update(os.path.relpath(path, root).encode() + b"\0")
            try:
                with open(path, "rb") as fh:
                    h.update(fh.read())
            except OSError:
                pass
    return h.hexdigest()


def operand_shapes(*trees) -> tuple:
    """Shape/dtype of every array leaf of ``trees``: a part of a blob key
    where the in-process key leaves operand geometry to jit. An exported
    program is specialized to its operands' shapes (an ELL width K, a
    bjacobi block size); two operators that share a program key but not
    a geometry would otherwise take turns overwriting one blob."""
    return tuple((tuple(a.shape), str(a.dtype))
                 for a in jax.tree_util.tree_leaves(trees))


def aot_enabled() -> bool:
    return os.environ.get("TPU_SOLVE_AOT", "1") not in ("0", "false")


def cache_dir() -> str:
    d = os.environ.get("TPU_SOLVE_AOT_DIR")
    if not d:
        from .. import CHECKOUT_DIR
        d = os.path.join(CHECKOUT_DIR, ".tpu_solve_cache", "aot")
    return d


def _mesh_fingerprint(comm) -> tuple:
    """The part of the key that pins device topology and runtime: an
    exported program embeds its mesh shape and sharding, so a blob is only
    valid on an identical mesh (count + platform + generation), and its
    Pallas kernels are lowered for this runtime (the platform version:
    the TPU runtime's build)."""
    d0 = comm.devices[0]
    client = getattr(d0, "client", None)
    return (len(comm.devices), d0.platform,
            getattr(d0, "device_kind", ""), comm.axis,
            getattr(client, "platform_version", ""))


@functools.lru_cache(maxsize=1)
def host_machine_fingerprint() -> str:
    """CPU-feature fingerprint of THIS host, keyed into every CPU-platform
    blob digest.

    XLA:CPU AOT artifacts embed the COMPILE machine's ISA feature set; a
    blob produced on one machine and executed on another with different
    features makes ``cpu_aot_loader`` spam per-load "machine features
    ... not supported on the host machine ... could lead to SIGILL"
    warnings (the MULTICHIP_r05 tail) and genuinely risks illegal
    instructions. Keying the digest on the host's feature flags means a
    different machine simply MISSES the cache and falls back to fresh
    tracing — a mismatched blob is never even opened. Linux exposes the
    flags in ``/proc/cpuinfo``; elsewhere the platform string is the
    best (coarser) stand-in."""
    import platform
    parts = [platform.machine()]
    try:
        with open("/proc/cpuinfo", encoding="utf-8", errors="replace") as fh:
            for line in fh:
                # x86 spells it "flags", arm64 "Features"
                if line.startswith(("flags", "Features")):
                    parts.append(" ".join(sorted(
                        line.split(":", 1)[1].split())))
                    break
    except OSError:
        parts.append(platform.platform())
    return hashlib.sha256("|".join(parts).encode()).hexdigest()[:16]


def _digest(kind: str, comm, key_parts, code: str = "") -> str:
    # CPU-platform programs additionally pin the host machine's feature
    # set (host_machine_fingerprint) — accelerator blobs are StableHLO
    # recompiled for the local device generation, which the
    # device_kind in _mesh_fingerprint already covers
    host = (host_machine_fingerprint()
            if comm.devices[0].platform == "cpu" else "")
    env = tuple(os.environ.get(k) for k in _TRACE_ENV)
    payload = repr((kind, _mesh_fingerprint(comm), host, key_parts, code,
                    env, jax.__version__,
                    bool(jax.config.jax_enable_x64)))
    return hashlib.sha256(payload.encode()).hexdigest()


def _deserialize(blob, donate_argnums=()):
    """A serialized export as a jitted callable, or None."""
    try:
        exported = jax.export.deserialize(bytearray(blob))
        # donation is a property of the jit wrapper, not the serialized
        # StableHLO — re-apply it so a loaded program keeps the traced
        # program's zero-allocation aliasing (krylov donated solves)
        return jax.jit(exported.call, donate_argnums=donate_argnums)
    # tpslint: disable=TPS005 — best-effort load: a stale/corrupt blob or
    # a jax ABI change must fall back to tracing, whatever it raises
    except Exception:
        return None


def _load(path: str, donate_argnums=()):
    """Deserialize a blob file into a jitted callable, or None."""
    try:
        with open(path, "rb") as fh:
            blob = fh.read()
    except OSError:
        return None
    return _deserialize(blob, donate_argnums)


def _store(path: str, exported_bytes: bytes):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                               suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as fh:
            fh.write(exported_bytes)
        os.replace(tmp, path)       # atomic publish (checkpoint.py rule)
    except OSError:
        try:
            os.unlink(tmp)
        except OSError:
            pass


@contextlib.contextmanager
def _this_runtime():
    """Export for this process's runtime only. By default ``jax.export``
    lowers for older runtimes too (forward compatibility), and Mosaic
    then lowers some casts differently: a Pallas kernel with a 64-bit
    constant under x64 (the stencil kernels' pads) recurses without end.
    A blob is only ever loaded by the same jax on the same runtime (both
    are in its key), so the exported kernels are lowered as a jit lowers
    them, and run the same code."""
    flag = "jax_export_ignore_forward_compatibility"
    was = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, was)


class Program:
    """A jitted program served through the export cache.

    ``aot`` says how this process got it: ``"hit"`` (deserialized from
    a blob: nothing traced), ``"miss"`` (no usable blob: the first call
    exports one) or ``"fallback"`` (a loaded program rejected a call's
    operands, so the traced jit serves from then on). Every other
    attribute (``lower``, ``trace``, ...) is the traced jit's,
    ``traced``."""

    def __init__(self, traced, path: str, donate_argnums, loaded):
        self.traced = traced
        self.aot = "miss" if loaded is None else "hit"
        self._path = path
        self._donate = tuple(donate_argnums)
        self._call = loaded

    def __getattr__(self, name):
        if name == "traced":        # not set yet: no recursion
            raise AttributeError(name)
        return getattr(self.traced, name)

    def __call__(self, *args):
        if self._call is None:
            self._call = self._export(args)
        elif self._call is not self.traced:
            try:
                return self._call(*args)
            except (ValueError, TypeError):
                # operands of another geometry than the blob's: the key
                # failed to pin it (an in-process key leaves shapes to
                # jit). AOT is never a correctness dependency: trace, as
                # jit would for new shapes, and leave the blob to its key
                self.aot = "fallback"
                self._call = self.traced
        return self._call(*args)

    def _export(self, args):
        """Trace and lower once, from the call's own operands: store the
        blob and run its deserialized form, the module a later process
        loads, so that process finds the backend compile in the
        persistent cache. The traced jit where export fails."""
        try:
            with _this_runtime():
                blob = jax.export.export(self.traced)(*args).serialize()
        # tpslint: disable=TPS005 — best-effort export: closures the
        # exporter rejects (custom calls, callbacks) keep the traced
        # program; only the cold-start saving is lost
        except Exception:
            return self.traced
        _store(self._path, blob)
        loaded = _deserialize(blob, self._donate)
        return self.traced if loaded is None else loaded


def status(prog) -> str:
    """``prog``'s ``aot`` state; ``"off"`` for a program not served
    through the export cache."""
    return getattr(prog, "aot", "off")


def _process_local(parts) -> bool:
    """Whether a program key names a shell callback, ``("shell", uid)``
    (PC) or ``("shellmat", uid)`` (operator), at any depth: uids restart
    in every process, so a blob keyed on one could be served to another
    function."""
    return any(isinstance(p, tuple)
               and ((p[:1] in (("shell",), ("shellmat",)))
                    or _process_local(p))
               for p in parts)


def wrap(kind: str, comm, key_parts, prog, donate_argnums=()):
    """Serve a jitted ``prog`` through the export cache.

    On a hit the deserialized program replaces ``prog`` outright — zero
    tracing in this process. On a miss the first call exports the
    program from its own concrete arguments (so no shape bookkeeping is
    needed), once, and later processes hit. ``key_parts`` must pin
    everything the trace depends on (ncv, operator key, ...); the mesh
    topology, jax version, x64 mode, the trace-time environment knobs
    and the package's source (:func:`package_fingerprint`) are appended
    automatically. ``donate_argnums`` (when ``prog`` was jitted with
    donation) is re-applied to the deserialized call, so loaded programs
    keep the traced program's buffer aliasing. Returns ``prog`` itself
    when ``TPU_SOLVE_AOT=0`` or ``key_parts`` names a shell callback.
    """
    if not aot_enabled() or _process_local(key_parts):
        return prog
    path = os.path.join(cache_dir(), _digest(kind, comm, key_parts,
                                             package_fingerprint())
                        + ".jaxexport")
    # undonated programs keep the 1-arg call shape (_load(path)) so
    # test doubles that stub _load stay signature-compatible
    loaded = None
    if os.path.exists(path):
        loaded = (_load(path, donate_argnums) if donate_argnums
                  else _load(path))
    return Program(prog, path, donate_argnums, loaded)
