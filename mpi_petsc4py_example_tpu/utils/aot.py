"""AOT program export/deserialize — fresh-process cold-start cutter.

A fresh driver process (the test2.py flow) spends its eigensolve phase
re-tracing and compile-cache-loading the two fixed-shape EPS programs
(seed+facto and compress+facto). The XLA compilation
cache only helps a warm *machine*; a fresh process still pays the full
Python trace + lowering for each program.

``jax.export`` serializes the traced/lowered StableHLO (with its sharding
annotations) once; a later process deserializes the blob and jits the
restored call, skipping Python tracing and lowering entirely. Backend
compilation of the restored StableHLO still runs, and is served by the
persistent XLA compilation cache where configured — the two caches
compose.

Cache layout: one ``<sha256>.jaxexport`` blob per (program kind, program
key, mesh topology, jax version) under ``TPU_SOLVE_AOT_DIR`` (default
``<checkout>/.tpu_solve_cache/aot``, a fixed path like the XLA cache's). Writes are atomic (tmp + ``os.replace``, the
checkpoint.py discipline). Every load/export failure falls back silently
to the traced program — AOT is an optimization, never a correctness
dependency. ``TPU_SOLVE_AOT=0`` disables the whole path.
"""

from __future__ import annotations

import functools
import hashlib
import os
import tempfile

import jax
import jax.export  # noqa: F401 — not re-exported from the bare jax module


@functools.lru_cache(maxsize=None)
def source_fingerprint(module_file: str, *extra_files: str) -> str:
    """sha256 of a builder module's source — part of every blob key, so a
    code change (new factorization math, changed specs) can never be
    served a stale pre-change program. ``extra_files`` are hashed in for
    builders whose kernel bodies live in OTHER modules (krylov.py's
    loops are assembled from cg_plans.py plans: an edit there changes
    the traced program without touching the builder file). Unreadable
    source (frozen app) degrades to hashing the module path: correctness
    then rests on the jax-version key alone, which still covers the
    common upgrade hazard."""
    h = hashlib.sha256()
    for f in (module_file,) + extra_files:
        try:
            with open(f, "rb") as fh:
                h.update(fh.read())
        except OSError:
            h.update(f.encode())
    return h.hexdigest()


def aot_enabled() -> bool:
    return os.environ.get("TPU_SOLVE_AOT", "1") not in ("0", "false")


def cache_dir() -> str:
    d = os.environ.get("TPU_SOLVE_AOT_DIR")
    if not d:
        from .. import CHECKOUT_DIR
        d = os.path.join(CHECKOUT_DIR, ".tpu_solve_cache", "aot")
    return d


def _mesh_fingerprint(comm) -> tuple:
    """The part of the key that pins device topology: an exported program
    embeds its mesh shape and sharding, so a blob is only valid on an
    identical mesh (count + platform + generation)."""
    d0 = comm.devices[0]
    return (len(comm.devices), d0.platform,
            getattr(d0, "device_kind", ""), comm.axis)


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
    payload = repr((kind, _mesh_fingerprint(comm), host, key_parts, code,
                    jax.__version__,
                    bool(jax.config.jax_enable_x64)))
    return hashlib.sha256(payload.encode()).hexdigest()


def _load(path: str, donate_argnums=()):
    """Deserialize a blob into a jitted callable, or None."""
    try:
        with open(path, "rb") as fh:
            blob = fh.read()
        exported = jax.export.deserialize(bytearray(blob))
        # donation is a property of the jit wrapper, not the serialized
        # StableHLO — re-apply it so a loaded program keeps the traced
        # program's zero-allocation aliasing (krylov donated solves)
        return jax.jit(exported.call, donate_argnums=donate_argnums)
    # tpslint: disable=TPS005 — best-effort load: a stale/corrupt blob or
    # a jax ABI change must fall back to tracing, whatever it raises
    except Exception:
        return None


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


def wrap(kind: str, comm, key_parts, prog, code: str = "",
         donate_argnums=()):
    """AOT-cache a compiled program factory's jitted ``prog``.

    On a cache hit the deserialized program replaces ``prog`` outright —
    zero tracing in this process. On a miss, the first *successful* call
    additionally exports + serializes the program (using the call's own
    concrete arguments, so no shape bookkeeping is needed) and later
    processes hit. ``key_parts`` must pin everything the trace depends on
    (ncv, operator key, ...); the mesh topology, jax version, x64 mode,
    and the builder's ``code`` fingerprint (:func:`source_fingerprint`)
    are appended automatically. ``donate_argnums`` (when the wrapped
    ``prog`` was jitted with donation) is re-applied to the deserialized
    call, so loaded programs keep the traced program's buffer aliasing.
    """
    if not aot_enabled():
        return prog
    path = os.path.join(cache_dir(), _digest(kind, comm, key_parts, code)
                        + ".jaxexport")
    # undonated programs keep the 1-arg call shape (_load(path)) so
    # test doubles that stub _load stay signature-compatible
    loaded = None
    if os.path.exists(path):
        loaded = (_load(path, donate_argnums) if donate_argnums
                  else _load(path))

    exported_once = [False]

    def call_traced_and_export(*args):
        out = prog(*args)
        if not exported_once[0]:
            exported_once[0] = True
            try:
                blob = jax.export.export(prog)(*args).serialize()
                _store(path, blob)
            # tpslint: disable=TPS005 — best-effort export: closures the
            # exporter rejects (custom calls, callbacks) keep the traced
            # program; only the cold-start saving is lost
            except Exception:
                pass
        return out

    if loaded is None:
        return call_traced_and_export

    def call_loaded(*args):
        try:
            return loaded(*args)
        except (ValueError, TypeError):
            # operand-shape mismatch: the blob was exported for a
            # different operand geometry the caller's key_parts failed to
            # pin (e.g. an operator attribute outside program_key). AOT
            # must never be a correctness dependency — fall back to the
            # traced program and OVERWRITE the stale blob with this
            # geometry's export.
            return call_traced_and_export(*args)

    return call_loaded
