"""Measured-latency reduction-plan auto-selection (``-ksp_reduction_auto``).

The repo now ships THREE reduction plans for the CG family — classic
(3 psum sites/iteration), pipelined (1 site, overlapped), and s-step
(1 site per s iterations at ~2x the operator applies) — and which one is
fastest is a property of the MESH, not the operator: on a single-host
CPU mesh a psum is a ~µs thread rendezvous and classic CG wins; through
a ~100 µs-per-reduction interconnect the 1-site plans win by the latency
they stop paying ("A highly scalable approach to solving linear systems
using two-stage multisplitting" frames exactly this ranking-by-
communication-cost). This module measures instead of guessing:

* :func:`measure_psum_latency_us` — the chained-psum probe (one program
  running N dependent scalar psums): the per-reduce-site latency each
  removed site buys back.
* :func:`probe_psum_latency_us` — the same probe behind an on-disk cache
  keyed by ``host_machine_fingerprint()`` + mesh topology (the utils/aot
  discipline: atomic writes, silent fallback), so auto-select does not
  re-pay the probe per process; ``-ksp_reduction_probe_refresh`` kills
  the cache.
* :func:`measure_apply_latency_us` — a chained operator+PC apply program
  timing one A+M application (halo traffic included) on the actual
  operands.
* :func:`select_reduction_plan` — ranks {cg, pipecg, sstep s∈{2,4,8}}
  under the additive model ``cost = applies·apply_us + sites·psum_us``
  and returns the winner with the full ranking attached. The model is
  deliberately conservative: it omits the per-plan bookkeeping overhead
  (pipecg's extra AXPY recurrences, sstep's Gram/combine arithmetic —
  measured at 10-20% of an iteration on the CPU mesh), so a plan must
  beat classic CG by ``margin`` (default 25% of the modeled cost) to
  displace it — on low-latency meshes auto-select therefore honestly
  keeps classic CG.
"""

from __future__ import annotations

import hashlib
import json
import os
import tempfile
import time
from dataclasses import dataclass, field

import numpy as np

#: candidate reduction plans: ("cg", None), ("pipecg", None), ("sstep", s)
DEFAULT_CANDIDATES = (("cg", None), ("pipecg", None),
                      ("sstep", 2), ("sstep", 4), ("sstep", 8))

#: modeled applies/iteration and psum sites/iteration per plan family.
#: cg: the general 3-phase schedule; pipecg: one fused site (overlap not
#: credited); sstep: the two-basis monomial CA-CG's (2s-1)/s applies and
#: 1/s sites. The constants mirror KSP._REDUCE_SITES / the collective-
#: volume gates — pinned against them in tests/test_sstep.py.
def _plan_model(ksp_type: str, s):
    if ksp_type == "cg":
        return 1.0, 3.0
    if ksp_type == "pipecg":
        return 1.0, 1.0
    if ksp_type == "sstep":
        s = int(s)
        return (2.0 * s - 1.0) / s, 1.0 / s
    raise ValueError(f"no reduction-plan model for KSP {ksp_type!r}")


def measure_psum_latency_us(comm, chain: int = 256) -> float:
    """Measured per-reduce-site latency of the mesh: one program running
    ``chain`` DEPENDENT scalar psums (each divides by the mesh size, so
    the value is preserved and the chain cannot be collapsed), timed
    best-of-3. This is the latency each removed reduce site saves per
    iteration — the quantity the 1-site reduction plans are buying back.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    axis = comm.axis
    ndev = comm.size

    def local(v):
        sm = jnp.sum(v)

        def body(_i, a):
            return lax.psum(a, axis) / ndev

        return lax.fori_loop(0, chain, body, sm)

    prog = jax.jit(comm.shard_map(local, (P(axis),), P()))
    v = comm.put_rows(np.ones(8 * ndev))
    jax.block_until_ready(prog(v))          # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(prog(v))
        best = min(best, time.perf_counter() - t0)
    return best / chain * 1e6


def _probe_dir() -> str:
    from ..utils import aot
    return os.path.join(os.path.dirname(aot.cache_dir()), "probe")


def _probe_path(comm) -> str:
    from ..utils import aot
    d0 = comm.devices[0]
    payload = repr((aot.host_machine_fingerprint(), len(comm.devices),
                    d0.platform, getattr(d0, "device_kind", ""),
                    comm.axis))
    digest = hashlib.sha256(payload.encode()).hexdigest()[:24]
    return os.path.join(_probe_dir(), f"psum_{digest}.json")


def probe_psum_latency_us(comm, chain: int = 256,
                          refresh: bool = False) -> tuple:
    """The psum-latency probe behind the on-disk cache: returns
    ``(psum_us, cached)``. Cache key = host machine fingerprint + mesh
    topology (a different machine or mesh shape simply misses); writes
    are atomic (tmp + ``os.replace``), every read/write failure degrades
    silently to a fresh measurement; ``refresh`` re-measures and
    overwrites (the ``-ksp_reduction_probe_refresh`` kill switch)."""
    path = _probe_path(comm)
    if not refresh:
        try:
            with open(path, encoding="utf-8") as fh:
                blob = json.load(fh)
            if blob.get("chain") == int(chain):
                return float(blob["psum_us"]), True
        # tpslint: disable=TPS005 — best-effort cache read: a corrupt or
        # stale blob must fall back to measuring, whatever it raises
        except Exception:
            pass
    psum_us = measure_psum_latency_us(comm, chain=chain)
    try:
        os.makedirs(_probe_dir(), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=_probe_dir(), suffix=".tmp")
        with os.fdopen(fd, "w", encoding="utf-8") as fh:
            json.dump({"psum_us": psum_us, "chain": int(chain),
                       "devices": int(comm.size)}, fh)
        os.replace(tmp, path)       # atomic publish (checkpoint.py rule)
    except OSError:
        pass
    return psum_us, False


def measure_apply_latency_us(comm, operator, pc, chain: int = 16) -> float:
    """Measured wall of ONE operator+PC application (halo/gather traffic
    included) on the actual operands: a chained-apply program (each
    iterate scaled by 0.5 so magnitudes stay bounded), best-of-3.
    Per-operator, deliberately NOT disk-cached — apply cost depends on
    the operand geometry, unlike the mesh's psum latency."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    axis = comm.axis
    n = operator.shape[0]
    pc.set_up(pc._mat or operator)      # idempotent (keyed on mat state)
    spmv = operator.local_spmv(comm)
    pc_apply = pc.local_apply(comm, n)

    def local(op_arrays, pc_arrays, v):
        def body(_i, u):
            return pc_apply(pc_arrays, spmv(op_arrays, u)) * 0.5

        return lax.fori_loop(0, chain, body, v)

    prog = jax.jit(comm.shard_map(
        local, (operator.op_specs(axis), pc.in_specs(axis), P(axis)),
        P(axis)))
    v = comm.put_rows(np.ones(n, dtype=np.dtype(operator.dtype)))
    jax.block_until_ready(prog(operator.device_arrays(),
                               pc.device_arrays(), v))   # compile
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        jax.block_until_ready(prog(operator.device_arrays(),
                                   pc.device_arrays(), v))
        best = min(best, time.perf_counter() - t0)
    return best / chain * 1e6


def rank_reduction_plans(psum_us: float, apply_us: float,
                         candidates=DEFAULT_CANDIDATES) -> list:
    """Rank the candidate plans under the additive per-iteration model
    ``cost_us = applies·apply_us + sites·psum_us`` — cheapest first.
    Returns one dict per candidate with the model inputs spelled out so
    benches/reports can publish the ranking verbatim."""
    ranked = []
    for ksp_type, s in candidates:
        applies, sites = _plan_model(ksp_type, s)
        ranked.append({
            "ksp_type": ksp_type, "s": int(s) if s else 0,
            "applies_per_iter": applies, "sites_per_iter": sites,
            "model_cost_us": applies * apply_us + sites * psum_us,
        })
    ranked.sort(key=lambda r: r["model_cost_us"])
    return ranked


@dataclass
class SelectionReport:
    """What :func:`select_reduction_plan` decided and WHY — kept on the
    KSP as ``_reduction_report``, so a caller can read the measured
    latencies the choice rests on."""
    ksp_type: str
    s: int
    psum_us: float
    apply_us: float
    probe_cached: bool
    margin: float
    model: str = "additive: applies*apply_us + sites*psum_us"
    ranking: list = field(default_factory=list)

    def as_dict(self) -> dict:
        return {"choice": self.ksp_type, "s": self.s,
                "psum_us": self.psum_us, "apply_us": self.apply_us,
                "probe_cached": self.probe_cached, "margin": self.margin,
                "model": self.model, "ranking": self.ranking}


def select_reduction_plan(comm, operator, pc, *,
                          candidates=DEFAULT_CANDIDATES,
                          refresh: bool = False,
                          margin: float = 0.25) -> SelectionReport:
    """Pick the reduction plan for (mesh, operator, pc) from MEASURED
    latencies. A non-classic plan must beat classic CG's modeled cost by
    ``margin`` (fractional) to displace it: the additive model omits the
    per-plan bookkeeping overhead (pipecg's extra recurrences, sstep's
    Gram arithmetic), so marginal modeled wins on low-latency meshes are
    noise — classic CG is kept and the report says why."""
    from ..telemetry.metrics import registry
    psum_us, cached = probe_psum_latency_us(comm, refresh=refresh)
    registry.gauge("autoselect.psum_latency_us").set(psum_us)
    apply_us = measure_apply_latency_us(comm, operator, pc)
    ranking = rank_reduction_plans(psum_us, apply_us, candidates)
    cg_cost = next(r["model_cost_us"] for r in ranking
                   if r["ksp_type"] == "cg")
    best = ranking[0]
    if (best["ksp_type"] != "cg"
            and best["model_cost_us"] > (1.0 - margin) * cg_cost):
        best = {"ksp_type": "cg", "s": 0}
    return SelectionReport(ksp_type=best["ksp_type"],
                           s=int(best.get("s", 0) or 0),
                           psum_us=float(psum_us),
                           apply_us=float(apply_us),
                           probe_cached=bool(cached), margin=margin,
                           ranking=ranking)
