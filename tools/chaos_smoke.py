#!/usr/bin/env python
"""Chaos smoke: silent-corruption AND device-eviction drills for CI.

Silent-corruption drills (ISSUE 5 satellite) run a CG solve under
silent-corruption fault specs and assert the full detection ->
rollback -> recovery -> verification chain:

* a detector fired (ABFT checksum / drift gate / sentinel — the
  recovery trail carries its name);
* the recovered answer's fp64 TRUE relative residual meets rtol;
* the iterate matches the manufactured solution.

Device-eviction drills (``--evict``, ISSUE 8 satellite) arm a PERMANENT
``device.lost`` fault mid-solve and mid-serving-load and assert the
elastic escalation (resilience/elastic.py):

* the solve/serving session recovers onto a STRICTLY SMALLER mesh
  (a ``mesh_shrink`` recovery event with old > new device counts);
* the resumed solve provably continued from the checkpointed iterate,
  not iteration 0 (the shrink event's resumed iteration, and fewer
  remaining iterations than a cold start);
* every pending serving request resolves — a converged fp64-parity
  result, DEADLINE_EXCEEDED, or ServerOverloadedError — never a hung
  future or a dead dispatcher.

Exit status is NONZERO on any failed drill — the CI contract that
neither silent corruption nor hardware loss can silently regress.

Modes:

* ``TPU_SOLVE_FAULTS`` set in the environment: ONE corruption drill
  under exactly that spec (the env-activation route);
* ``--evict``: the two device-eviction drills via ``inject_faults``;
* ``--sstep`` (ISSUE 15): a bitflip armed INSIDE an s-step block (the
  basis-build applies checked by the one stacked Gram psum's ABFT
  partials) must detect -> roll back to the verified carry -> re-enter
  to an fp64-parity answer, and the ill-conditioned-monomial-basis
  drill must restart, exhaust ``-ksp_sstep_max_replacements``, and
  DEMOTE to classic CG (a ``sstep_demote`` RecoveryEvent) while still
  converging;
* ``--fleet`` (ISSUE 13): the loss -> shrink -> heal -> RE-GROW round
  trip — a retry-ladder drill proving the re-grown mesh RESUMES the
  solve past iteration 0, and a mixed-QoS router drill with one
  injected ``device.lost`` AND one ``heal()`` mid-load, exiting nonzero
  unless every future resolves and post-heal capacity returns;
* ``--multisplit`` (ISSUE 17): the asynchronous-tier drills — a sticky
  slow device (``comm.delay`` timing fault) must be absorbed as bounded
  staleness (resyncs fire, the solve converges to strict fp64 parity);
  a mid-solve ``device.lost`` must degrade to ONE stale block and
  re-home it, with every block's published version sequence strictly
  increasing across the loss (survivors provably never revisit
  iteration 0); and an ``exchange.put`` drop/partition must only ever
  cost staleness, never correctness;
* ``--transport`` (ISSUE 20): the multi-host RPC drills — killing the
  owning replica host mid-load must fail every in-flight future over
  to the survivor with the re-homed solve RESUMING past iteration 0
  from the shipped elastic checkpoint; injected duplicate delivery
  (a lost reply forcing a retry, and a doubled request) must execute
  each logical call exactly once — the idempotency cache absorbs the
  duplicate, the future never double-resolves; and a partition struck
  during a live migration must leave a truthful placement (src still
  serves at parity) and, once healed, ``reconcile()`` must converge the
  fleet to a single owner with the orphaned copy unregistered;
* ``--persistent`` (ISSUE 18): the device-resident request-queue
  drills — a silent bitflip armed across a fully-staged persistent
  launch must resolve EVERY slot future with no silently-wrong answer
  (a slot either converges to strict fp64 parity or honestly reports
  non-convergence — the verified-residual exit gate is the detector),
  and a mid-launch ``device.lost`` must resolve every slot through the
  elastic tier (resuming past iteration 0), shrink the server's mesh,
  and REBUILD the resident program on the surviving geometry for
  post-recovery traffic;
* neither: the builtin silent-corruption sweep over every silent fault
  kind at every injectable point (spmv.result / pc.apply / comm.psum).

``--trace-out <path>`` (composable with every mode) arms the telemetry
layer for the run and exports the Chrome/Perfetto trace afterwards,
with the flight-recorder ring dumped next to it (``<path>.flight.json``)
— then VALIDATES both: the trace must be non-empty and schema-clean,
and (under ``--evict``) must contain the retry -> shrink span chain with
the resumed iteration number as a span attribute, the ISSUE-11
acceptance drill. Exit status stays nonzero on any validation miss.
"""

from __future__ import annotations

import os
import sys

# the eviction drills need a multi-device mesh to shrink; force the
# 8-virtual-device CPU host platform (the tests/conftest.py idiom) BEFORE
# any jax import — harmless when real accelerator devices take precedence
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8").strip()

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

RTOL = 1e-10

#: the builtin sweep: every silent kind at every injectable point
#: (at=2 targets the loop apply; times=1 lets the retry re-trace clean)
BUILTIN_SPECS = (
    "spmv.result=bitflip:at=2:times=1",
    "spmv.result=scale:mag=1e-3:at=2:times=1",
    "pc.apply=bitflip:at=2:times=1",
    "pc.apply=scale:mag=1e-2:at=2:times=1",
    "comm.psum=corrupt:times=1:at=3",
)


def drill(label: str, ctx) -> list[str]:
    """One corruption drill; returns a list of failure descriptions."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr

    comm = tps.DeviceComm()
    A = poisson2d_csr(12)
    M = tps.Mat.from_scipy(comm, A)
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type("cg")
    ksp.get_pc().set_type("jacobi")
    ksp.set_tolerances(rtol=RTOL)
    ksp.abft = True
    ksp.residual_replacement = 10
    x_true = np.random.default_rng(0).random(A.shape[0])
    b = A @ x_true
    x, bv = M.get_vecs()
    bv.set_global(b)

    problems: list[str] = []
    with ctx:
        res = tps.resilient_solve(
            ksp, bv, x, tps.RetryPolicy(sleep=lambda _d: None))
    detectors = [e.detector for e in res.recovery_events
                 if e.kind == "fault" and e.detector]
    if not detectors:
        problems.append("corruption went UNDETECTED (no detector event)")
    if not res.converged:
        problems.append(f"recovered solve did not converge: {res}")
    if not any(e.kind == "verify" for e in res.recovery_events):
        problems.append("no post-recovery true-residual verification ran")
    rtrue = (np.linalg.norm(b - A @ x.to_numpy())
             / np.linalg.norm(b))
    if not rtrue <= RTOL * 1.05:
        problems.append(f"true relative residual {rtrue:.3e} misses rtol")
    if not np.allclose(x.to_numpy(), x_true, atol=1e-7):
        problems.append("recovered iterate differs from the manufactured "
                        "solution")
    status = "OK" if not problems else "FAIL"
    print(f"[chaos] {label}: {status} detectors={detectors} "
          f"attempts={res.attempts} true_rres={rtrue:.3e}")
    return [f"{label}: {p}" for p in problems]


def drill_megasolve() -> list[str]:
    """Silent corruption INSIDE the fused whole-solve loop
    (``--megasolve``, ISSUE 12 satellite): with ``-ksp_megasolve`` the
    entire refinement/verification recurrence is ONE compiled program —
    a bitflip armed on the inner CG's operator apply must be detected by
    the nested guarded plan loop's ABFT channel, freeze the fused outer
    recurrence, surface the verified-iterate carry (the rollback
    target), and recover through the resilient ladder to an fp64-parity
    answer — at exactly ONE compiled-program launch per attempt, proven
    from the telemetry dispatch counter (detection -> rollback ->
    re-entry still costs one dispatch each way)."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.utils.profiling import dispatch_counts

    problems: list[str] = []
    comm = tps.DeviceComm()
    A = poisson2d_csr(12)
    M = tps.Mat.from_scipy(comm, A)
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type("cg")
    ksp.get_pc().set_type("jacobi")
    ksp.set_tolerances(rtol=RTOL)
    ksp.megasolve = True
    ksp.abft = True
    ksp.residual_replacement = 10
    x_true = np.random.default_rng(0).random(A.shape[0])
    b = A @ x_true
    x, bv = M.get_vecs()
    bv.set_global(b)
    before = dispatch_counts()
    with tps.inject_faults("spmv.result=bitflip:at=2:times=1"):
        res = tps.resilient_solve(
            ksp, bv, x, tps.RetryPolicy(sleep=lambda _d: None))
    after = dispatch_counts()
    mega = int(after.get("megasolve", 0) - before.get("megasolve", 0))
    other = int(sum(after.values()) - sum(before.values())) - mega
    detectors = [e.detector for e in res.recovery_events
                 if e.kind == "fault" and e.detector]
    if not detectors:
        problems.append("fused-loop corruption went UNDETECTED")
    if not any(e.kind == "rollback" for e in res.recovery_events):
        problems.append("no rollback re-entry in the recovery trail")
    if not any(e.kind == "verify" for e in res.recovery_events):
        problems.append("no post-recovery true-residual verification ran")
    if not res.converged:
        problems.append(f"recovered fused solve did not converge: {res}")
    if mega != res.attempts:
        problems.append(
            f"{mega} fused launches for {res.attempts} attempt(s) — the "
            "one-dispatch-per-attempt contract broke under fire")
    if other != 0:
        problems.append(f"{other} UNFUSED program launch(es) on the "
                        "megasolve path")
    rtrue = (np.linalg.norm(b - A @ x.to_numpy()) / np.linalg.norm(b))
    if not rtrue <= RTOL * 1.05:
        problems.append(f"true relative residual {rtrue:.3e} misses rtol")
    if not np.allclose(x.to_numpy(), x_true, atol=1e-7):
        problems.append("recovered iterate differs from the manufactured "
                        "solution")
    status = "OK" if not problems else "FAIL"
    print(f"[chaos] megasolve: {status} detectors={detectors} "
          f"attempts={res.attempts} fused_launches={mega} "
          f"true_rres={rtrue:.3e}")
    return [f"megasolve: {p}" for p in problems]


def drill_sstep() -> list[str]:
    """Silent corruption INSIDE an s-step block (``--sstep``, ISSUE 15
    satellite): a bitflip armed on a basis-build operator apply must be
    detected by the ABFT partials riding the block's ONE stacked Gram
    psum, roll the iterate back to the VERIFIED carry, and recover
    through the resilient ladder (rollback -> re-entry -> independent
    re-verification) to an fp64-parity answer — the PR-5 chain proven
    inside the communication-avoiding schedule."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr

    problems: list[str] = []
    comm = tps.DeviceComm()
    A = poisson2d_csr(12)
    M = tps.Mat.from_scipy(comm, A)
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type("sstep")
    ksp.sstep_s = 4
    ksp.get_pc().set_type("jacobi")
    ksp.set_tolerances(rtol=RTOL)
    ksp.abft = True
    ksp.residual_replacement = 12
    x_true = np.random.default_rng(0).random(A.shape[0])
    b = A @ x_true
    x, bv = M.get_vecs()
    bv.set_global(b)
    # at=2 lands on the FIRST block's P-chain basis apply (the init
    # residual is spmv site 1) — corruption inside the s-block proper
    with tps.inject_faults("spmv.result=bitflip:at=2:times=1"):
        res = tps.resilient_solve(
            ksp, bv, x, tps.RetryPolicy(sleep=lambda _d: None))
    detectors = [e.detector for e in res.recovery_events
                 if e.kind == "fault" and e.detector]
    if not detectors:
        problems.append("s-block corruption went UNDETECTED")
    if not any(e.kind == "rollback" for e in res.recovery_events):
        problems.append("no rollback to the verified carry in the "
                        "recovery trail")
    if not any(e.kind == "verify" for e in res.recovery_events):
        problems.append("no post-recovery true-residual verification ran")
    if not res.converged:
        problems.append(f"recovered s-step solve did not converge: {res}")
    if any(e.kind == "sstep_demote" for e in res.recovery_events):
        problems.append("healthy-basis drill DEMOTED to classic cg")
    rtrue = (np.linalg.norm(b - A @ x.to_numpy()) / np.linalg.norm(b))
    if not rtrue <= RTOL * 1.05:
        problems.append(f"true relative residual {rtrue:.3e} misses rtol")
    if not np.allclose(x.to_numpy(), x_true, atol=1e-7):
        problems.append("recovered iterate differs from the manufactured "
                        "solution")
    status = "OK" if not problems else "FAIL"
    print(f"[chaos] sstep: {status} detectors={detectors} "
          f"attempts={res.attempts} true_rres={rtrue:.3e}")
    failures = [f"sstep: {p}" for p in problems]

    # ---- the demotion half: an ill-conditioned monomial basis at
    # large s must restart, exhaust -ksp_sstep_max_replacements, and
    # DEMOTE to classic CG with a RecoveryEvent — and still converge
    from mpi_petsc4py_example_tpu.models import tridiag_family
    A2 = tridiag_family(384)
    M2 = tps.Mat.from_scipy(comm, A2)
    b2 = np.asarray(A2 @ np.random.default_rng(5).random(384))
    k2 = tps.KSP().create(comm)
    k2.set_operators(M2)
    k2.set_type("sstep")
    k2.sstep_s = 12
    k2.get_pc().set_type("none")
    k2.set_tolerances(rtol=1e-12, max_it=8000)
    k2.residual_replacement = 24
    k2.sstep_max_replacements = 1
    x2, bv2 = M2.get_vecs()
    bv2.set_global(b2)
    res2 = k2.solve(bv2, x2)
    dem = [e for e in res2.recovery_events if e.kind == "sstep_demote"]
    problems2: list[str] = []
    if not dem:
        problems2.append("ill-conditioned basis never demoted")
    if not res2.converged:
        problems2.append(f"demoted solve did not converge: {res2}")
    r2 = (np.linalg.norm(b2 - A2 @ x2.to_numpy()) / np.linalg.norm(b2))
    if not r2 <= 1e-11:
        problems2.append(f"demoted answer residual {r2:.3e} misses rtol")
    status2 = "OK" if not problems2 else "FAIL"
    print(f"[chaos] sstep-demote: {status2} demotions={len(dem)} "
          f"iters={res2.iterations} true_rres={r2:.3e}")
    return failures + [f"sstep-demote: {p}" for p in problems2]


def drill_evict_solve() -> list[str]:
    """Permanent device loss MID-SOLVE: the elastic escalation must land
    the solve on a strictly smaller mesh, resumed from the checkpointed
    iterate, with the answer at fp64 parity."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.resilience import faults as _faults

    problems: list[str] = []
    comm = tps.DeviceComm()
    if comm.size < 2:
        return ["evict-solve: needs a multi-device mesh "
                f"(got {comm.size} device[s])"]
    A = poisson2d_csr(16)

    def make_session():
        M = tps.Mat.from_scipy(comm, A)
        ksp = tps.KSP().create(comm)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=RTOL)
        x, bv = M.get_vecs()
        return ksp, x, bv

    x_true = np.random.default_rng(0).random(A.shape[0])
    b = A @ x_true
    # cold baseline (same geometry ladder end state is smaller, but the
    # iteration count to beat is the uninterrupted one)
    ksp0, x0, bv0 = make_session()
    bv0.set_global(b)
    cold = ksp0.solve(bv0, x0)

    ksp, x, bv = make_session()
    bv.set_global(b)
    victim = comm.device_ids[-1]
    spec = f"device.lost=unavailable:device={victim}:iter=15"
    try:
        with tps.inject_faults(spec):
            res = tps.resilient_solve(
                ksp, bv, x, tps.RetryPolicy(sleep=lambda _d: None),
                elastic=tps.ElasticPolicy(max_same_mesh_retries=1))
        shrinks = [e for e in res.recovery_events
                   if e.kind == "mesh_shrink"]
        if not shrinks:
            problems.append("no mesh_shrink recovery event")
        elif not shrinks[0].new_devices < shrinks[0].old_devices:
            problems.append(f"mesh did not shrink: {shrinks[0]}")
        elif shrinks[0].iterations <= 0:
            problems.append("resumed from iteration 0, not the "
                            "checkpointed iterate")
        if ksp.comm.size >= comm.size:
            problems.append(f"session still on {ksp.comm.size} devices")
        if not res.converged:
            problems.append(f"recovered solve did not converge: {res}")
        if not res.iterations < cold.iterations:
            problems.append(
                f"resumed solve took {res.iterations} iterations, not "
                f"fewer than the {cold.iterations}-iteration cold start")
        rtrue = (np.linalg.norm(b - A @ x.to_numpy())
                 / np.linalg.norm(b))
        if not rtrue <= RTOL * 1.05:
            problems.append(f"true relative residual {rtrue:.3e} "
                            "misses rtol")
        print(f"[chaos] evict-solve: "
              f"{'OK' if not problems else 'FAIL'} "
              f"{comm.size}->{ksp.comm.size} devices, "
              f"iters {res.iterations} (cold {cold.iterations}), "
              f"true_rres={rtrue:.3e}")
    finally:
        _faults.heal()
    return [f"evict-solve: {p}" for p in problems]


def drill_evict_serving() -> list[str]:
    """Permanent device loss MID-SERVING-LOAD: the server must adopt the
    degraded mesh and EVERY pending future must resolve — a converged
    fp64-parity result, DEADLINE_EXCEEDED, or ServerOverloadedError —
    with the dispatcher alive for post-recovery traffic."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.resilience import faults as _faults
    from mpi_petsc4py_example_tpu.serving import SolveServer

    problems: list[str] = []
    comm = tps.DeviceComm()
    if comm.size < 2:
        return ["evict-serving: needs a multi-device mesh "
                f"(got {comm.size} device[s])"]
    A = poisson2d_csr(12)
    n = A.shape[0]
    rng = np.random.default_rng(8)
    R = 12
    Xt = rng.random((n, R))
    B = np.asarray(A @ Xt)
    victim = comm.device_ids[-1]
    srv = SolveServer(
        comm, window=0.005, max_k=4, max_queue=64, deadline=120.0,
        retry_policy=tps.RetryPolicy(sleep=lambda _d: None),
        autostart=False)
    try:
        srv.register_operator("poisson", A, rtol=RTOL)
        futs = [srv.submit("poisson", B[:, j]) for j in range(R)]
        # the loss fires at the 2nd solve-program boundary: some blocks
        # complete on the full mesh, the rest ride the shrink
        with tps.inject_faults(
                f"device.lost=unavailable:device={victim}:at=2:iter=6"):
            srv.start()
            if not srv.drain(600):
                problems.append("drain timed out — hung future(s)")
        answered = converged = typed = 0
        for j, f in enumerate(futs):
            if not f.done():
                problems.append(f"request {j} future never resolved")
                continue
            answered += 1
            exc = f.exception(0)
            if exc is None:
                r = f.result(0)
                rres = (np.linalg.norm(B[:, j] - A @ r.x)
                        / np.linalg.norm(B[:, j]))
                if not (r.converged and rres <= RTOL * 1.05):
                    problems.append(
                        f"request {j}: reason={r.reason_name} "
                        f"true_rres={rres:.3e} (parity miss)")
                else:
                    converged += 1
            elif isinstance(exc, (tps.DeadlineExceededError,
                                  tps.ServerOverloadedError)):
                typed += 1
            else:
                problems.append(f"request {j}: untyped failure {exc!r}")
        st = srv.stats()
        if not st["mesh_shrinks"]:
            problems.append("server never adopted a shrunk mesh")
        if srv.comm.size >= comm.size:
            problems.append(f"server still on {srv.comm.size} devices")
        if converged == 0:
            problems.append("no request converged across the shrink")
        # the dispatcher must survive: post-recovery traffic still served
        post = srv.solve("poisson", B[:, 0], timeout=300)
        rres = (np.linalg.norm(B[:, 0] - A @ post.x)
                / np.linalg.norm(B[:, 0]))
        if not (post.converged and rres <= RTOL * 1.05):
            problems.append(f"post-recovery request failed parity "
                            f"({post.reason_name}, {rres:.3e})")
        print(f"[chaos] evict-serving: "
              f"{'OK' if not problems else 'FAIL'} "
              f"{comm.size}->{srv.comm.size} devices, {answered}/{R} "
              f"answered ({converged} converged, {typed} typed errors), "
              f"shrinks={len(st['mesh_shrinks'])}")
    finally:
        srv.shutdown(wait=False)
        _faults.heal()
    return [f"evict-serving: {p}" for p in problems]


def _persistent_server(tps, comm, A, rtol):
    from mpi_petsc4py_example_tpu.serving import SolveServer
    srv = SolveServer(
        comm, window=0.005, max_k=8, autostart=False,
        retry_policy=tps.RetryPolicy(sleep=lambda _d: None))
    srv.register_operator("poisson", A, ksp_type="cg", pc_type="jacobi",
                          rtol=rtol, persistent=True)
    return srv


def drill_persistent_bitflip() -> list[str]:
    """Silent bitflip across a fully-staged persistent launch
    (``--persistent``, ISSUE 18): with a fault plan armed the runner
    routes the whole launch through the resilient per-batch path, the
    flip corrupts ONE slot's inner recurrence, and the megasolve
    verified-residual exit gate is the detector — the poisoned slot
    must either converge to strict fp64 parity (the fp64 refinement
    outer absorbed the flip) or honestly report non-convergence; a
    CONVERGED slot that misses parity is the silent lie this drill
    exists to catch. Every one of the Q slot futures must resolve, and
    the resident program must serve post-fault traffic on the direct
    path."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.utils.profiling import dispatch_counts

    problems: list[str] = []
    comm = tps.DeviceComm()
    A = poisson2d_csr(12)
    n = A.shape[0]
    rng = np.random.default_rng(9)
    Q = 8
    Xt = rng.random((n, Q))
    B = np.asarray(A @ Xt)
    srv = _persistent_server(tps, comm, A, RTOL)
    try:
        with tps.inject_faults("spmv.result=bitflip:at=2:times=1"):
            futs = [srv.submit("poisson", B[:, j]) for j in range(Q)]
            srv.start()
            if not srv.drain(600):
                problems.append("drain timed out — hung slot future(s)")
        answered = parity = honest = 0
        for j, f in enumerate(futs):
            if not f.done():
                problems.append(f"slot {j} future never resolved")
                continue
            answered += 1
            exc = f.exception(0)
            if exc is not None:
                problems.append(f"slot {j}: untyped failure {exc!r}")
                continue
            r = f.result(0)
            rres = (np.linalg.norm(B[:, j] - A @ r.x)
                    / np.linalg.norm(B[:, j]))
            if r.converged:
                if rres <= RTOL * 1.05:
                    parity += 1
                else:
                    problems.append(
                        f"slot {j}: CONVERGED with true_rres "
                        f"{rres:.3e} — a silently wrong answer")
            else:
                honest += 1        # the gate refused to lie
        st = srv.stats().get("persistent", {}).get("poisson", {})
        if st.get("fallbacks", 0) < 1:
            problems.append("armed plan never routed the launch through "
                            "the resilient fallback")
        # the plan is disarmed: post-fault traffic rides the DIRECT
        # resident program again, at ≤ one dispatch for the request
        before = dispatch_counts()
        post = srv.solve("poisson", B[:, 0], timeout=300)
        after = dispatch_counts()
        direct = int(after.get("persistent_serve", 0)
                     - before.get("persistent_serve", 0))
        rres = (np.linalg.norm(B[:, 0] - A @ post.x)
                / np.linalg.norm(B[:, 0]))
        if not (post.converged and rres <= RTOL * 1.05):
            problems.append(f"post-fault request failed parity "
                            f"({post.reason_name}, {rres:.3e})")
        if direct != 1:
            problems.append(f"post-fault request cost {direct} "
                            "persistent_serve dispatch(es), wanted 1")
        print(f"[chaos] persistent-bitflip: "
              f"{'OK' if not problems else 'FAIL'} {answered}/{Q} "
              f"answered ({parity} fp64-parity, {honest} honestly "
              f"non-converged), fallbacks={st.get('fallbacks')}")
    finally:
        srv.shutdown(wait=False)
    return [f"persistent-bitflip: {p}" for p in problems]


def drill_persistent_lost() -> list[str]:
    """Mid-launch device loss on a persistent session (``--persistent``,
    ISSUE 18): the loss fires at the resilient fallback's program
    boundary, the elastic tier shrinks the mesh RESUMING past iteration
    0, every slot future resolves converged at fp64 parity, the server
    adopts the shrunk mesh, and the NEXT launch transparently rebuilds
    the resident program on the surviving geometry
    (``stats['rebuilds']``)."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.resilience import faults as _faults

    problems: list[str] = []
    comm = tps.DeviceComm()
    if comm.size < 2:
        return ["persistent-lost: needs a multi-device mesh "
                f"(got {comm.size} device[s])"]
    A = poisson2d_csr(12)
    n = A.shape[0]
    rng = np.random.default_rng(10)
    Q = 6
    Xt = rng.random((n, Q))
    B = np.asarray(A @ Xt)
    victim = comm.device_ids[-1]
    srv = _persistent_server(tps, comm, A, RTOL)
    try:
        spec = f"device.lost=unavailable:device={victim}:at=1:iter=10"
        with tps.inject_faults(spec):
            futs = [srv.submit("poisson", B[:, j]) for j in range(Q)]
            srv.start()
            if not srv.drain(600):
                problems.append("drain timed out — hung slot future(s)")
        for j, f in enumerate(futs):
            if not f.done():
                problems.append(f"slot {j} future never resolved")
                continue
            exc = f.exception(0)
            if exc is not None:
                problems.append(f"slot {j}: untyped failure {exc!r}")
                continue
            r = f.result(0)
            rres = (np.linalg.norm(B[:, j] - A @ r.x)
                    / np.linalg.norm(B[:, j]))
            if not (r.converged and rres <= RTOL * 1.05):
                problems.append(f"slot {j}: reason={r.reason_name} "
                                f"true_rres={rres:.3e} (parity miss)")
        st = srv.stats()
        if not st["mesh_shrinks"]:
            problems.append("server never adopted a shrunk mesh")
        elif st["mesh_shrinks"][0]["resumed_iteration"] <= 0:
            problems.append("shrunk solve restarted from iteration 0 — "
                            "the checkpoint carry was lost")
        if srv.comm.size >= comm.size:
            problems.append(f"server still on {srv.comm.size} devices")
        # the registry still holds the victim, but the adopted mesh
        # excludes it: the next launch must take the DIRECT path and
        # rebuild the resident program for the shrunk geometry
        post = srv.solve("poisson", B[:, 0], timeout=600)
        rres = (np.linalg.norm(B[:, 0] - A @ post.x)
                / np.linalg.norm(B[:, 0]))
        if not (post.converged and rres <= RTOL * 1.05):
            problems.append(f"post-shrink request failed parity "
                            f"({post.reason_name}, {rres:.3e})")
        pst = srv.stats().get("persistent", {}).get("poisson", {})
        if pst.get("rebuilds", 0) != 1:
            problems.append(
                f"{pst.get('rebuilds', 0)} resident-program rebuild(s) "
                "after the shrink, wanted exactly 1")
        print(f"[chaos] persistent-lost: "
              f"{'OK' if not problems else 'FAIL'} "
              f"{comm.size}->{srv.comm.size} devices, "
              f"resumed_iter="
            f"{st['mesh_shrinks'][0]['resumed_iteration'] if st['mesh_shrinks'] else '-'}, "
              f"rebuilds={pst.get('rebuilds')}")
    finally:
        srv.shutdown(wait=False)
        _faults.heal()
    return [f"persistent-lost: {p}" for p in problems]


def drill_fleet_regrow() -> list[str]:
    """Loss -> shrink -> heal -> RE-GROW in one resilient solve
    (``--fleet``, the elastic ladder's round trip): a sticky device loss
    shrinks the session (resuming past iteration 0), the heal lands
    mid-backoff, and the next transient failure re-grows it onto the
    repaired full mesh — where the solve again RESUMES from the
    checkpointed iterate, never iteration 0. The deterministic proof of
    the acceptance line 'solve resumes past iteration 0 on the re-grown
    mesh'."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.resilience import faults as _faults

    problems: list[str] = []
    comm = tps.DeviceComm()
    if comm.size < 2:
        return ["fleet-regrow: needs a multi-device mesh "
                f"(got {comm.size} device[s])"]
    A = poisson2d_csr(16)
    M = tps.Mat.from_scipy(comm, A)
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type("cg")
    ksp.get_pc().set_type("jacobi")
    ksp.set_tolerances(rtol=RTOL)
    x_true = np.random.default_rng(0).random(A.shape[0])
    b = A @ x_true
    x, bv = M.get_vecs()
    bv.set_global(b)
    healed = []

    def sleep_heals(_d):
        # the repair arrives while the session runs degraded: the
        # backoff of the first post-shrink transient failure is the
        # deterministic host-side moment to apply it
        if not healed:
            healed.append(_faults.heal())

    victim = comm.device_ids[-1]
    spec = (f"device.lost=unavailable:device={victim}:at=1:iter=10,"
            "ksp.program=unavailable:at=2:times=2:iter=20")
    try:
        with tps.inject_faults(spec):
            res = tps.resilient_solve(ksp, bv, x,
                                      tps.RetryPolicy(sleep=sleep_heals))
        shrinks = [e for e in res.recovery_events
                   if e.kind == "mesh_shrink"]
        regrows = [e for e in res.recovery_events
                   if e.kind == "mesh_regrow"]
        if not shrinks:
            problems.append("no mesh_shrink recovery event")
        elif shrinks[0].iterations <= 0:
            problems.append("shrink resumed from iteration 0")
        if not regrows:
            problems.append("no mesh_regrow recovery event (heal was "
                            f"{healed})")
        else:
            g = regrows[0]
            if not g.new_devices > g.old_devices:
                problems.append(f"re-grow did not grow: {g}")
            if g.iterations <= 0:
                problems.append("solve did NOT resume past iteration 0 "
                                "on the re-grown mesh")
        if ksp.comm.size != comm.size:
            problems.append(f"capacity did not return: "
                            f"{ksp.comm.size}/{comm.size} devices")
        if not res.converged:
            problems.append(f"recovered solve did not converge: {res}")
        rtrue = (np.linalg.norm(b - A @ x.to_numpy())
                 / np.linalg.norm(b))
        if not rtrue <= RTOL * 1.05:
            problems.append(f"true relative residual {rtrue:.3e} misses "
                            "rtol")
        print(f"[chaos] fleet-regrow: "
              f"{'OK' if not problems else 'FAIL'} ladder "
              f"{comm.size}->{shrinks[0].new_devices if shrinks else '?'}"
              f"->{ksp.comm.size} devices, resumed at "
              f"{shrinks[0].iterations if shrinks else '?'} then "
              f"{regrows[0].iterations if regrows else '?'}, "
              f"true_rres={rtrue:.3e}")
    finally:
        _faults.heal()
    return [f"fleet-regrow: {p}" for p in problems]


def drill_fleet_serving() -> list[str]:
    """Mixed-QoS load on a router fleet with ONE injected device loss
    AND one heal mid-load (``--fleet``): every future must resolve (a
    converged fp64-parity result or a typed QoS error), the replica must
    shrink then RE-GROW, and post-heal capacity must return to the
    provisioned mesh with post-recovery traffic still served."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.resilience import faults as _faults
    from mpi_petsc4py_example_tpu.serving import SolveRouter

    problems: list[str] = []
    comm = tps.DeviceComm()
    if comm.size < 2:
        return ["fleet-serving: needs a multi-device mesh "
                f"(got {comm.size} device[s])"]
    A = poisson2d_csr(12)
    n = A.shape[0]
    rng = np.random.default_rng(14)
    R = 16
    Xt = rng.random((n, R))
    B = np.asarray(A @ Xt)
    victim = comm.device_ids[-1]
    rt = SolveRouter(2, comm, window=0.004, max_k=4, deadline=120.0,
                     retry_policy=tps.RetryPolicy(sleep=lambda _d: None))
    try:
        rt.register_operator("poisson", A, pc_type="jacobi", rtol=RTOL)
        # mixed-QoS Poisson-ish load: alternating classes, bursty gaps
        classes = ["interactive" if j % 2 else "bulk" for j in range(R)]
        futs = []
        # the loss fires at the 1st dispatched block under the armed
        # plan, with real partial state
        with tps.inject_faults(
                f"device.lost=unavailable:device={victim}:at=1:iter=6"):
            for j in range(R // 2):
                futs.append(rt.submit("poisson", B[:, j],
                                      qos=classes[j]))
            if not rt.drain(600):
                problems.append("drain timed out during the loss phase")
        st = rt.stats()
        if st["mesh_shrinks"] != 1:
            problems.append(f"expected 1 mesh shrink, saw "
                            f"{st['mesh_shrinks']}")
        # ONE heal mid-load: capacity must come back for the second half
        _faults.heal()
        regrown = rt.heal_check()
        for j in range(R // 2, R):
            futs.append(rt.submit("poisson", B[:, j], qos=classes[j]))
        if not rt.drain(600):
            problems.append("drain timed out during the heal phase")
        st = rt.stats()
        if regrown < 1 or st["mesh_regrows"] < 1:
            problems.append(f"no replica re-grew after the heal "
                            f"(regrown={regrown}, stats="
                            f"{st['mesh_regrows']})")
        sizes = [s["devices"] for s in st["per_replica"].values()]
        if any(sz != comm.size for sz in sizes):
            problems.append(f"post-heal capacity did not return: "
                            f"replica sizes {sizes} != {comm.size}")
        answered = converged = typed = 0
        for j, f in enumerate(futs):
            if not f.done():
                problems.append(f"request {j} future never resolved")
                continue
            answered += 1
            exc = f.exception(0)
            if exc is None:
                r = f.result(0)
                rres = (np.linalg.norm(B[:, j] - A @ r.x)
                        / np.linalg.norm(B[:, j]))
                if not (r.converged and rres <= RTOL * 1.05):
                    problems.append(
                        f"request {j} ({classes[j]}): "
                        f"reason={r.reason_name} true_rres={rres:.3e} "
                        "(parity miss)")
                else:
                    converged += 1
            elif isinstance(exc, (tps.DeadlineExceededError,
                                  tps.ServerOverloadedError)):
                typed += 1
            else:
                problems.append(f"request {j}: untyped failure {exc!r}")
        if converged == 0:
            problems.append("no request converged across the ladder")
        print(f"[chaos] fleet-serving: "
              f"{'OK' if not problems else 'FAIL'} {answered}/{R} "
              f"answered ({converged} converged, {typed} typed), "
              f"shrinks={st['mesh_shrinks']} regrows={st['mesh_regrows']} "
              f"replica sizes back to {sizes}")
    finally:
        rt.shutdown(wait=False)
        _faults.heal()
    return [f"fleet-serving: {p}" for p in problems]


def _multisplit_problem(n=256, nblocks=4, seed=3):
    """Block-diagonally-dominant model problem (the Frommer–Szyld
    convergence condition the async tier documents) + manufactured
    solution."""
    import scipy.sparse as sp

    A = sp.diags([-1.0, 4.0, -1.0], [-1, 0, 1], shape=(n, n),
                 format="csr")
    x_true = np.random.default_rng(seed).random(n)
    b = np.asarray(A @ x_true)
    return A, b, x_true, nblocks


def drill_multisplit_jitter() -> list[str]:
    """Sticky slow device under the async tier (``--multisplit``): a
    seeded ``comm.delay`` timing fault pins one block's device at +20 ms
    per step. The bounded-staleness supervisor must absorb it — resyncs
    fire, observed staleness stays within the bound — and the solve must
    still land at strict fp64 parity. Every synchronous plan pays this
    straggler at every reduction; this drill proves the tolerance
    machinery."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.resilience import faults as _faults
    from mpi_petsc4py_example_tpu.solvers.multisplit import MultisplitSolver

    problems: list[str] = []
    A, b, _x_true, nblocks = _multisplit_problem()
    bound = 3
    ms = MultisplitSolver(nblocks=nblocks, max_stale=bound, rtol=RTOL)
    ms.set_operator(A)
    slow = ms._blocks[1].device_id
    spec = f"comm.delay=delay:device={slow}:times=*:mean=0.02:seed=7"
    try:
        with tps.inject_faults(spec):
            res = ms.solve(b)
    finally:
        _faults.heal()
    if not res.converged:
        problems.append(f"jittered solve did not converge: {res}")
    if res.resyncs == 0:
        problems.append("sticky slow device never forced a resync — the "
                        "staleness bound is not being enforced")
    # ages grow by at most 1 per outer step, so the FIRST over-bound
    # read — the one that triggers the resync — records bound+1; any
    # age past that means a resync failed to pull the partner back
    if res.max_stale_seen > bound + 1:
        problems.append(f"observed staleness {res.max_stale_seen} "
                        f"exceeds the enforced bound {bound}+1")
    rtrue = np.linalg.norm(b - A @ res.x) / np.linalg.norm(b)
    if not rtrue <= RTOL:
        problems.append(f"true relative residual {rtrue:.3e} misses the "
                        "strict tolerance")
    status = "OK" if not problems else "FAIL"
    print(f"[chaos] multisplit-jitter: {status} cut={res.cut_version} "
          f"resyncs={res.resyncs} max_stale_seen={res.max_stale_seen} "
          f"true_rres={rtrue:.3e}")
    return [f"multisplit-jitter: {p}" for p in problems]


def drill_multisplit_lost() -> list[str]:
    """Mid-solve ``device.lost`` under the async tier (``--multisplit``,
    the ISSUE 17 acceptance drill): the solve must degrade to ONE stale
    block (survivors iterate against its frozen last-exchanged version),
    re-home the lost block onto a survivor, and converge to strict fp64
    tolerance — with every block's published version sequence strictly
    increasing across the loss. A restart-from-iteration-0 anywhere
    would publish a version at or below one already seen; the recorded
    sequences make 'survivors never revisit iteration 0' a checked
    property, not prose."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.parallel import exchange as _ex
    from mpi_petsc4py_example_tpu.resilience import faults as _faults
    from mpi_petsc4py_example_tpu.solvers.multisplit import MultisplitSolver

    problems: list[str] = []
    A, b, _x_true, nblocks = _multisplit_problem()
    published: dict[int, list[int]] = {}
    rehomes: list[tuple[int, int]] = []
    orig_pub = _ex.StaleExchange.publish
    orig_repub = _ex.StaleExchange.republish

    def pub(self, block, payload):
        v = orig_pub(self, block, payload)
        if v is not None:
            published.setdefault(block, []).append(v)
        return v

    def repub(self, block, payload, *, version=None):
        orig_repub(self, block, payload, version=version)
        rehomes.append((block, self.version(block)))

    ms = MultisplitSolver(nblocks=nblocks, rtol=RTOL)
    ms.set_operator(A)
    victim = ms._blocks[2].device_id
    _ex.StaleExchange.publish = pub
    _ex.StaleExchange.republish = repub
    try:
        with tps.inject_faults(
                f"device.lost=unavailable:device={victim}:at=5"):
            res = ms.solve(b)
    finally:
        _ex.StaleExchange.publish = orig_pub
        _ex.StaleExchange.republish = orig_repub
        _faults.heal()
    if not res.converged:
        problems.append(f"degraded solve did not converge: {res}")
    if res.blocks_lost < 1:
        problems.append("the armed device.lost never cost a block")
    if not rehomes:
        problems.append("the lost block was never re-homed")
    if min(res.block_steps) <= 0:
        problems.append(f"a block reports zero outer steps: "
                        f"{res.block_steps}")
    for blk, seq in sorted(published.items()):
        if any(b2 <= a for a, b2 in zip(seq, seq[1:])):
            problems.append(
                f"block {blk} published a non-increasing version "
                f"sequence {seq[:12]}... — somebody revisited "
                "iteration 0")
    for blk, frozen in rehomes:
        later = [v for v in published.get(blk, []) if v > frozen]
        if not later and res.converged:
            problems.append(
                f"re-homed block {blk} never published past its frozen "
                f"version {frozen} — re-home did not resume progress")
    rtrue = np.linalg.norm(b - A @ res.x) / np.linalg.norm(b)
    if not rtrue <= RTOL:
        problems.append(f"true relative residual {rtrue:.3e} misses the "
                        "strict tolerance")
    status = "OK" if not problems else "FAIL"
    print(f"[chaos] multisplit-lost: {status} cut={res.cut_version} "
          f"blocks_lost={res.blocks_lost} steps={res.block_steps} "
          f"rehomes={rehomes} true_rres={rtrue:.3e}")
    return [f"multisplit-lost: {p}" for p in problems]


def drill_multisplit_partition() -> list[str]:
    """Exchange partition under the async tier (``--multisplit``): an
    armed ``exchange.put`` drop fault discards a block's publishes — its
    peers see a frozen version and its staleness grows — yet the solve
    may only pay TIME (extra outer steps / resyncs), never correctness:
    strict fp64 parity at a consistent cut."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.resilience import faults as _faults
    from mpi_petsc4py_example_tpu.solvers.multisplit import MultisplitSolver

    problems: list[str] = []
    A, b, _x_true, nblocks = _multisplit_problem()
    ms = MultisplitSolver(nblocks=nblocks, rtol=RTOL)
    ms.set_operator(A)
    try:
        with tps.inject_faults("exchange.put=drop:device=3:at=3:times=6"):
            res = ms.solve(b)
    finally:
        _faults.heal()
    drops = ms._exchange.drops
    if not res.converged:
        problems.append(f"partitioned solve did not converge: {res}")
    if drops < 1:
        problems.append("the armed exchange.put fault never dropped a "
                        "publish")
    rtrue = np.linalg.norm(b - A @ res.x) / np.linalg.norm(b)
    if not rtrue <= RTOL:
        problems.append(f"true relative residual {rtrue:.3e} misses the "
                        "strict tolerance")
    status = "OK" if not problems else "FAIL"
    print(f"[chaos] multisplit-partition: {status} cut={res.cut_version} "
          f"drops={drops} resyncs={res.resyncs} true_rres={rtrue:.3e}")
    return [f"multisplit-partition: {p}" for p in problems]


def _transport_fleet(tps, comm, hosts, **kw):
    """A drill-speed FleetManager: zero batching window, no retry/client
    sleeps (backoff math still runs, the drill just doesn't wait)."""
    from mpi_petsc4py_example_tpu.serving.remote import FleetManager

    return FleetManager(hosts, comm, window=0.0, max_k=4,
                        retry_policy=tps.RetryPolicy(sleep=lambda _d: None),
                        client_sleep=lambda _d: None, **kw)


def drill_transport_loss() -> list[str]:
    """Host loss mid-load (``--transport``): kill the owning replica
    host AFTER a warm solve + lease step cached its elastic checkpoint,
    then submit again — the in-flight future must fail over to the
    survivor, the re-homed solve must RESUME past iteration 0 (the
    FailoverEvent carries the warm-start iteration), and the answer must
    hold strict fp64 residual parity across the failover boundary."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.resilience import faults as _faults

    problems: list[str] = []
    comm = tps.DeviceComm()
    A = poisson2d_csr(10)
    xt = np.random.default_rng(0).random(A.shape[0])
    b = np.asarray(A @ xt)
    mgr = _transport_fleet(tps, comm, 2)
    try:
        mgr.register_operator("a", A, pc_type="jacobi", rtol=RTOL)
        res = mgr.submit("a", b).result(timeout=120)
        r0 = np.linalg.norm(b - A @ res.x) / np.linalg.norm(b)
        if not r0 <= RTOL * 1.05:
            problems.append(f"pre-loss residual {r0:.3e} misses rtol")
        mgr.lease_step()  # pulls the post-solve checkpoint client-side
        owner = mgr.router.owner("a")
        mgr.kill_host(owner)
        res2 = mgr.submit("a", b).result(timeout=120)
        r2 = np.linalg.norm(b - A @ res2.x) / np.linalg.norm(b)
        if not r2 <= RTOL * 1.05:
            problems.append(f"post-loss residual {r2:.3e} misses rtol "
                            "(parity broke across the failover boundary)")
        new_owner = mgr.router.owner("a")
        if new_owner == owner:
            problems.append(f"session never re-homed off the dead host "
                            f"{owner}")
        if not mgr.failovers:
            problems.append("no FailoverEvent was recorded")
        resumed = mgr.failovers[0].resumed_iteration if mgr.failovers \
            else 0
        if resumed <= 0:
            problems.append(f"re-homed solve restarted from iteration 0 "
                            f"(resumed_iteration={resumed}) — the "
                            "checkpoint never shipped")
        status = "OK" if not problems else "FAIL"
        print(f"[chaos] transport-loss: {status} {owner}->{new_owner} "
              f"resumed_iteration={resumed} true_rres={r2:.3e}")
    finally:
        mgr.shutdown(wait=False)
        _faults.heal()
    return [f"transport-loss: {p}" for p in problems]


def drill_transport_duplicate() -> list[str]:
    """Duplicate delivery under retry (``--transport``): a reply
    dropped AFTER the handler ran forces the client to retry the same
    idempotency key (phase A), and an injected request duplication
    delivers one logical call twice (phase B) — in both, the host must
    execute the solve EXACTLY once per logical request (no
    double-solve, no double-resolved future) while serving the
    duplicate from its idempotency cache."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.resilience import faults as _faults

    problems: list[str] = []
    comm = tps.DeviceComm()
    A = poisson2d_csr(10)
    xt = np.random.default_rng(1).random(A.shape[0])
    b = np.asarray(A @ xt)
    mgr = _transport_fleet(tps, comm, 1)
    try:
        mgr.register_operator("a", A, pc_type="jacobi", rtol=RTOL)
        host = mgr.hosts["r0"]
        # phase A: the reply is lost once — the retry must JOIN the
        # already-executed call, not re-run it
        calls0 = host.rpc.stats["calls"]
        with tps.inject_faults("rpc.recv=drop:at=1:times=1"):
            res = mgr.submit("a", b).result(timeout=120)
        ra = np.linalg.norm(b - A @ res.x) / np.linalg.norm(b)
        calls_a = host.rpc.stats["calls"] - calls0
        dups_a = host.rpc.stats["duplicates"]
        if calls_a != 1:
            problems.append(f"lost-reply retry re-executed the handler "
                            f"({calls_a} executions for 1 logical call)")
        if dups_a < 1:
            problems.append("the retried delivery never hit the "
                            "idempotency cache")
        if not ra <= RTOL * 1.05:
            problems.append(f"phase-A residual {ra:.3e} misses rtol")
        # phase B: the request itself is delivered twice
        calls1 = host.rpc.stats["calls"]
        with tps.inject_faults("rpc.send=duplicate:at=1:times=1"):
            res2 = mgr.submit("a", b).result(timeout=120)
        rb = np.linalg.norm(b - A @ res2.x) / np.linalg.norm(b)
        calls_b = host.rpc.stats["calls"] - calls1
        if calls_b != 1:
            problems.append(f"duplicated request double-solved "
                            f"({calls_b} executions for 1 logical call)")
        if not rb <= RTOL * 1.05:
            problems.append(f"phase-B residual {rb:.3e} misses rtol")
        st = mgr.stubs["r0"].stats()
        if st["requests"] != 2:
            problems.append(f"server saw {st['requests']} requests for "
                            "2 logical solves — duplicates leaked "
                            "through to the solve queue")
        status = "OK" if not problems else "FAIL"
        print(f"[chaos] transport-duplicate: {status} "
              f"executions={calls_a}+{calls_b} "
              f"cache_hits={host.rpc.stats['duplicates']} "
              f"server_requests={st['requests']}")
    finally:
        mgr.shutdown(wait=False)
        _faults.heal()
    return [f"transport-duplicate: {p}" for p in problems]


def drill_transport_partition() -> list[str]:
    """Partition during live migration (``--transport``): a sticky
    partition of the migration DESTINATION makes the move fail after
    the dst may already hold a registered copy. The router's placement
    must stay truthful (src still owns and serves at parity), and once
    the partition heals, ``reconcile()`` must converge the fleet to a
    SINGLE truthful placement table — the orphaned dst copy is
    unregistered, never split-brained."""
    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu.models import poisson2d_csr
    from mpi_petsc4py_example_tpu.resilience import faults as _faults
    from mpi_petsc4py_example_tpu.serving.transport import TransportError

    problems: list[str] = []
    comm = tps.DeviceComm()
    A = poisson2d_csr(10)
    xt = np.random.default_rng(2).random(A.shape[0])
    b = np.asarray(A @ xt)
    mgr = _transport_fleet(tps, comm, 2)
    try:
        mgr.register_operator("p", A, pc_type="jacobi", rtol=RTOL)
        src = mgr.router.owner("p")
        dst = next(n for n in mgr.stubs if n != src)
        dst_idx = int(dst[1:])
        migrate_failed = False
        with tps.inject_faults(
                f"rpc.recv=partition:device={dst_idx}:times=*"):
            try:
                mgr.router.migrate("p", dst)
            except (TransportError, tps.DeadlineExceededError,
                    RuntimeError):
                migrate_failed = True
            if not migrate_failed:
                problems.append("migration across a partitioned "
                                "destination reported success")
            if mgr.router.owner("p") != src:
                problems.append(f"placement lied during the partition: "
                                f"owner={mgr.router.owner('p')} != {src}")
            res = mgr.submit("p", b).result(timeout=120)
            rr = np.linalg.norm(b - A @ res.x) / np.linalg.norm(b)
            if not rr <= RTOL * 1.05:
                problems.append(f"during-partition residual {rr:.3e} "
                                "misses rtol")
        # partition healed: the dst may hold an orphaned epoch-stamped
        # copy — reconcile must remove it and keep ONE truthful owner
        rep = mgr.reconcile()
        res_src = mgr.stubs[src].client.call("resident", {}, deadline=10.0)
        res_dst = mgr.stubs[dst].client.call("resident", {}, deadline=10.0)
        if "p" not in res_src:
            problems.append(f"the authoritative copy vanished from {src}")
        if "p" in res_dst:
            problems.append(f"split brain: {dst} still holds 'p' after "
                            "reconcile")
        if mgr.router.owner("p") != src:
            problems.append(f"reconcile re-homed away from the healthy "
                            f"owner: {mgr.router.owner('p')}")
        res3 = mgr.submit("p", b).result(timeout=120)
        r3 = np.linalg.norm(b - A @ res3.x) / np.linalg.norm(b)
        if not r3 <= RTOL * 1.05:
            problems.append(f"post-reconcile residual {r3:.3e} misses "
                            "rtol")
        status = "OK" if not problems else "FAIL"
        print(f"[chaos] transport-partition: {status} src={src} dst={dst} "
              f"orphans_removed={rep['orphans_removed']} "
              f"true_rres={r3:.3e}")
    finally:
        mgr.shutdown(wait=False)
        _faults.heal()
    return [f"transport-partition: {p}" for p in problems]


def validate_trace(trace_path: str, evict: bool) -> list[str]:
    """Structural validation of the exported Perfetto trace + flight
    dump — the CI telemetry job's schema gate."""
    import json

    from mpi_petsc4py_example_tpu import telemetry

    problems: list[str] = []
    with open(trace_path) as f:
        doc = json.load(f)
    evs = doc.get("traceEvents", [])
    if not evs:
        return [f"trace {trace_path}: empty traceEvents"]
    names = set()
    for e in evs:
        missing = [k for k in ("name", "ph", "pid") if k not in e]
        if e.get("ph") == "X":
            missing += [k for k in ("ts", "dur", "tid") if k not in e]
            names.add(e["name"])
        if missing:
            problems.append(f"trace event {e.get('name')!r} missing "
                            f"key(s) {missing}")
            break
    if evict:
        # the acceptance drill: the eviction's retry -> shrink chain
        # must be in the trace, shrink carrying the resumed iteration
        for want in ("resilient.solve", "resilient.shrink", "ksp.solve"):
            if want not in names:
                problems.append(f"trace has no {want!r} span")
        shrinks = [e for e in evs if e.get("ph") == "X"
                   and e["name"] == "resilient.shrink"]
        if not any(int(e.get("args", {}).get("resumed_iteration", 0)) > 0
                   for e in shrinks):
            problems.append("no resilient.shrink span carries a positive "
                            "resumed_iteration attribute")
        # the chain must also survive as a TREE in the flight ring: a
        # resilient.solve root whose descendants include the shrink
        def has_shrink(tree):
            return (tree["name"] == "resilient.shrink"
                    or any(has_shrink(c) for c in tree["children"]))
        roots = telemetry.flight_recorder.spans()
        if not any(t["name"] == "resilient.solve" and has_shrink(t)
                   for t in roots):
            problems.append("flight ring holds no resilient.solve tree "
                            "containing the shrink span")
    flight_path = trace_path + ".flight.json"
    with open(flight_path) as f:
        dump = json.load(f)
    if not dump.get("entries"):
        problems.append(f"flight dump {flight_path} is empty")
    if evict and not any(e.get("type") == "event"
                         and e.get("kind") == "fault"
                         and e["data"].get("point") == "device.lost"
                         for e in dump.get("entries", [])):
        problems.append("flight dump records no device.lost fault event")
    return [f"trace: {p}" for p in problems]


def main() -> int:
    import contextlib

    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu import telemetry

    failures: list[str] = []
    argv = sys.argv[1:]
    trace_out = None
    if "--trace-out" in argv:
        i = argv.index("--trace-out")
        if i + 1 >= len(argv):
            print("--trace-out needs a path", file=sys.stderr)
            return 2
        trace_out = argv[i + 1]
        telemetry.enable()
    env_spec = os.environ.get("TPU_SOLVE_FAULTS", "").strip()
    if "--fleet" in sys.argv[1:]:
        # ISSUE 13 acceptance: loss -> shrink -> heal -> RE-GROW end to
        # end — the solve resumes past iteration 0 on the re-grown
        # mesh, every mixed-QoS future resolves, and post-heal capacity
        # returns to the provisioned mesh
        failures += drill_fleet_regrow()
        failures += drill_fleet_serving()
        what = "fleet loss/shrink/heal/re-grow"
    elif "--evict" in sys.argv[1:]:
        # ISSUE 8 acceptance: permanent device loss mid-solve AND
        # mid-serving-load must recover onto a strictly smaller mesh
        failures += drill_evict_solve()
        failures += drill_evict_serving()
        what = "device-eviction"
    elif "--megasolve" in sys.argv[1:]:
        # ISSUE 12 acceptance: a bitflip inside the FUSED whole-solve
        # loop must detect -> rollback -> re-enter at one dispatch per
        # attempt
        failures += drill_megasolve()
        what = "megasolve fused-loop corruption"
    elif "--multisplit" in sys.argv[1:]:
        # ISSUE 17 acceptance: the async tier must absorb a sticky slow
        # device as bounded staleness, degrade a mid-solve device.lost
        # to ONE stale block (survivors provably never revisit
        # iteration 0), and pay an exchange partition only in staleness
        failures += drill_multisplit_jitter()
        failures += drill_multisplit_lost()
        failures += drill_multisplit_partition()
        what = "asynchronous-multisplit staleness/loss"
    elif "--transport" in sys.argv[1:]:
        # ISSUE 20 acceptance: host loss mid-load must resolve every
        # pending future with the re-homed solve resuming past
        # iteration 0; injected duplicate delivery must never
        # double-solve or double-resolve; a healed partition must
        # reconcile to a single truthful placement table
        failures += drill_transport_loss()
        failures += drill_transport_duplicate()
        failures += drill_transport_partition()
        what = "fleet-transport loss/duplicate/partition"
    elif "--persistent" in sys.argv[1:]:
        # ISSUE 18 acceptance: a bitflip across a fully-staged
        # persistent launch must resolve every slot with no silently-
        # wrong answer, and a mid-launch device loss must shrink,
        # resume past iteration 0, and rebuild the resident program
        failures += drill_persistent_bitflip()
        failures += drill_persistent_lost()
        what = "persistent-serving corruption/loss"
    elif "--sstep" in sys.argv[1:]:
        # ISSUE 15 acceptance: a bitflip inside an s-block must detect
        # -> rollback to the verified carry -> re-enter, and the
        # ill-conditioned-basis demotion chain must land on classic CG
        failures += drill_sstep()
        what = "s-step block corruption + demotion"
    elif env_spec:
        # env-armed: the plan is already active from the environment
        failures += drill(f"env:{env_spec}", contextlib.nullcontext())
        what = "silent-corruption"
    else:
        for spec in BUILTIN_SPECS:
            failures += drill(spec, tps.inject_faults(spec))
        what = "silent-corruption"
    if trace_out:
        telemetry.export_trace(trace_out)
        telemetry.flight_recorder.dump(trace_out + ".flight.json",
                                       reason="chaos smoke")
        failures += validate_trace(trace_out, "--evict" in sys.argv[1:])
        print(f"[chaos] trace exported to {trace_out} "
              f"(+ {trace_out}.flight.json)")
    if failures:
        print("[chaos] FAILURES:", file=sys.stderr)
        for f in failures:
            print(f"  - {f}", file=sys.stderr)
        return 1
    print(f"[chaos] all {what} drills recovered and verified")
    return 0


if __name__ == "__main__":
    sys.exit(main())
