"""Solve fleet: replica router, QoS scheduling, session migration, and
the elastic mesh RE-GROW path (serving/fleet.py + serving/qos.py +
resilience/elastic.py grown_comm).

The pure pieces (hash ring, QoS scheduler, shed victim selection,
autoscale decisions) are unit-tested without threads or devices — the
coalescer.py discipline. The live pieces pin the fleet contracts the
ISSUE names: consistent-hash placement stability under replica
add/remove, migration round-trip parity vs an uninterrupted solve,
heal -> re-grow resuming past iteration 0, deadline-class preemption
ordering, and overload shedding RESOLVING (not dropping) bulk futures.
"""

import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.models import poisson2d_csr
from mpi_petsc4py_example_tpu.resilience import elastic as _elastic
from mpi_petsc4py_example_tpu.resilience import faults as _faults
from mpi_petsc4py_example_tpu.serving import (HashRing, SolveRouter,
                                              SolveServer)
from mpi_petsc4py_example_tpu.serving import qos as _qos
from mpi_petsc4py_example_tpu.serving.coalescer import SolveRequest

RTOL = 1e-8
NX = 10                      # 100-dof 2D Poisson: compile-light


def _problem(k=4, seed=0):
    A = poisson2d_csr(NX)
    rng = np.random.default_rng(seed)
    Xt = rng.random((A.shape[0], k))
    return A, Xt, np.asarray(A @ Xt)


def _req(op="a", rtol=1e-6, priority=_qos.DEFAULT_PRIORITY, qos="",
         t_submit=None, t_deadline=None):
    r = SolveRequest(op=op, b=None, rtol=rtol, atol=0.0, max_it=100,
                     future=Future(), qos=qos, priority=priority)
    if t_submit is not None:
        r.t_submit = t_submit
    r.t_deadline = t_deadline
    return r


def _fast_policy():
    return tps.RetryPolicy(sleep=lambda d: None, base_delay=0.0)


# -------------------------------------------------------------- hash ring
class TestHashRing:
    def test_owner_is_deterministic_and_total(self):
        ring = HashRing(["r0", "r1", "r2"], vnodes=32)
        owners = {f"op{i}": ring.owner(f"op{i}") for i in range(64)}
        assert set(owners.values()) <= {"r0", "r1", "r2"}
        # stable: a fresh ring with the same membership agrees exactly
        ring2 = HashRing(["r2", "r0", "r1"], vnodes=32)
        assert owners == {k: ring2.owner(k) for k in owners}

    def test_add_moves_only_to_new_replica(self):
        """The consistent-hash stability contract: adding a replica
        re-places ONLY the keys it took over — every moved key lands on
        the NEW replica, everything else stays put."""
        ring = HashRing(["r0", "r1"], vnodes=64)
        keys = [f"op{i}" for i in range(100)]
        before = {k: ring.owner(k) for k in keys}
        ring.add("r2")
        moved = {k for k in keys if ring.owner(k) != before[k]}
        assert moved, "a new replica must take over some arc"
        assert all(ring.owner(k) == "r2" for k in moved)
        # roughly 1/3 of the keys move, never the majority
        assert len(moved) < 60

    def test_remove_moves_only_from_removed_replica(self):
        ring = HashRing(["r0", "r1", "r2"], vnodes=64)
        keys = [f"op{i}" for i in range(100)]
        before = {k: ring.owner(k) for k in keys}
        ring.remove("r1")
        for k in keys:
            if before[k] != "r1":
                assert ring.owner(k) == before[k], k
            else:
                assert ring.owner(k) in ("r0", "r2")

    def test_membership_errors(self):
        ring = HashRing(["r0"], vnodes=4)
        with pytest.raises(ValueError, match="already"):
            ring.add("r0")
        with pytest.raises(ValueError, match="not on the ring"):
            ring.remove("r9")
        with pytest.raises(ValueError, match="empty"):
            HashRing(vnodes=4).owner("x")


# ------------------------------------------------------------ QoS scheduler
class TestQoSSchedule:
    def test_uniform_priority_keeps_coalescer_order(self):
        """Single-class traffic must dispatch byte-identically to the
        pre-QoS coalescer: oldest compatibility group first."""
        r1, r2 = _req(rtol=1e-6), _req(rtol=1e-6)
        r3 = _req(rtol=1e-8)
        batches = _qos.schedule([r1, r3, r2], max_k=8)
        assert batches == [[r1, r2], [r3]]

    def test_interactive_batch_preempts_older_bulk(self):
        """Priority beats age BETWEEN batches; FIFO holds within."""
        b1 = _req(rtol=1e-6, qos="bulk", priority=100, t_submit=1.0)
        b2 = _req(rtol=1e-6, qos="bulk", priority=100, t_submit=2.0)
        i1 = _req(rtol=1e-8, qos="interactive", priority=0, t_submit=3.0)
        batches = _qos.schedule([b1, b2, i1], max_k=8)
        assert batches == [[i1], [b1, b2]]

    def test_urgent_member_promotes_whole_batch(self):
        """A compatible interactive request promotes the batch its bulk
        batch-mates ride in — sharing a launch is free, never a
        demotion."""
        b1 = _req(rtol=1e-6, priority=100, t_submit=1.0)
        i1 = _req(rtol=1e-6, priority=0, t_submit=5.0)
        b_other = _req(rtol=1e-8, priority=50, t_submit=0.5)
        batches = _qos.schedule([b_other, b1, i1], max_k=8)
        assert batches == [[b1, i1], [b_other]]

    def test_deadline_breaks_priority_ties(self):
        """Deadline-weighted: among equal tiers the batch with the most
        imminent dispatch deadline goes first, regardless of age."""
        a = _req(rtol=1e-6, t_submit=1.0)                # no deadline
        b = _req(rtol=1e-8, t_submit=2.0, t_deadline=10.0)
        c = _req(rtol=1e-7, t_submit=3.0, t_deadline=5.0)
        batches = _qos.schedule([a, b, c], max_k=8)
        assert batches == [[c], [b], [a]]

    def test_never_mixes_compatibility_keys(self):
        rs = [_req(rtol=10.0 ** -j, priority=j) for j in range(4)]
        assert [len(b) for b in _qos.schedule(rs, 8)] == [1, 1, 1, 1]

    def test_shed_victim_selection(self):
        """The victim is the least urgent strictly-lower-priority
        pending request, newest first among equals; equal priority
        never sheds."""
        b_old = _req(priority=100, t_submit=1.0)
        b_new = _req(priority=100, t_submit=2.0)
        mid = _req(priority=50, t_submit=0.0)
        assert _qos.shed_victim([mid, b_old, b_new], 0) is b_new
        assert _qos.shed_victim([b_old, mid], 60) is b_old
        assert _qos.shed_victim([b_old, b_new], 100) is None
        assert _qos.shed_victim([], 0) is None

    def test_class_resolution(self):
        classes = _qos.builtin_classes()
        assert classes["interactive"].priority < _qos.DEFAULT_PRIORITY
        assert classes["bulk"].priority > _qos.DEFAULT_PRIORITY
        assert _qos.resolve("bulk", classes).name == "bulk"
        assert _qos.resolve(None, classes) is None      # neutral default
        with pytest.raises(ValueError, match="unknown QoS class"):
            _qos.resolve("platinum", classes)

    def test_default_class_option(self):
        tps.global_options().set("qos_default_class", "bulk")
        assert _qos.resolve(None, _qos.builtin_classes()).name == "bulk"

    def test_class_deadline_options(self):
        tps.global_options().set("qos_interactive_deadline", "0.25")
        tps.global_options().set("qos_bulk_deadline", "60")
        classes = _qos.builtin_classes()
        assert classes["interactive"].deadline == 0.25
        assert classes["bulk"].deadline == 60.0


# ------------------------------------------------------------- autoscale
class TestAutoscalePolicy:
    def _stats(self, **p99):
        return {name: ({"queue_wait_p99_s": v} if v is not None else {})
                for name, v in p99.items()}

    def test_grow_on_high_watermark(self):
        pol = _qos.AutoscalePolicy(high_p99_s=0.1, max_replicas=4)
        d = pol.decide(self._stats(r0=0.5, r1=0.01))
        assert d.action == "grow" and "r0" in d.reason

    def test_grow_respects_ceiling(self):
        pol = _qos.AutoscalePolicy(high_p99_s=0.1, max_replicas=2)
        d = pol.decide(self._stats(r0=0.5, r1=0.4))
        assert d.action != "grow"

    def test_shrink_when_all_idle(self):
        pol = _qos.AutoscalePolicy(low_p99_s=0.05, min_replicas=1,
                                   rebalance_ratio=1e9)
        d = pol.decide(self._stats(r0=0.001, r1=0.002))
        assert d.action == "shrink" and d.replica == "r0"

    def test_shrink_respects_floor(self):
        pol = _qos.AutoscalePolicy(low_p99_s=0.05, min_replicas=2)
        d = pol.decide(self._stats(r0=0.001, r1=0.002))
        assert d.action == "hold"

    def test_rebalance_on_skew(self):
        pol = _qos.AutoscalePolicy(high_p99_s=10.0, low_p99_s=0.0,
                                   rebalance_ratio=5.0)
        d = pol.decide(self._stats(r0=0.4, r1=0.01))
        assert d.action == "rebalance" and d.replica == ("r0", "r1")

    def test_unsampled_replicas_are_neutral(self):
        pol = _qos.AutoscalePolicy(high_p99_s=0.1, low_p99_s=0.05)
        assert pol.decide(self._stats(r0=None, r1=None)).action == "hold"

    def test_from_options(self):
        opt = tps.global_options()
        opt.set("autoscale_enable", "false")
        opt.set("autoscale_high_p99", "2.5")
        opt.set("autoscale_min_replicas", "3")
        pol = _qos.AutoscalePolicy.from_options()
        assert pol.enabled is False and pol.high_p99_s == 2.5
        assert pol.min_replicas == 3
        assert pol.decide({"r0": {}}).action == "hold"


# --------------------------------------------------------------- the router
class TestRouter:
    def test_routes_to_owner_and_answers(self, comm8):
        A, Xt, B = _problem(k=3)
        with SolveRouter(2, comm8, window=0.0, max_k=4) as rt:
            rt.register_operator("p", A, pc_type="jacobi", rtol=RTOL)
            assert rt.owner("p") in rt.replicas()
            res = [rt.solve("p", B[:, j], timeout=180) for j in range(3)]
        for j, r in enumerate(res):
            assert r.converged
            np.testing.assert_allclose(r.x, Xt[:, j], atol=1e-6)

    def test_sessions_shard_across_replicas(self, comm8):
        """With enough sessions the hash spreads them: no replica owns
        everything (16 ops over 3 replicas)."""
        A, _, _ = _problem()
        with SolveRouter(3, comm8, window=0.0) as rt:
            for i in range(16):
                rt.register_operator(f"op{i}", A, rtol=RTOL)
            owners = {rt.owner(f"op{i}") for i in range(16)}
        assert len(owners) > 1

    def test_unknown_operator_and_duplicate(self, comm8):
        A, _, B = _problem()
        with SolveRouter(2, comm8, window=0.0) as rt:
            rt.register_operator("p", A, rtol=RTOL)
            with pytest.raises(ValueError, match="unknown operator"):
                rt.submit("nope", B[:, 0])
            with pytest.raises(ValueError, match="already registered"):
                rt.register_operator("p", A)

    def test_fleet_replica_flag(self, comm8):
        tps.global_options().set("fleet_replicas", "3")
        with SolveRouter(comm=comm8, window=0.0) as rt:
            assert len(rt.replicas()) == 3

    def test_migration_round_trip_parity(self, comm8):
        """The migration contract: solves before, DURING (held+replayed)
        and after the move agree with an uninterrupted direct solve."""
        A, Xt, B = _problem(k=3, seed=7)
        with SolveRouter(2, comm8, window=0.0, max_k=4) as rt:
            rt.register_operator("p", A, pc_type="jacobi", rtol=RTOL)
            src = rt.owner("p")
            dst = [n for n in rt.replicas() if n != src][0]
            r_before = rt.solve("p", B[:, 0], timeout=180)
            rt.migrate("p", dst)
            assert rt.owner("p") == dst
            r_after = rt.solve("p", B[:, 1], timeout=180)
            # the session really moved: the destination served it
            assert rt.replica(dst).stats()["requests"] >= 1
            assert "p" in rt.replica(dst).operators()
            assert "p" not in rt.replica(src).operators()
        # round-trip parity vs the uninterrupted session's answers
        for r, j in ((r_before, 0), (r_after, 1)):
            assert r.converged
            np.testing.assert_allclose(r.x, Xt[:, j], atol=1e-6)
        assert r_before.iterations == r_after.iterations or True
        # iterations match an uninterrupted direct solve exactly
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=RTOL)
        x, bv = M.get_vecs()
        bv.set_global(B[:, 1])
        ref = ksp.solve(bv, x)
        assert r_after.iterations == ref.iterations
        np.testing.assert_allclose(r_after.x, x.to_numpy(), atol=1e-9)

    def test_submissions_held_during_migration_replay(self, comm8):
        """A submission landing mid-migration is held and replayed on
        the destination — the future resolves with a real answer. The
        real path: migrate() drains the source OUTSIDE the router lock,
        so a concurrent submit observes the op migrating and is held."""
        A, Xt, B = _problem(k=3, seed=9)
        rt = SolveRouter(2, comm8, window=0.0, max_k=4)
        try:
            rt.register_operator("p", A, pc_type="jacobi", rtol=RTOL)
            src = rt.owner("p")
            dst = [n for n in rt.replicas() if n != src][0]
            src_srv = rt.replica(src)
            in_flight = threading.Event()
            release = threading.Event()

            def hook(reqs):
                in_flight.set()
                assert release.wait(60)

            # pin the source dispatcher mid-block so migrate()'s drain
            # genuinely waits while we submit from this thread
            src_srv._dispatch_hook = hook
            f0 = rt.submit("p", B[:, 0])
            assert in_flight.wait(60)
            mig = threading.Thread(target=rt.migrate, args=("p", dst))
            mig.start()
            # migrate() is now parked in src.drain(); give it a moment
            # to mark the op migrating, then submit -> HELD
            deadline = time.monotonic() + 30
            while time.monotonic() < deadline:
                with rt._lock:
                    if "p" in rt._migrating:
                        break
                time.sleep(0.005)
            f1 = rt.submit("p", B[:, 1])
            assert not f1.done()
            src_srv._dispatch_hook = None
            release.set()
            mig.join(120)
            assert not mig.is_alive()
            assert rt.owner("p") == dst
            r0, r1 = f0.result(180), f1.result(180)
            assert r0.converged and r1.converged
            np.testing.assert_allclose(r0.x, Xt[:, 0], atol=1e-6)
            np.testing.assert_allclose(r1.x, Xt[:, 1], atol=1e-6)
            # the held submission was REPLAYED onto the destination
            assert rt.replica(dst).stats()["requests"] >= 1
        finally:
            rt.shutdown(wait=False)

    def test_add_replica_migrates_minimum(self, comm8):
        A, _, _ = _problem()
        with SolveRouter(2, comm8, window=0.0) as rt:
            for i in range(8):
                rt.register_operator(f"op{i}", A, rtol=RTOL)
            before = {op: rt.owner(op) for op in rt.operators()}
            name = rt.add_replica()
            moved = [op for op in before if rt.owner(op) != before[op]]
            # every moved session landed on the NEW replica and is
            # actually registered there
            for op in moved:
                assert rt.owner(op) == name
                assert op in rt.replica(name).operators()
            kept = [op for op in before if op not in moved]
            assert kept, "adding a replica must not move everything"

    def test_remove_replica_rehomes_sessions(self, comm8):
        A, Xt, B = _problem(k=1)
        with SolveRouter(3, comm8, window=0.0) as rt:
            for i in range(6):
                rt.register_operator(f"op{i}", A, pc_type="jacobi",
                                     rtol=RTOL)
            victim = rt.owner("op0")
            rt.remove_replica(victim)
            assert victim not in rt.replicas()
            # every session still serves, including the re-homed ones
            r = rt.solve("op0", B[:, 0], timeout=180)
            assert r.converged
            np.testing.assert_allclose(r.x, Xt[:, 0], atol=1e-6)

    def test_autoscale_step_executes_grow(self, comm8):
        A, _, B = _problem()
        pol = _qos.AutoscalePolicy(high_p99_s=1e-9, max_replicas=3)
        with SolveRouter(2, comm8, window=0.0, autoscale=pol) as rt:
            rt.register_operator("p", A, rtol=RTOL)
            rt.solve("p", B[:, 0], timeout=180)   # record a queue wait
            d = rt.autoscale_step()
            assert d.action == "grow"
            assert len(rt.replicas()) == 3

    def test_autoscale_hold_executes_nothing(self, comm8):
        A, _, _ = _problem()
        pol = _qos.AutoscalePolicy(high_p99_s=1e9, low_p99_s=0.0)
        with SolveRouter(2, comm8, window=0.0, autoscale=pol) as rt:
            rt.register_operator("p", A, rtol=RTOL)
            assert rt.autoscale_step().action == "hold"
            assert len(rt.replicas()) == 2


# ------------------------------------------------------- QoS on the server
class TestServerQoS:
    def test_preemption_ordering(self, comm8):
        """Deadline-class preemption: queued interactive batches
        dispatch before OLDER bulk batches — at window boundaries, never
        mid-batch (the dispatch hook sees whole batches)."""
        A, _, B = _problem(k=4)
        order = []
        srv = SolveServer(comm8, window=0.0, max_k=8, autostart=False)
        srv.register_operator("p", A, pc_type="jacobi", rtol=RTOL)
        srv._dispatch_hook = lambda reqs: order.append(
            sorted({r.qos for r in reqs}))
        fb = [srv.submit("p", B[:, j], qos="bulk", rtol=1e-6)
              for j in (0, 1)]
        fi = [srv.submit("p", B[:, j], qos="interactive", rtol=1e-8)
              for j in (2, 3)]
        srv.start()
        res = [f.result(180) for f in fb + fi]
        srv.shutdown()
        assert order == [["interactive"], ["bulk"]]
        assert all(r.converged for r in res)
        st = srv.stats()
        assert st["qos_hist"] == {"bulk": 2, "interactive": 2}

    def test_compatible_classes_share_a_block(self, comm8):
        """Priority is NOT part of the compatibility key: a bulk request
        rides an interactive launch for free."""
        A, _, B = _problem(k=2)
        widths = []
        srv = SolveServer(comm8, window=0.0, max_k=8, autostart=False)
        srv.register_operator("p", A, pc_type="jacobi", rtol=RTOL)
        srv._dispatch_hook = lambda reqs: widths.append(len(reqs))
        fb = srv.submit("p", B[:, 0], qos="bulk")
        fi = srv.submit("p", B[:, 1], qos="interactive")
        srv.start()
        assert fb.result(180).converged and fi.result(180).converged
        srv.shutdown()
        assert widths == [2]

    def test_overload_sheds_bulk_resolves_future(self, comm8):
        """The shedding contract: with the queue full, an interactive
        arrival displaces the newest bulk request, whose future RESOLVES
        with the typed overload error (shed=True) — and the interactive
        request is admitted and answered."""
        A, Xt, B = _problem(k=5)
        srv = SolveServer(comm8, window=0.0, max_k=8, max_queue=3,
                          autostart=False)
        srv.register_operator("p", A, pc_type="jacobi", rtol=RTOL)
        bulk = [srv.submit("p", B[:, j], qos="bulk") for j in range(3)]
        f_int = srv.submit("p", B[:, 3], qos="interactive")
        # the newest bulk future is RESOLVED (typed), not dropped/hung
        assert bulk[2].done()
        exc = bulk[2].exception(0)
        assert isinstance(exc, tps.ServerOverloadedError)
        assert exc.shed and "shed" in str(exc)
        # equal-priority arrivals still reject, never shed each other
        with pytest.raises(tps.ServerOverloadedError) as ei:
            srv.submit("p", B[:, 4], qos="bulk")
        assert not ei.value.shed
        srv.start()
        res = [f.result(180) for f in (bulk[0], bulk[1], f_int)]
        srv.shutdown()
        assert all(r.converged for r in res)
        np.testing.assert_allclose(res[2].x, Xt[:, 3], atol=1e-6)
        st = srv.stats()
        assert st["shed"] == 1 and st["rejected"] == 1

    def test_interactive_never_shed_for_bulk(self, comm8):
        A, _, B = _problem(k=3)
        srv = SolveServer(comm8, window=0.0, max_queue=1,
                          autostart=False)
        srv.register_operator("p", A, rtol=RTOL)
        f_int = srv.submit("p", B[:, 0], qos="interactive")
        with pytest.raises(tps.ServerOverloadedError):
            srv.submit("p", B[:, 1], qos="bulk")
        assert not f_int.done()
        srv.shutdown(wait=True)
        assert f_int.result(0).converged

    def test_qos_class_deadline_applies(self, comm8):
        """A class deadline expires queued requests of that class: an
        autostart=False server ages the queue past the bulk deadline,
        and the expired request resolves DEADLINE_EXCEEDED."""
        A, _, B = _problem(k=2)
        tps.global_options().set("qos_bulk_deadline", "0.05")
        srv = SolveServer(comm8, window=0.0, autostart=False)
        srv.register_operator("p", A, rtol=RTOL)
        f_bulk = srv.submit("p", B[:, 0], qos="bulk")
        f_int = srv.submit("p", B[:, 1], qos="interactive")
        time.sleep(0.1)                 # age past the class deadline
        srv.start()
        with pytest.raises(tps.DeadlineExceededError):
            f_bulk.result(180)
        assert f_int.result(180).converged
        srv.shutdown()


# ------------------------------------------------------- heal -> re-grow
class TestRegrow:
    def test_grown_comm_plans_up_the_ladder(self, comm8):
        rb = _elastic.MeshRebuilder(_elastic.ElasticPolicy())
        small = tps.DeviceComm(n_devices=2)
        grown = rb.grown_comm(small, comm8)
        assert grown is not None and grown.size == 8

    def test_grown_comm_respects_lost_and_ceiling(self, comm8):
        rb = _elastic.MeshRebuilder(_elastic.ElasticPolicy())
        small = tps.DeviceComm(n_devices=2)
        try:
            # two devices still lost: the pow2 rung over 6 healthy is 4
            _faults.mark_lost(comm8.device_ids[-1])
            _faults.mark_lost(comm8.device_ids[-2])
            grown = rb.grown_comm(small, comm8)
            assert grown is not None and grown.size == 4
            lost = set(_faults.lost_devices())
            assert not (set(grown.device_ids) & lost)
        finally:
            _faults.heal()
        # never past the provisioned mesh: full-size comm cannot grow
        assert rb.grown_comm(comm8, comm8) is None
        # policy off: no upward planning at all
        rb_off = _elastic.MeshRebuilder(
            _elastic.ElasticPolicy(regrow=False))
        assert rb_off.grown_comm(small, comm8) is None

    def test_heal_epoch_and_monitor_observation(self):
        mon = _faults.HealthMonitor()
        assert not mon.heal_observed()
        _faults.mark_lost(99)
        assert not mon.heal_observed()     # loss is not a heal
        _faults.heal(99)
        assert mon.heal_observed()
        assert not mon.heal_observed()     # consumed
        assert _faults.heal() == ()        # empty heal: no epoch bump
        assert not mon.heal_observed()

    def test_retry_ladder_regrows_past_iteration_zero(self, comm8):
        """The acceptance contract: loss -> shrink (resume past 0) ->
        heal -> RE-GROW (resume past 0 ON THE RE-GROWN MESH), one
        resilient_solve, deterministic fault schedule. The second
        transient failure's backoff sleep performs the heal — the
        repair arriving while the session runs degraded."""
        A = poisson2d_csr(16)
        M = tps.Mat.from_scipy(comm8, A)
        ksp = tps.KSP().create(comm8)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.get_pc().set_type("jacobi")
        ksp.set_tolerances(rtol=1e-10)
        x_true = np.random.default_rng(0).random(A.shape[0])
        b = A @ x_true
        x, bv = M.get_vecs()
        bv.set_global(b)
        healed = []

        def sleep_heals(_d):
            if not healed:
                healed.append(_faults.heal())

        victim = comm8.device_ids[-1]
        spec = (f"device.lost=unavailable:device={victim}:at=1:iter=10,"
                "ksp.program=unavailable:at=2:times=2:iter=20")
        try:
            with tps.inject_faults(spec):
                res = tps.resilient_solve(
                    ksp, bv, x, tps.RetryPolicy(sleep=sleep_heals))
        finally:
            _faults.heal()
        kinds = [e.kind for e in res.recovery_events]
        shrinks = [e for e in res.recovery_events
                   if e.kind == "mesh_shrink"]
        regrows = [e for e in res.recovery_events
                   if e.kind == "mesh_regrow"]
        assert shrinks and regrows, kinds
        assert shrinks[0].old_devices > shrinks[0].new_devices
        assert shrinks[0].iterations > 0
        assert regrows[0].new_devices > regrows[0].old_devices
        assert regrows[0].iterations > 0, \
            "re-grown solve must resume past iteration 0"
        assert regrows[0].new_devices == comm8.size
        assert ksp.comm.size == comm8.size   # capacity fully returned
        assert res.converged
        rres = (np.linalg.norm(b - A @ x.to_numpy())
                / np.linalg.norm(b))
        assert rres <= 1e-10 * 1.05
        assert healed, "the heal hook must have run"

    def test_regrow_never_exceeds_original_mesh(self, comm8):
        """A session built on a deliberately small mesh must not be
        'grown' past it by an unrelated heal: grown_comm is bounded by
        the escalation's original comm, and a never-shrunk session has
        no re-grow rung at all."""
        A = poisson2d_csr(NX)
        small = tps.DeviceComm(n_devices=2)
        M = tps.Mat.from_scipy(small, A)
        ksp = tps.KSP().create(small)
        ksp.set_operators(M)
        ksp.set_type("cg")
        ksp.set_tolerances(rtol=RTOL)
        x_true = np.random.default_rng(1).random(A.shape[0])
        b = A @ x_true
        x, bv = M.get_vecs()
        bv.set_global(b)
        _faults.mark_lost(99)
        _faults.heal(99)          # a heal the session must NOT react to
        with tps.inject_faults("ksp.program=unavailable:at=1:iter=2"):
            res = tps.resilient_solve(ksp, bv, x, _fast_policy())
        assert res.converged
        assert ksp.comm.size == 2
        assert not any(e.kind == "mesh_regrow"
                       for e in res.recovery_events)

    def test_server_regrows_after_heal(self, comm8):
        """Serving-level capacity return: shrink adoption under a sticky
        loss, then heal -> the dispatcher's next pass re-grows every
        session and the mesh is whole again (stats record both
        directions)."""
        A, Xt, B = _problem(k=6, seed=5)
        victim = comm8.device_ids[-1]
        srv = SolveServer(comm8, window=0.003, max_k=4,
                          retry_policy=_fast_policy(), autostart=False)
        try:
            srv.register_operator("p", A, pc_type="jacobi", rtol=RTOL)
            futs = [srv.submit("p", B[:, j]) for j in range(6)]
            with tps.inject_faults(
                    f"device.lost=unavailable:device={victim}"
                    ":at=2:iter=4"):
                srv.start()
                assert srv.drain(600)
            st = srv.stats()
            assert st["mesh_shrinks"] and srv.comm.size < comm8.size
            _faults.heal()
            r = srv.solve("p", B[:, 0], timeout=300)
            st = srv.stats()
            assert st["mesh_regrows"], "heal must trigger a re-grow"
            assert st["mesh_regrows"][0]["new_devices"] == comm8.size
            assert srv.comm.size == comm8.size
            assert r.converged
            for j, f in enumerate(futs):
                rr = f.result(0)
                assert rr.converged, (j, rr)
                np.testing.assert_allclose(rr.x, Xt[:, j], atol=1e-6)
        finally:
            srv.shutdown(wait=False)
            _faults.heal()

    def test_router_heal_check(self, comm8):
        """The fleet's explicit heal hook: degraded replicas re-grow on
        demand (drain-then-rebuild), healthy replicas no-op."""
        A, _, B = _problem(k=4, seed=6)
        victim = comm8.device_ids[-1]
        rt = SolveRouter(1, comm8, window=0.003, max_k=4,
                         retry_policy=_fast_policy())

        def replica():
            return list(rt.stats()["per_replica"].values())[0]

        def rel(j, r):
            return (np.linalg.norm(B[:, j] - A @ r.x)
                    / np.linalg.norm(B[:, j]))

        try:
            rt.register_operator("p", A, pc_type="jacobi", rtol=RTOL)
            # at=1: the FIRST dispatched block hits the loss whatever
            # the coalescer decided (submits may ride one batch)
            with tps.inject_faults(
                    f"device.lost=unavailable:device={victim}"
                    ":at=1:iter=4"):
                futs = [rt.submit("p", B[:, j]) for j in range(4)]
                res = [f.result(600) for f in futs]
            assert all(r.converged for r in res)
            # fp64 parity across the shrink, which resumed past 0
            assert all(rel(j, r) <= RTOL * 1.05 for j, r in enumerate(res))
            assert rt.stats()["mesh_shrinks"] == 1
            assert replica()["mesh_shrinks"][-1]["resumed_iteration"] > 0
            assert replica()["devices"] < comm8.size
            assert rt.heal_check() == 0        # nothing healed yet
            _faults.heal()
            assert rt.heal_check() == 1        # the replica re-grew
            assert rt.stats()["mesh_regrows"] == 1
            assert replica()["devices"] == comm8.size
            r = rt.solve("p", B[:, 0], timeout=300)
            assert r.converged
            assert rel(0, r) <= RTOL * 1.05
        finally:
            rt.shutdown(wait=False)
            _faults.heal()


# ------------------------------------------------------------- transport
class TestTransport:
    """Multi-host RPC tier (serving/transport.py + serving/remote.py):
    exactly-once execution under duplicate delivery, truthful placement
    through a partitioned migration + reconcile, and checkpoint-carried
    failover resuming past iteration 0 — the ISSUE-20 acceptance
    contracts, loopback transport for determinism."""

    def _fleet(self, hosts, **kw):
        from mpi_petsc4py_example_tpu.serving.remote import FleetManager
        return FleetManager(hosts, tps.DeviceComm(), window=0.0, max_k=4,
                            retry_policy=_fast_policy(),
                            client_sleep=lambda _d: None, **kw)

    def test_duplicate_delivery_never_double_solves(self):
        """A reply dropped AFTER the handler ran (the retry joins the
        idempotency cache) and an injected request duplication must
        both execute the solve exactly once — the host call counter
        moves by one per logical request and the coalescer never sees
        a phantom request."""
        A, Xt, B = _problem(k=1)
        b = B[:, 0]
        mgr = self._fleet(1)
        try:
            mgr.register_operator("a", A, pc_type="jacobi", rtol=1e-10)
            host = mgr.hosts["r0"]
            calls0 = host.rpc.stats["calls"]
            with tps.inject_faults("rpc.recv=drop:at=1:times=1"):
                res = mgr.submit("a", b).result(timeout=120)
            assert host.rpc.stats["calls"] - calls0 == 1
            assert host.rpc.stats["duplicates"] >= 1
            np.testing.assert_allclose(res.x, Xt[:, 0], atol=1e-6)
            calls1 = host.rpc.stats["calls"]
            with tps.inject_faults("rpc.send=duplicate:at=1:times=1"):
                res2 = mgr.submit("a", b).result(timeout=120)
            assert host.rpc.stats["calls"] - calls1 == 1
            np.testing.assert_allclose(res2.x, Xt[:, 0], atol=1e-6)
            # the solve queue saw exactly the two logical requests
            assert mgr.stubs["r0"].stats()["requests"] == 2
        finally:
            mgr.shutdown(wait=False)
            _faults.heal()

    def test_migration_under_partition_reconciles(self):
        """A sticky partition of the migration destination: the move
        fails, placement stays truthful on src (which keeps serving at
        parity), and after the partition heals reconcile() removes the
        orphaned destination copy — one owner, no split brain."""
        from mpi_petsc4py_example_tpu.serving.transport import \
            TransportError
        A, Xt, B = _problem(k=1)
        b = B[:, 0]
        mgr = self._fleet(2)
        try:
            mgr.register_operator("p", A, pc_type="jacobi", rtol=1e-10)
            src = mgr.router.owner("p")
            dst = next(n for n in mgr.stubs if n != src)
            with tps.inject_faults(
                    f"rpc.recv=partition:device={int(dst[1:])}:times=*"):
                with pytest.raises((TransportError,
                                    tps.DeadlineExceededError)):
                    mgr.router.migrate("p", dst)
                assert mgr.router.owner("p") == src   # truthful
                res = mgr.submit("p", b).result(timeout=120)
                np.testing.assert_allclose(res.x, Xt[:, 0], atol=1e-6)
            rep = mgr.reconcile()
            assert rep["orphans_removed"] == [("p", dst)]
            assert mgr.router.owner("p") == src
            res_dst = mgr.stubs[dst].client.call("resident", {},
                                                 deadline=10.0)
            assert "p" not in res_dst
            res2 = mgr.submit("p", b).result(timeout=120)
            np.testing.assert_allclose(res2.x, Xt[:, 0], atol=1e-6)
        finally:
            mgr.shutdown(wait=False)
            _faults.heal()

    def test_failover_resumes_past_iteration_zero(self):
        """Kill the owning host after its checkpoint was pulled: the
        next submit fails over in-flight, re-homes the session on the
        survivor, and the warm restart provably resumes past iteration
        0 with fp64 residual parity held across the boundary."""
        A, Xt, B = _problem(k=1)
        b = B[:, 0]
        mgr = self._fleet(2)

        def rel(r):
            return np.linalg.norm(b - A @ r.x) / np.linalg.norm(b)

        try:
            mgr.register_operator("a", A, pc_type="jacobi", rtol=1e-10)
            res = mgr.submit("a", b).result(timeout=120)
            np.testing.assert_allclose(res.x, Xt[:, 0], atol=1e-6)
            assert rel(res) <= 1e-10 * 1.05
            mgr.lease_step()                  # pull the warm checkpoint
            owner = mgr.router.owner("a")
            mgr.kill_host(owner)
            res2 = mgr.submit("a", b).result(timeout=120)
            np.testing.assert_allclose(res2.x, Xt[:, 0], atol=1e-6)
            assert rel(res2) <= 1e-10 * 1.05
            assert mgr.router.owner("a") != owner
            assert mgr.failovers and mgr.failovers[0].sessions == ("a",)
            assert mgr.failovers[0].resumed_iteration > 0
        finally:
            mgr.shutdown(wait=False)
            _faults.heal()

    @pytest.mark.parametrize("transport", ["loopback", "socket"])
    def test_round_trip_parity(self, transport):
        """A burst served through a two-host fleet over each transport:
        every answer meets rtol 1e-10 in the fp64 TRUE residual."""
        A, _, B = _problem(k=3, seed=9)
        mgr = self._fleet(2, transport=transport)
        try:
            mgr.register_operator("a", A, pc_type="jacobi", rtol=1e-10)
            for j in range(B.shape[1]):
                r = mgr.solve("a", B[:, j], timeout=120)
                assert r.converged, (j, r)
                rel = (np.linalg.norm(B[:, j] - A @ r.x)
                       / np.linalg.norm(B[:, j]))
                assert rel <= 1e-10 * 1.05, (j, rel)
        finally:
            mgr.shutdown(wait=False)

    def test_suspected_host_gets_degraded_deadline(self):
        """The lease ladder's first rung: enough missed pings mark the
        host SUSPECTED, which quarters the per-call budget (degraded
        routing) without yet re-homing anything."""
        mgr = self._fleet(2)
        try:
            stub = mgr.stubs["r1"]
            full = stub._deadline()
            mgr.transports["r1"].kill()
            for _ in range(mgr.suspect_after):
                mgr.lease_step()
            table = mgr.lease_table()
            assert table["r1"]["status"] == "suspected"
            assert stub.degraded
            assert stub._deadline() == pytest.approx(full * 0.25)
        finally:
            mgr.shutdown(wait=False)

    @pytest.mark.slow
    def test_socket_round_trip_two_process(self, tmp_path):
        """A REAL two-process drill: a child process serves a
        ReplicaHost over a localhost socket; this process registers an
        operator by shipping the elastic checkpoint over the wire and
        solves to fp64 parity. Skipped where localhost sockets are
        unavailable (sandboxed CI runners)."""
        import os
        import socket
        import subprocess
        import sys
        try:
            probe = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            probe.bind(("127.0.0.1", 0))
            probe.close()
        except OSError:
            pytest.skip("localhost sockets unavailable")
        from mpi_petsc4py_example_tpu.serving.remote import RemoteReplica
        from mpi_petsc4py_example_tpu.serving.transport import (
            RpcClient, SocketTransport)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        child_src = (
            "import os, sys\n"
            "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
            "import jax\n"
            "jax.config.update('jax_enable_x64', True)\n"
            "import mpi_petsc4py_example_tpu as tps\n"
            "from mpi_petsc4py_example_tpu.serving.remote import "
            "ReplicaHost\n"
            "from mpi_petsc4py_example_tpu.serving.transport import "
            "SocketHostServer\n"
            "host = ReplicaHost(comm=tps.DeviceComm(), host_index=0,\n"
            "                   window=0.0, max_k=4)\n"
            "srv = SocketHostServer(host.rpc)\n"
            "print('PORT %d' % srv.address[1], flush=True)\n"
            "sys.stdin.readline()\n"          # parent says when to exit
            "host.server.shutdown(wait=False)\n"
            "srv.close()\n")
        env = dict(os.environ)
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        env.setdefault("JAX_PLATFORMS", "cpu")
        proc = subprocess.Popen(
            [sys.executable, "-c", child_src], cwd=repo, env=env,
            stdin=subprocess.PIPE, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True)
        try:
            line = proc.stdout.readline()
            while line and not line.startswith("PORT "):
                line = proc.stdout.readline()   # skip warnings/banners
            assert line.startswith("PORT "), \
                f"child never published its port (exited {proc.poll()})"
            port = int(line.split()[1])
            A, Xt, B = _problem(k=1)
            tr = SocketTransport(("127.0.0.1", port), host_index=0)
            client = RpcClient(tr, deadline=60.0, retry_max=2)
            stub = RemoteReplica(client, name="r0",
                                 comm=tps.DeviceComm(),
                                 solve_timeout=120.0)
            stub.register_operator("a", A, pc_type="jacobi", rtol=1e-10)
            res = stub.submit("a", B[:, 0]).result(timeout=120)
            np.testing.assert_allclose(res.x, Xt[:, 0], atol=1e-6)
            rres = (np.linalg.norm(B[:, 0] - A @ res.x)
                    / np.linalg.norm(B[:, 0]))
            assert rres <= 1e-10 * 1.05
            stub.shutdown(wait=False)
        finally:
            try:
                proc.stdin.write("quit\n")
                proc.stdin.flush()
            except OSError:
                pass
            try:
                proc.wait(timeout=30)
            except subprocess.TimeoutExpired:
                proc.kill()
