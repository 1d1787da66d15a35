"""Asynchronous two-stage multisplitting (ISSUE 17): the stale-tolerant
solver tier and its exchange.

The contract pinned here: reads of the boundary exchange NEVER block and
carry an honest staleness age; convergence is declared ONLY at a
globally consistent version cut (never on stale local norms); the
bounded-staleness supervisor resyncs partners over
``-multisplit_max_stale``; a mid-solve ``device.lost`` degrades to ONE
frozen-stale block, re-homes it, and provably never restarts from
iteration 0 (version counters stay monotonic across the loss); and the
serving tier's ``multisplit`` schedule class routes per-request solves
through the async tier with the QoS-urgent staleness tightening.
tools/chaos_smoke.py ``--multisplit`` drills the same properties under
heavier fault schedules.
"""

import io

import numpy as np
import pytest
import scipy.sparse as sp

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu.parallel.exchange import (
    ExchangeRead, StaleExchange, StalenessBoundExceeded,
    check_staleness_bound)
from mpi_petsc4py_example_tpu.resilience import faults
from mpi_petsc4py_example_tpu.solvers.multisplit import (
    BLOCK_PROGRAM_KIND, RESIDUAL_PROGRAM_KIND, MultisplitSolver)
from mpi_petsc4py_example_tpu.telemetry import metrics as _metrics


def tridiag(n, diag=4.0):
    """Block-diagonally-dominant model operator (the classical
    multisplitting convergence condition)."""
    return sp.diags([-1.0, diag, -1.0], [-1, 0, 1], shape=(n, n),
                    format="csr")


def manufactured(A, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.random(A.shape[0])
    return x, np.asarray(A @ x)


# ---------------------------------------------------------------- exchange
class TestStaleExchange:
    def test_publish_monotonic_versions(self):
        ex = StaleExchange(2)
        assert ex.publish(0, np.zeros(2)) == 1
        assert ex.publish(0, np.ones(2)) == 2
        assert ex.versions() == (2, 0)

    def test_read_never_blocks_and_carries_age(self):
        ex = StaleExchange(2)
        ex.publish(1, np.full(2, 7.0))
        r = ex.read(1, reader_version=4)
        assert isinstance(r, ExchangeRead)
        assert r.version == 1 and r.age == 3
        np.testing.assert_array_equal(r.payload, np.full(2, 7.0))
        # a fresher-than-reader neighbor clamps to age 0
        ex.publish(1, np.zeros(2))
        ex.publish(1, np.zeros(2))
        assert ex.read(1, reader_version=1).age == 0

    def test_unpublished_slot_is_maximally_stale(self):
        ex = StaleExchange(3)
        r = ex.read(2, reader_version=5)
        assert r.payload is None and r.version == 0 and r.age == 5

    def test_read_all_excludes_self(self):
        ex = StaleExchange(3)
        for b in range(3):
            ex.publish(b, np.full(1, float(b)))
        reads = ex.read_all(1, 1)
        assert set(reads) == {0, 2}

    def test_staleness_bound_check_and_strict_raise(self):
        reads = {0: ExchangeRead(None, 1, 2), 2: ExchangeRead(None, 1, 5)}
        assert check_staleness_bound(reads, 4) == (2,)
        assert check_staleness_bound(reads, 5) == ()
        with pytest.raises(StalenessBoundExceeded):
            check_staleness_bound(reads, 4, strict=True)

    def test_consistent_cut_matching_versions(self):
        ex = StaleExchange(2, history=4)
        assert ex.consistent_cut() is None          # nothing published
        ex.publish(0, np.array([1.0]))
        assert ex.consistent_cut() is None          # block 1 never did
        ex.publish(1, np.array([2.0]))
        ex.publish(0, np.array([3.0]))              # block 0 runs ahead
        cut, payloads = ex.consistent_cut()
        assert cut == 1                             # min live version
        assert payloads[0][0] == 1.0 and payloads[1][0] == 2.0

    def test_consistent_cut_refuses_pruned_history(self):
        ex = StaleExchange(2, history=2)
        ex.publish(1, np.array([0.0]))
        for k in range(5):                          # block 0 races ahead,
            ex.publish(0, np.array([float(k)]))     # ring prunes v1
        assert ex.consistent_cut() is None

    def test_mark_lost_freezes_and_serves_cut(self):
        ex = StaleExchange(2, history=4)
        ex.publish(0, np.array([1.0]))
        ex.publish(1, np.array([5.0]))
        ex.publish(1, np.array([6.0]))
        ex.mark_lost(0)
        with pytest.raises(RuntimeError):
            ex.publish(0, np.array([9.0]))
        cut, payloads = ex.consistent_cut()
        assert cut == 2                   # lost block no longer gates it
        assert payloads[0][0] == 1.0      # frozen latest serves the cut
        assert ex.lost() == frozenset({0})

    def test_republish_resumes_never_from_zero(self):
        ex = StaleExchange(2, history=4)
        for _ in range(3):
            ex.publish(0, np.zeros(1))
        ex.mark_lost(0)
        with pytest.raises(ValueError):             # regressing is refused
            ex.republish(0, np.zeros(1), version=1)
        ex.republish(0, np.ones(1))
        assert ex.version(0) == 3                   # frozen version kept
        assert ex.publish(0, np.ones(1)) == 4       # and resumes forward

    def test_wait_for_timeout_and_lost(self):
        ex = StaleExchange(2)
        assert ex.wait_for(1, 1, timeout=0.01) is False
        ex.mark_lost(1)                             # waiting is futile now
        assert ex.wait_for(1, 99, timeout=0.01) is True

    def test_exchange_put_drop_fault_counts_and_keeps_previous(self):
        ex = StaleExchange(2)
        ex.publish(0, np.array([1.0]))
        with tps.inject_faults("exchange.put=drop:device=0:times=2"):
            assert ex.publish(0, np.array([2.0])) is None
            assert ex.publish(0, np.array([3.0])) is None
            assert ex.publish(0, np.array([4.0])) == 2   # window spent
        assert ex.drops == 2
        assert ex.read(0, 0).version == 2


# ------------------------------------------------------------ timing fault
class TestCommDelayFault:
    def test_spec_parses(self):
        f, = faults.parse_spec(
            "comm.delay=delay:device=1:times=*:mean=0.02:seed=7")
        assert f.point == "comm.delay" and f.kind == "delay"
        assert f.device == 1 and f.forever and f.mean == 0.02

    def test_unseeded_clause_is_exact_and_device_filtered(self):
        with tps.inject_faults("comm.delay=delay:device=1:times=*"
                               ":mean=0.005"):
            assert faults.delay_seconds("comm.delay", device=1) == 0.005
            assert faults.delay_seconds("comm.delay", device=2) == 0.0
        assert faults.delay_seconds("comm.delay", device=1) == 0.0

    def test_seeded_draws_are_reproducible(self):
        spec = "comm.delay=delay:times=*:mean=0.01:seed=3"
        with tps.inject_faults(spec):
            a = [faults.delay_seconds("comm.delay", device=0)
                 for _ in range(4)]
        with tps.inject_faults(spec):
            b = [faults.delay_seconds("comm.delay", device=0)
                 for _ in range(4)]
        assert a == b and all(d > 0 for d in a) and len(set(a)) > 1


# ----------------------------------------------------------------- solver
class TestMultisplitSolver:
    def test_parity_against_direct_solve(self, comm8):
        A = tridiag(256)
        x_true, b = manufactured(A, seed=1)
        ms = MultisplitSolver(comm8, nblocks=4, rtol=1e-10)
        ms.set_operator(A)
        res = ms.solve(b)
        assert res.converged, res
        rres = np.linalg.norm(b - A @ res.x) / np.linalg.norm(b)
        assert rres <= 1e-10
        np.testing.assert_allclose(res.x, x_true, rtol=1e-7)

    def test_result_shape_and_history(self, comm8):
        A = tridiag(192)
        _, b = manufactured(A, seed=2)
        ms = MultisplitSolver(comm8, nblocks=3, rtol=1e-8)
        ms.set_operator(A)
        res = ms.solve(b)
        assert res.converged
        assert res.cut_version > 0 and res.iterations == res.cut_version
        assert len(res.block_steps) == 3
        assert all(s > 0 for s in res.block_steps)
        assert res.history and res.history[-1][0] == res.cut_version
        # the history is (cut_version, CONSISTENT-cut residual) pairs —
        # monotone version axis, final entry under the target
        versions = [v for v, _ in res.history]
        assert versions == sorted(versions)
        assert res.history[-1][1] <= 1e-8 * np.linalg.norm(b)
        assert res.max_stale_seen >= 0 and res.blocks_lost == 0

    def test_forcing_term_reaches_strict_tolerance(self, comm8):
        # regression: an ||rhs||-relative inner tolerance floors the
        # outer error at inner_rtol (the inner solve accepts the warm
        # start unchanged once the boundary stops moving). The two-stage
        # forcing term targets the WARM-START residual, so even a loose
        # 1e-2 inner tolerance must reach the strict fp64 outer target.
        A = tridiag(256)
        _, b = manufactured(A, seed=3)
        ms = MultisplitSolver(comm8, nblocks=4, rtol=1e-10,
                              inner_rtol=1e-2)
        ms.set_operator(A)
        res = ms.solve(b)
        assert res.converged, res
        rres = np.linalg.norm(b - A @ res.x) / np.linalg.norm(b)
        assert rres <= 1e-10, f"stalled at {rres:.3e} — forcing term broken"

    def test_warm_start_and_resolve(self, comm8):
        A = tridiag(192)
        x_true, b = manufactured(A, seed=4)
        ms = MultisplitSolver(comm8, nblocks=2, rtol=1e-9)
        ms.set_operator(A)
        cold = ms.solve(b)
        warm = ms.solve(b, x0=cold.x)
        assert warm.converged
        assert warm.cut_version <= cold.cut_version

    def test_operator_can_be_framework_mat(self, comm8):
        A = tridiag(128)
        _, b = manufactured(A, seed=5)
        ms = MultisplitSolver(comm8, nblocks=2, rtol=1e-9)
        ms.set_operator(tps.Mat.from_scipy(comm8, A))
        res = ms.solve(b)
        assert res.converged
        assert np.linalg.norm(b - A @ res.x) <= 1e-9 * np.linalg.norm(b)

    def test_bad_inputs_raise(self, comm8):
        ms = MultisplitSolver(comm8, nblocks=2)
        with pytest.raises(RuntimeError):
            ms.solve(np.zeros(4))                  # set_operator first
        with pytest.raises(ValueError):
            ms.set_operator(np.zeros((3, 4)))       # non-square
        ms.set_operator(tridiag(64))
        with pytest.raises(ValueError):
            ms.solve(np.zeros(65))                  # rhs length mismatch

    def test_flags_set_defaults_kwargs_override(self, comm8):
        opts = tps.global_options()
        opts.set("multisplit_blocks", "3")
        opts.set("multisplit_max_stale", "7")
        opts.set("multisplit_inner_type", "pipecg")
        opts.set("multisplit_inner_rtol", "1e-3")
        ms = MultisplitSolver(comm8)
        assert ms.nblocks == 3 and ms.max_stale == 7
        assert ms.inner_type == "pipecg" and ms.inner_rtol == 1e-3
        over = MultisplitSolver(comm8, nblocks=2, max_stale=1)
        assert over.nblocks == 2 and over.max_stale == 1

    def test_per_solve_stale_bound_override(self, comm8):
        A = tridiag(192)
        _, b = manufactured(A, seed=6)
        ms = MultisplitSolver(comm8, nblocks=4, rtol=1e-9, max_stale=6)
        ms.set_operator(A)
        res = ms.solve(b, max_stale=1)              # QoS-urgent tightening
        assert res.converged
        assert np.linalg.norm(b - A @ res.x) <= 1e-9 * np.linalg.norm(b)

    def test_program_kind_constants(self):
        # contracts.py PROGRAM_KINDS must keep covering the async tier
        from mpi_petsc4py_example_tpu.contracts import PROGRAM_KINDS
        assert BLOCK_PROGRAM_KIND in PROGRAM_KINDS
        assert RESIDUAL_PROGRAM_KIND in PROGRAM_KINDS


# ------------------------------------------------------------- degradation
class TestDegradation:
    def test_jitter_absorbed_with_parity(self, comm8):
        A = tridiag(256)
        _, b = manufactured(A, seed=7)
        ms = MultisplitSolver(comm8, nblocks=4, rtol=1e-9, max_stale=2)
        ms.set_operator(A)
        slow = ms._blocks[1].device_id
        try:
            with tps.inject_faults(f"comm.delay=delay:device={slow}"
                                   ":times=*:mean=0.004:seed=7"):
                res = ms.solve(b)
        finally:
            faults.heal()
        assert res.converged, res
        assert res.resyncs > 0      # the sticky straggler tripped the bound
        assert res.max_stale_seen <= 3     # bound+1: detection then resync
        assert np.linalg.norm(b - A @ res.x) <= 1e-9 * np.linalg.norm(b)

    def test_jitter_on_every_device_keeps_strict_parity(self, comm8):
        """Seeded delays on every block worker at once: staleness
        absorbs them, and the answer still meets rtol 1e-10 in the fp64
        true residual."""
        A = tridiag(256)
        _, b = manufactured(A, seed=16)
        ms = MultisplitSolver(comm8, nblocks=4, rtol=1e-10,
                              inner_rtol=1e-4)
        ms.set_operator(A)
        try:
            with tps.inject_faults(
                    "comm.delay=delay:times=*:mean=0.002:seed=16"):
                res = ms.solve(b)
        finally:
            faults.heal()
        assert res.converged, res
        assert np.linalg.norm(b - A @ res.x) <= 1e-10 * np.linalg.norm(b)

    def test_device_lost_degrades_and_never_restarts(self, comm8):
        A = tridiag(256)
        _, b = manufactured(A, seed=8)
        ms = MultisplitSolver(comm8, nblocks=4, rtol=1e-9)
        ms.set_operator(A)
        victim = ms._blocks[2].device_id
        try:
            with tps.inject_faults(
                    f"device.lost=unavailable:device={victim}:at=4"):
                res = ms.solve(b)
        finally:
            faults.heal()
        assert res.converged, res
        assert res.blocks_lost >= 1
        assert all(s > 0 for s in res.block_steps)
        # monotone version counters across the loss: every block's final
        # exchanged version covers the convergence cut — nobody rewound
        assert all(v >= res.cut_version
                   for v in ms._exchange.versions())
        assert np.linalg.norm(b - A @ res.x) <= 1e-9 * np.linalg.norm(b)

    def test_partition_costs_staleness_not_correctness(self, comm8):
        A = tridiag(192)
        _, b = manufactured(A, seed=9)
        ms = MultisplitSolver(comm8, nblocks=3, rtol=1e-9)
        ms.set_operator(A)
        try:
            with tps.inject_faults("exchange.put=drop:device=1:times=4"):
                res = ms.solve(b)
        finally:
            faults.heal()
        assert res.converged
        assert ms._exchange.drops >= 1
        assert np.linalg.norm(b - A @ res.x) <= 1e-9 * np.linalg.norm(b)


# ---------------------------------------------------------------- serving
class TestServingMultisplit:
    def test_schedule_class_and_parity(self, comm8):
        A = tridiag(192)
        x_true, b = manufactured(A, seed=10)
        srv = tps.SolveServer(comm8, max_k=2)
        try:
            sess = srv.register_operator("ms", A, rtol=1e-9,
                                         multisplit=True)
            assert sess.schedule == "multisplit"
            fut = srv.submit("ms", b)
            r = fut.result(timeout=120)
            assert r.converged, r
            rres = (np.linalg.norm(b - A @ r.x)
                    / np.linalg.norm(b))
            assert rres <= 1e-9
        finally:
            srv.shutdown(wait=True)

    def test_urgent_qos_tightens_stale_bound(self, comm8):
        tps.global_options().set("multisplit_urgent_stale", "1")
        A = tridiag(192)
        _, b = manufactured(A, seed=11)
        srv = tps.SolveServer(comm8, max_k=2)
        try:
            srv.register_operator("ms", A, rtol=1e-9, multisplit=True)
            fut = srv.submit("ms", b, qos="interactive")
            r = fut.result(timeout=120)
            assert r.converged
            assert (np.linalg.norm(b - A @ r.x)
                    <= 1e-9 * np.linalg.norm(b))
        finally:
            srv.shutdown(wait=True)

    def test_default_sessions_stay_synchronous(self, comm8):
        srv = tps.SolveServer(comm8, max_k=2)
        try:
            sess = srv.register_operator("sync", tridiag(128), rtol=1e-9)
            assert sess.schedule != "multisplit"
            assert sess.multisplit is None
        finally:
            srv.shutdown(wait=True)


# -------------------------------------------------------------- telemetry
class TestTelemetryWiring:
    def test_flags_registered(self):
        from mpi_petsc4py_example_tpu.utils.options import KNOWN_FLAGS
        for flag in ("multisplit_blocks", "multisplit_max_stale",
                     "multisplit_inner_type", "multisplit_inner_rtol",
                     "multisplit_inner_max_it", "multisplit_max_outer",
                     "multisplit_resync_timeout",
                     "multisplit_urgent_stale"):
            assert flag in KNOWN_FLAGS, flag

    def test_metric_names_registered(self):
        from mpi_petsc4py_example_tpu.telemetry.names import NAMES
        assert NAMES["multisplit.step"][0] == "counter"
        assert NAMES["multisplit.resyncs"][0] == "counter"
        assert NAMES["multisplit.block_lost"][0] == "counter"
        assert NAMES["multisplit.stale_age"][0] == "histogram"
        assert NAMES["multisplit.solve"][0] == "span"

    def test_solve_advances_counters_and_log_view_row(self, comm8):
        from mpi_petsc4py_example_tpu.utils.profiling import log_view
        _metrics.registry.reset()
        A = tridiag(192)
        _, b = manufactured(A, seed=12)
        ms = MultisplitSolver(comm8, nblocks=3, rtol=1e-8)
        ms.set_operator(A)
        res = ms.solve(b)
        assert res.converged
        # block_steps is snapshotted at convergence, before the workers
        # park — in-flight steps may still land on the counter after it
        steps = _metrics.registry.counter("multisplit.step").total()
        assert steps == sum(st.steps for st in ms._blocks)
        assert steps >= sum(res.block_steps)
        assert _metrics.registry.histogram("multisplit.stale_age").count > 0
        out = io.StringIO()
        log_view(file=out)
        text = out.getvalue()
        assert "multisplit staleness histogram" in text
        assert f"{int(steps)} step(s)" in text
