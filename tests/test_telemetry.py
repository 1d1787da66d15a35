"""Structured solve telemetry (ISSUE 11): spans, metrics registry,
flight recorder, Perfetto trace export.

Pins the layer's contracts:

* span trees: nesting + structured attributes across the full ladder —
  ksp.solve (setup/dispatch/fetch children), refine.outer -> refine.step
  -> ksp.solve, resilient.solve -> shrink (with the resumed iteration as
  a span attribute);
* registry: snapshot schema (JSON-able, typed), Prometheus text format
  (golden check), the shared Histogram.summary percentile path that
  SolveServer.stats() and profiling.serving_stats() both use;
* flight recorder: captures an injected crash + elastic shrink, ring
  truncation provably bounded;
* trace export: Chrome/Perfetto trace-event structural validity;
* the disabled path: ZERO extra XLA programs and zero extra live device
  buffers (the test_donation live-arrays idiom) — and the armed path
  adds no programs either (telemetry is pure host work);
* compile spans: what JAX traces, lowers and compiles while armed
  becomes ``compile.*`` children of the open span (of one shared
  ``compile.group`` root, one ring slot, when none is open), nothing
  while disarmed, and no listener is registered in a process that never
  arms telemetry.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mpi_petsc4py_example_tpu as tps
from mpi_petsc4py_example_tpu import telemetry
from mpi_petsc4py_example_tpu.models import poisson2d_csr
from mpi_petsc4py_example_tpu.resilience import faults as _faults
from mpi_petsc4py_example_tpu.solvers.krylov import donation_supported
from mpi_petsc4py_example_tpu.telemetry.flight import (DEFAULT_FLIGHT_LEN,
                                                      record_fault)
from mpi_petsc4py_example_tpu.utils import profiling

RTOL = 1e-8
NX = 10


@pytest.fixture(autouse=True)
def telemetry_isolation():
    """Every test starts disarmed with empty registry/ring and leaves
    the process the same way (the ring length restored)."""
    telemetry.disable()
    telemetry.reset()
    profiling.clear_events()
    yield
    telemetry.disable()
    telemetry.reset()
    telemetry.flight_recorder.set_maxlen(DEFAULT_FLIGHT_LEN)
    profiling.clear_events()


def _ksp(comm, A, pc="jacobi", rtol=RTOL):
    M = tps.Mat.from_scipy(comm, A)
    ksp = tps.KSP().create(comm)
    ksp.set_operators(M)
    ksp.set_type("cg")
    ksp.get_pc().set_type(pc)
    ksp.set_tolerances(rtol=rtol)
    return ksp, M


def _names(tree):
    yield tree["name"]
    for c in tree["children"]:
        yield from _names(c)


class TestSpans:
    def test_solve_span_tree_and_attrs(self, comm8):
        A = poisson2d_csr(NX)
        ksp, M = _ksp(comm8, A)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        telemetry.enable()
        res = ksp.solve(b, x)
        roots = telemetry.flight_recorder.spans()
        root = roots[-1]
        assert root["name"] == "ksp.solve"
        kids = [c["name"] for c in root["children"]]
        assert "ksp.dispatch" in kids and "ksp.fetch" in kids
        assert "ksp.setup" in kids
        a = root["attrs"]
        assert a["ksp_type"] == "cg" and a["pc"] == "jacobi"
        assert a["n"] == A.shape[0] and a["devices"] == comm8.size
        assert a["precision"] == "float64"
        assert a["iterations"] == res.iterations
        assert a["reduce_sites"] == 3          # plain CG schedule
        assert a["converged"] is True
        # timestamps: monotonic duration positive, children inside parent
        assert root["t1"] >= root["t0"]
        for c in root["children"]:
            assert c["t0"] >= root["t0"] and c["t1"] <= root["t1"]

    def test_refine_nests_inner_solves(self, comm8):
        import scipy.sparse as sp
        A = sp.csr_matrix(poisson2d_csr(NX))
        rk = tps.RefinedKSP(comm8)
        rk.set_inner_precision("f32")
        rk.set_operators(A)
        rk.set_type("cg")
        rk.get_pc().set_type("jacobi")
        rk.set_tolerances(rtol=1e-10)
        telemetry.enable()
        xh, res = rk.solve(np.asarray(A @ np.ones(A.shape[0])))
        assert res.converged
        outer = [t for t in telemetry.flight_recorder.spans()
                 if t["name"] == "refine.outer"][-1]
        steps = [c for c in outer["children"] if c["name"] == "refine.step"]
        assert len(steps) == rk.refine_steps
        # every step drove one inner low-precision KSP solve
        for s in steps:
            assert "ksp.solve" in [c["name"] for c in s["children"]]
            assert s["attrs"]["inner_iterations"] >= 0
        assert outer["attrs"]["inner_precision"] == "f32"
        assert outer["attrs"]["refine_steps"] == rk.refine_steps

    def test_retry_shrink_chain_with_resumed_iteration(self, comm8):
        """The ISSUE-11 acceptance shape: a permanent device loss
        mid-solve produces resilient.solve -> resilient.shrink with the
        RESUMED ITERATION as a span attribute, plus the fault +
        recovery events in the flight ring."""
        A = poisson2d_csr(16)
        ksp, M = _ksp(comm8, A, rtol=1e-10)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        victim = comm8.device_ids[-1]
        telemetry.enable()
        try:
            with tps.inject_faults(
                    f"device.lost=unavailable:device={victim}:iter=15"):
                res = tps.resilient_solve(
                    ksp, b, x, tps.RetryPolicy(sleep=lambda _d: None),
                    elastic=tps.ElasticPolicy(max_same_mesh_retries=1))
        finally:
            _faults.heal()
        assert res.converged
        roots = [t for t in telemetry.flight_recorder.spans()
                 if t["name"] == "resilient.solve"]
        assert roots, "no resilient.solve root span"
        root = roots[-1]
        shrinks = [c for c in root["children"]
                   if c["name"] == "resilient.shrink"]
        assert shrinks, list(_names(root))
        sh = shrinks[-1]["attrs"]
        assert sh["old_devices"] > sh["new_devices"]
        assert sh["resumed_iteration"] > 0
        # the nested solve attempts are children of the same root
        assert "ksp.solve" in [c["name"] for c in root["children"]]
        # the ring also holds the fault event + the recovery ladder
        faults = telemetry.flight_recorder.events("fault")
        assert any(e["data"]["point"] == "device.lost" for e in faults)
        stages = [e["data"]["stage"] for e in
                  telemetry.flight_recorder.events("recovery")]
        assert "fault" in stages and "mesh_shrink" in stages

    def test_disabled_spans_are_the_shared_noop(self):
        assert telemetry.span("ksp.solve") is telemetry.NOOP
        assert telemetry.start_span("serving.request") is telemetry.NOOP
        with telemetry.span("ksp.solve") as sp:
            sp.set_attr("x", 1).set_attrs(y=2)
        assert telemetry.flight_recorder.entries() == []

    def test_unregistered_name_rejected_when_armed(self):
        telemetry.enable()
        with pytest.raises(KeyError, match="not registered"):
            telemetry.span("no.such.span")
        with pytest.raises(KeyError, match="not registered"):
            telemetry.registry.counter("no.such.counter")


class TestRegistry:
    def test_snapshot_schema_is_jsonable_and_typed(self, comm8):
        A = poisson2d_csr(NX)
        ksp, M = _ksp(comm8, A)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        ksp.solve(b, x)               # metrics record with spans OFF too
        snap = telemetry.snapshot()
        json.dumps(snap)              # JSON-able end to end
        assert snap["solve.count"]["type"] == "counter"
        assert snap["solve.count"]["total"] >= 1
        assert "KSPSolve(cg+jacobi)" in snap["solve.count"]["values"]
        assert snap["solve.iterations"]["type"] == "counter"
        lat = snap["solve.latency_seconds"]
        assert lat["type"] == "histogram" and lat["count"] >= 1
        assert lat["buckets"][-1]["le"] == "+Inf"
        assert sum(b["count"] for b in lat["buckets"]) == lat["count"]

    def test_prometheus_text_golden(self):
        reg = telemetry.registry
        reg.counter("abft.checks").inc(5)
        reg.counter("sync.count").inc(2, label="KSP result fetch/solve")
        reg.gauge("solve.programs").set(3)
        h = reg.histogram("serving.queue_wait_seconds",
                          buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)
        golden = (
            '# HELP tpu_solve_abft_checks ABFT checksum checks performed\n'
            '# TYPE tpu_solve_abft_checks counter\n'
            'tpu_solve_abft_checks 5\n'
            '# HELP tpu_solve_serving_queue_wait_seconds submit -> '
            'dispatch wait per request\n'
            '# TYPE tpu_solve_serving_queue_wait_seconds histogram\n'
            'tpu_solve_serving_queue_wait_seconds_bucket{le="0.1"} 1\n'
            'tpu_solve_serving_queue_wait_seconds_bucket{le="1"} 2\n'
            'tpu_solve_serving_queue_wait_seconds_bucket{le="+Inf"} 2\n'
            'tpu_solve_serving_queue_wait_seconds_sum 0.55\n'
            'tpu_solve_serving_queue_wait_seconds_count 2\n'
            '# HELP tpu_solve_solve_programs jit-compiled solver '
            'programs held (KSP + EPS caches)\n'
            '# TYPE tpu_solve_solve_programs gauge\n'
            'tpu_solve_solve_programs 3\n'
            '# HELP tpu_solve_sync_count host<->device sync points by '
            'kind\n'
            '# TYPE tpu_solve_sync_count counter\n'
            'tpu_solve_sync_count{label="KSP result fetch/solve"} 2\n')
        assert telemetry.prometheus_text() == golden

    def test_kind_mismatch_rejected(self):
        with pytest.raises(ValueError, match="registered as a counter"):
            telemetry.registry.gauge("solve.count")

    def test_shared_percentile_helper_no_drift(self, comm8):
        """The dedup satellite: SolveServer.stats() and
        profiling.serving_stats() compute queue-wait percentiles through
        the SAME Histogram.summary — identical values, by construction."""
        from mpi_petsc4py_example_tpu.serving import SolveServer
        A = poisson2d_csr(NX)
        srv = SolveServer(comm8, window=0.0, max_k=4, autostart=False)
        srv.register_operator("p", A, rtol=RTOL)
        B = np.asarray(A @ np.random.default_rng(0).random(
            (A.shape[0], 3)))
        futs = [srv.submit("p", B[:, j]) for j in range(3)]
        srv.start()
        [f.result(180) for f in futs]
        srv.shutdown()
        st = srv.stats()
        ps = profiling.serving_stats()
        assert st["queue_wait_p50_s"] == ps["wait_p50_s"]
        assert st["queue_wait_p99_s"] == ps["wait_p99_s"]
        assert st["queue_wait_mean_s"] == pytest.approx(ps["wait_mean_s"])
        assert st["width_hist"] == ps["width_hist"]

    def test_log_view_prints_per_iteration_histogram_row(self, comm8,
                                                         capsys):
        import sys
        A = poisson2d_csr(NX)
        ksp, M = _ksp(comm8, A)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        ksp.solve(b, x)
        profiling.log_view(file=sys.stdout)
        out = capsys.readouterr().out
        assert "per-iteration latency histogram" in out
        assert "p50" in out and "p99" in out


class TestFlightRecorder:
    def test_crash_capture_and_ring_truncation(self, comm8):
        """An injected mid-solve crash is captured (fault event + the
        recovery ladder), and the ring provably truncates to
        -telemetry_flight_len entries, oldest first."""
        telemetry.enable(flight_len=8)
        A = poisson2d_csr(NX)
        ksp, M = _ksp(comm8, A, rtol=1e-10)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        with tps.inject_faults("ksp.program=unavailable:iter=4"):
            res = tps.resilient_solve(
                ksp, b, x, tps.RetryPolicy(sleep=lambda _d: None))
        assert res.converged and res.attempts == 2
        faults = telemetry.flight_recorder.events("fault")
        assert any(e["data"]["point"] == "ksp.program" for e in faults)
        stages = [e["data"]["stage"] for e in
                  telemetry.flight_recorder.events("recovery")]
        for want in ("fault", "checkpoint", "backoff", "resume"):
            assert want in stages, stages
        # truncation: flood the ring past its bound
        for i in range(20):
            telemetry.flight_recorder.record_event("mesh_shrink", seq=i)
        entries = telemetry.flight_recorder.entries()
        assert len(entries) == 8 == telemetry.flight_recorder.maxlen
        # only the NEWEST survive — the crash events above rolled off
        assert [e["data"]["seq"] for e in entries] == list(range(12, 20))

    def test_dump_and_auto_dump(self, comm8, tmp_path):
        telemetry.enable()
        telemetry.flight_recorder.record_event("mesh_shrink", seq=1)
        p = telemetry.flight_recorder.dump(
            str(tmp_path / "flight.json"), reason="test")
        dump = json.loads((tmp_path / "flight.json").read_text())
        assert dump["reason"] == "test" and dump["entries"]
        assert telemetry.flight_recorder.last_dump_path == p
        # auto_dump is a no-op while disarmed
        telemetry.disable()
        assert telemetry.auto_dump("x") is None

    def test_unrecovered_error_auto_dumps(self, comm8, tmp_path,
                                          monkeypatch):
        import tempfile
        monkeypatch.setattr(tempfile, "gettempdir",
                            lambda: str(tmp_path))
        telemetry.enable()
        A = poisson2d_csr(NX)
        ksp, M = _ksp(comm8, A)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        with tps.inject_faults("ksp.solve=oom:times=*"):
            with pytest.raises(tps.DeviceExecutionError):
                tps.resilient_solve(
                    ksp, b, x, tps.RetryPolicy(sleep=lambda _d: None))
        path = telemetry.flight_recorder.last_dump_path
        assert path and path.startswith(str(tmp_path))
        dump = json.loads(open(path).read())
        assert any(e.get("kind") == "fault" for e in dump["entries"])
        # the FAILED operation's own span tree is in the dump (the span
        # closes before the auto-dump fires): a post-mortem that omits
        # the dying solve's spans would answer the wrong question
        failed = [e["span"] for e in dump["entries"]
                  if e["type"] == "span"
                  and e["span"]["name"] == "resilient.solve"]
        assert failed and failed[-1]["attrs"].get("error"), failed


class TestTraceExport:
    def test_chrome_trace_structure(self, comm8, tmp_path):
        telemetry.enable()
        A = poisson2d_csr(NX)
        ksp, M = _ksp(comm8, A)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        ksp.solve(b, x)
        ksp.solve(b, x)
        out = tmp_path / "trace.json"
        doc = telemetry.export_trace(str(out))
        # the file round-trips as the same document
        assert json.loads(out.read_text()) == doc
        evs = doc["traceEvents"]
        assert evs and doc["displayTimeUnit"] == "ms"
        xs = [e for e in evs if e["ph"] == "X"]
        assert xs, "no complete (ph:X) span events"
        for e in xs:
            for key in ("name", "ts", "dur", "pid", "tid", "args"):
                assert key in e, (key, e)
            assert e["dur"] >= 0
        assert {e["name"] for e in xs} >= {"ksp.solve", "ksp.dispatch"}
        # per-thread tracks are named, counters sampled
        assert any(e["ph"] == "M" and e["name"] == "thread_name"
                   for e in evs)
        cs = [e for e in evs if e["ph"] == "C"]
        assert any(e["name"] == "solve.count" for e in cs)
        # child spans nest within their parent's [ts, ts+dur] window
        root = [e for e in xs if e["name"] == "ksp.solve"][0]
        disp = [e for e in xs if e["name"] == "ksp.dispatch"][0]
        assert root["ts"] <= disp["ts"]
        assert disp["ts"] + disp["dur"] <= root["ts"] + root["dur"] + 1


class TestServingTelemetry:
    def test_dispatch_and_linked_request_spans(self, comm8):
        from mpi_petsc4py_example_tpu.serving import SolveServer
        telemetry.enable()
        A = poisson2d_csr(NX)
        srv = SolveServer(comm8, window=0.0, max_k=4, autostart=False)
        srv.register_operator("p", A, rtol=RTOL)
        B = np.asarray(A @ np.random.default_rng(1).random(
            (A.shape[0], 3)))
        futs = [srv.submit("p", B[:, j]) for j in range(3)]
        srv.start()
        [f.result(180) for f in futs]
        srv.shutdown()
        roots = telemetry.flight_recorder.spans()
        dispatches = [t for t in roots if t["name"] == "serving.dispatch"]
        assert dispatches
        batch = dispatches[-1]
        assert batch["attrs"]["width"] == 3
        # the batch's solve ran INSIDE the dispatch span on the
        # dispatcher thread (resilient dispatch -> batched solve)
        assert "resilient.solve" in list(_names(batch))
        reqs = [t for t in roots if t["name"] == "serving.request"]
        assert len(reqs) == 3
        for r in reqs:
            assert r["attrs"]["outcome"] == "ok"
            assert r["attrs"]["batch_span"] == batch["span_id"]
            assert r["attrs"]["queue_wait"] >= 0.0

    def test_metrics_endpoint_prometheus(self, comm8):
        from mpi_petsc4py_example_tpu.serving import SolveServer
        A = poisson2d_csr(NX)
        srv = SolveServer(comm8, window=0.0, max_k=4, autostart=False)
        srv.register_operator("p", A, rtol=RTOL)
        fut = srv.submit("p", np.asarray(A @ np.ones(A.shape[0])))
        srv.start()
        fut.result(180)
        srv.shutdown()
        text = srv.metrics_endpoint()
        assert "# TYPE tpu_solve_serving_requests counter" in text
        assert "tpu_solve_serving_requests 1" in text
        assert "tpu_solve_serving_queue_wait_seconds_count 1" in text
        assert "# TYPE tpu_solve_solve_count counter" in text


class TestDisabledPathFree:
    def test_zero_extra_programs_disabled_and_armed(self, comm8):
        """The instrumented solve compiles EXACTLY the same programs
        with telemetry off and on — spans are pure host work."""
        A = poisson2d_csr(NX)
        ksp, M = _ksp(comm8, A)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        ksp.solve(b, x)              # warm: programs built
        n0 = profiling.program_count()
        for _ in range(3):
            ksp.solve(b, x)
        assert profiling.program_count() == n0
        telemetry.enable()
        res = ksp.solve(b, x)
        assert res.converged
        assert profiling.program_count() == n0

    @pytest.mark.skipif(
        not donation_supported(),
        reason="backend cannot alias donated buffers — the live-arrays "
               "population is only exactly stable with donation")
    def test_zero_extra_device_buffers(self, comm8):
        """The test_donation live-arrays idiom: repeat solves leave the
        live device-buffer population EXACTLY unchanged whether
        telemetry is disabled or armed — no hidden device allocations
        in the observability layer."""
        A = poisson2d_csr(NX)
        ksp, M = _ksp(comm8, A)
        x, b = M.get_vecs()
        b.set_global(A @ np.ones(A.shape[0]))
        for _ in range(2):
            ksp.solve(b, x)
        n0 = len(jax.live_arrays())
        for _ in range(3):
            ksp.solve(b, x)
        assert len(jax.live_arrays()) == n0
        telemetry.enable()
        for _ in range(3):
            res = ksp.solve(b, x)
        assert res.converged
        assert len(jax.live_arrays()) == n0


def _fresh_jit():
    """A jit no earlier call has compiled (a new closure each time)."""
    k = np.random.default_rng().integers(1 << 30)
    return jax.jit(lambda v: v * 2.0 + float(k))


def _run_py(code: str) -> str:
    """Run ``code`` in a fresh CPU process; its last stdout line."""
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=300,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode == 0, p.stderr[-3000:]
    return p.stdout.strip().splitlines()[-1]


class TestCompileSpans:
    COMPILE = ("compile.trace", "compile.lower", "compile.backend")

    def test_fresh_jit_gets_compile_children(self):
        f, x = _fresh_jit(), jnp.ones(4)
        x.block_until_ready()
        telemetry.enable()
        with telemetry.span("ksp.dispatch"):
            f(x).block_until_ready()
        with telemetry.span("ksp.dispatch"):
            f(x).block_until_ready()          # cached: nothing to build
        first, second = telemetry.flight_recorder.spans()[-2:]
        kids = first["children"]
        assert {c["name"] for c in kids} == set(self.COMPILE)
        for c in kids:
            assert first["t0"] <= c["t0"] <= c["t1"] <= first["t1"], c
            assert c["attrs"]["fun_name"]
        backend = [c for c in kids if c["name"] == "compile.backend"]
        assert all(c["attrs"]["cache_hit"] in (True, False)
                   for c in backend)
        assert second["children"] == []

    def test_compile_without_open_span_joins_a_group(self):
        f, x = _fresh_jit(), jnp.ones(4)
        x.block_until_ready()
        telemetry.enable()
        f(x).block_until_ready()
        _fresh_jit()(x).block_until_ready()
        (group,) = telemetry.flight_recorder.spans()
        assert group["name"] == "compile.group"
        kids = group["children"]
        names = [c["name"] for c in kids]     # traces nest: count >= 2
        assert set(names) == set(self.COMPILE)
        assert names.count("compile.backend") == 2
        assert group["t0"] == min(c["t0"] for c in kids)
        assert group["t1"] == max(c["t1"] for c in kids)

    def test_eager_compiles_keep_solves_and_faults_in_the_ring(self):
        """At the default ring length, 300 programs compiled outside any
        span take one ring slot: the solve tree and the fault event
        recorded before them stay."""
        assert telemetry.flight_recorder.maxlen == DEFAULT_FLIGHT_LEN
        x = jnp.ones(4)
        x.block_until_ready()
        telemetry.enable()
        with telemetry.span("ksp.solve"):
            pass
        record_fault("ksp.solve", "unavailable")
        for _ in range(300):
            _fresh_jit()(x).block_until_ready()
        with telemetry.span("ksp.solve"):
            pass
        kinds = [e["span"]["name"] if e["type"] == "span" else e["kind"]
                 for e in telemetry.flight_recorder.entries()]
        assert kinds == ["ksp.solve", "fault", "compile.group", "ksp.solve"]
        group = telemetry.flight_recorder.spans()[1]
        backend = [c for c in group["children"]
                   if c["name"] == "compile.backend"]
        assert len(backend) == 300

    @pytest.mark.parametrize("armed_before", [False, True],
                             ids=["never_enabled", "after_disable"])
    def test_disabled_records_nothing(self, armed_before):
        if armed_before:
            telemetry.enable()
            telemetry.disable()
        f, x = _fresh_jit(), jnp.ones(4)
        f(x).block_until_ready()
        assert telemetry.flight_recorder.entries() == []
        assert telemetry.span("ksp.solve") is telemetry.NOOP

    def test_listener_registered_only_by_enable(self):
        """A process that never arms telemetry registers nothing with
        jax.monitoring, solves included; enable() registers once."""
        out = _run_py("""
            import numpy as np
            from jax._src import monitoring
            import mpi_petsc4py_example_tpu as tps
            from mpi_petsc4py_example_tpu import telemetry
            from mpi_petsc4py_example_tpu.models import poisson2d_csr

            def ours():
                return [f for f in monitoring.get_event_listeners()
                        + monitoring.get_event_duration_listeners()
                        if f.__module__.startswith("mpi_petsc4py")]

            A = poisson2d_csr(6)
            comm = tps.DeviceComm()
            M = tps.Mat.from_scipy(comm, A)
            ksp = tps.KSP().create(comm)
            ksp.set_operators(M)
            ksp.set_type("cg")
            x, b = M.get_vecs()
            b.set_global(A @ np.ones(A.shape[0]))
            ksp.solve(b, x)
            before = len(ours())
            telemetry.enable()
            telemetry.disable()
            telemetry.enable()
            print(before, len(ours()))
            """)
        assert out == "0 2", out

    def test_persistent_cache_load_is_a_hit(self, tmp_path):
        """A program loaded back from the persistent compile cache records
        its compile.backend span with cache_hit true; the first build
        records false."""
        out = _run_py(f"""
            import jax
            import jax.numpy as jnp
            from mpi_petsc4py_example_tpu import telemetry
            # after the package import, which sets its own cache options
            jax.config.update("jax_compilation_cache_dir", {str(tmp_path)!r})
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0)
            x = jnp.arange(8.0)
            x.block_until_ready()
            telemetry.enable()

            def hits():
                with telemetry.span("ksp.dispatch"):
                    jax.jit(lambda v: v * 3.0 - 1.0)(x).block_until_ready()
                kids = telemetry.flight_recorder.spans()[-1]["children"]
                return [c["attrs"]["cache_hit"] for c in kids
                        if c["name"] == "compile.backend"]

            first = hits()
            jax.clear_caches()
            print(first, hits())
            """)
        assert out == "[False] [True]", out


class TestOptionsWiring:
    def test_flags_configure_telemetry(self, tmp_path):
        opt = tps.global_options()
        opt.set("telemetry", "1")
        opt.set("telemetry_flight_len", "17")
        telemetry.configure_from_options()
        assert telemetry.enabled()
        assert telemetry.flight_recorder.maxlen == 17

    def test_telemetry_dump_flag_writes_snapshot(self, tmp_path):
        # the atexit payload writer, exercised directly
        from mpi_petsc4py_example_tpu.telemetry import _atexit_dump
        telemetry.registry.counter("abft.checks").inc()
        path = tmp_path / "dump.json"
        _atexit_dump(str(path))
        payload = json.loads(path.read_text())
        assert payload["metrics"]["abft.checks"]["total"] == 1
        assert "flight" in payload
