#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips it names, and print its result.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: it holds the cell's chips, sets up (operator, a pool of
right-hand sides drawn on the device from the seed, the solve programs
from the compile cache), solves back to back for ``--seconds`` (a closed
loop with one client, each solve from x0 = 0 until x is ready on the
device and the KSP result is on the host), checks a seeded sample of the
solutions the window returned against the plain fp64 reference, and
prints one JSON line last. ``--trace 1`` runs the same window under the
profiler with the program's spans on and reports the per-layer metrics.

Everything that belongs to one configuration, traffic mix, operator
kind, bytes model or per-layer metric is a file of its own under
``perfbench/``, found by the names in ``BENCHMARK.json``; see PERF.md.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE = os.path.join(ROOT, ".perfbench_cache")
if ROOT not in sys.path:        # the system under test, at the checkout root
    sys.path.insert(1, ROOT)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# every key a traffic mix may set; the loop is always closed, one client
TRAFFIC_KEYS = {"about", "ksp_type", "pc_type", "rtol", "max_it",
                "true_residual_check", "rhs_pool", "check_sample"}


class BenchError(Exception):
    """The run cannot be made: exit non-zero and print no result."""


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``perfbench/<kind>/<name>.py`` as a module."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise BenchError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_traffic(name: str, traffic: dict) -> dict:
    """A traffic mix with a key the runner does not read is refused, so
    that no knob is silently ignored."""
    unread = sorted(set(traffic) - TRAFFIC_KEYS)
    if unread:
        raise BenchError(f"traffic {name!r}: the runner reads no {unread}")
    return traffic


def cell_spec(workload: str, bench: dict | None = None) -> dict:
    """The cell's entry, configuration, traffic mix and metrics."""
    if bench is None:
        path = os.path.join(ROOT, "BENCHMARK.json")
        if not os.path.exists(path):
            raise BenchError(f"no {path}")
        bench = load_json(path)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    traffic = check_traffic(cell["traffic"], load_json(
        os.path.join(HERE, "traffic", cell["traffic"] + ".json")))
    return {"cell": cell,
            "config": load_json(os.path.join(ROOT, conf["file"])),
            "traffic": traffic,
            "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
            "per_layer": [m for m in bench["per_layer"] if applies(m)]}


def set_cache_env():
    """Compile caches at fixed paths inside the checkout, set before JAX
    loads: the program takes JAX_COMPILATION_CACHE_DIR and
    TPU_SOLVE_AOT_DIR where they are set. libtpu's logs go nowhere."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(CACHE, "jax")
    os.environ["TPU_SOLVE_AOT_DIR"] = os.path.join(CACHE, "aot")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def cell_devices(chips: int, allow_cpu: bool = False):
    """The cell's chips; a run that finds no TPU, or too few, stops."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise BenchError(f"no TPU: JAX found {devs[0].platform} devices")
    peaks = load_json(os.path.join(HERE, "peaks.json"))["devices"]
    if devs[0].platform == "tpu" and devs[0].device_kind not in peaks:
        raise BenchError(f"no peaks for {devs[0].device_kind!r} in "
                         "perfbench/peaks.json")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devs)}")
    return devs[:chips]


def seed_key(seed: int):
    """A JAX key holding all of ``seed``'s bits (the driver's seeds pass
    2**32)."""
    import jax
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def _flatten(tree, depth=0, out=None):
    """``(t0, t1, name, depth)`` of a span tree, root first."""
    out = [] if out is None else out
    out.append((tree["t0"], tree["t1"], tree["name"], depth))
    for c in tree.get("children", ()):
        _flatten(c, depth + 1, out)
    return out


def judge(checked, converged, cfg: dict, traffic: dict, refmod):
    """The comparison that decides ``correct``, for a run and for its
    control alike: ``checked`` holds host ``(x, b)`` pairs of sampled
    solves, ``converged`` every solve's own claim. Returns ``(correct,
    checks, relres)``; ``checks`` holds each number beside its limit."""
    rels = [refmod.relres(x, b, cfg) for x, b in checked]
    worst = max(rels) / float(traffic["rtol"]) if rels else float("inf")
    limit = float(cfg["guarantee"]["relres_over_rtol_limit"])
    failed = sum(1 for c in converged if not c)
    checks = {"relres_over_rtol": {"value": worst, "limit": limit},
              "unconverged_solves": {"value": failed, "limit": 0}}
    return bool(rels) and worst <= limit and failed == 0, checks, rels


class Run:
    """What one run measured; the per-layer readers take it as is."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def run_cell(spec: dict, seed: int, seconds: float, trace: bool, devices,
             *, cpu: bool = False, trace_dir: str | None = None,
             log=None) -> dict:
    """Set up, warm up, measure and check one cell; returns the result."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mpi_petsc4py_example_tpu as tps
    from mpi_petsc4py_example_tpu import telemetry

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfg, traffic = spec["config"], spec["traffic"]
    chips = int(cfg["chips"])
    if trace:
        telemetry.enable(flight_len=1 << 20)

    # ---- set-up: operator, RHS pool, programs --------------------------
    comm = tps.DeviceComm(devices=list(devices))
    opmod = load_module("operators", cfg["operator"])
    refmod = load_module("references", cfg["operator"])
    op = opmod.build(cfg, comm)
    info = opmod.info(cfg)
    pool_n = int(traffic["rhs_pool"])
    sample_k = int(traffic["check_sample"])
    make = opmod.rhs_maker(cfg, comm)
    key = seed_key(seed)
    pool = [tps.Vec(comm, info["n"], data=make(key, jnp.int32(i)),
                    layout=op.layout) for i in range(pool_n)]
    zeros = opmod.zeros_maker(cfg, comm)
    order = np.random.default_rng([seed, 1]).permutation(pool_n)

    ksp = tps.KSP().create(comm)
    ksp.set_operators(op)
    ksp.set_type(traffic["ksp_type"])
    ksp.get_pc().set_type(traffic["pc_type"])
    ksp.set_tolerances(rtol=traffic["rtol"], max_it=traffic["max_it"])
    ksp.set_true_residual_check(bool(traffic["true_residual_check"]))

    def solve(b):
        x = tps.Vec(comm, info["n"], data=zeros(), layout=op.layout)
        res = ksp.solve(b, x)
        x.data.block_until_ready()
        return x, res

    # warm-up: every program the window runs, from the compile cache, at
    # one iteration each (max_it is a runtime scalar, not part of the
    # program): the solve from x0 = 0, and the true-residual gate's
    # re-entry from a nonzero guess
    ksp.set_tolerances(max_it=1)
    for guess in (False, True):
        ksp.set_initial_guess_nonzero(guess)
        solve(pool[order[0]])
    ksp.set_initial_guess_nonzero(False)
    ksp.set_tolerances(max_it=traffic["max_it"])
    jax.block_until_ready([v.data for v in pool])
    setup_s = time.perf_counter() - T_START
    setup_spans = telemetry.flight_recorder.spans() if trace else []
    log(f"setup_s={setup_s:.3f} pool={pool_n} sample={sample_k} "
        f"devices={[str(d) for d in devices]}")

    # ---- the window ----------------------------------------------------
    compiles = [0, False]

    def on_compile(event, duration, **kw):
        if compiles[1] and event == COMPILE_EVENT:
            compiles[0] += 1

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    profile = trace and not cpu     # a CPU run traces no device
    own_dir = profile and trace_dir is None
    if profile:
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-") \
            if own_dir else trace_dir
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        tracer = jax.profiler.trace(trace_dir, profiler_options=opts)
        annotate = jax.profiler.TraceAnnotation
    else:
        tracer = contextlib.nullcontext()
        annotate = lambda name: contextlib.nullcontext()  # noqa: E731
    rng = np.random.default_rng([seed, 2])
    records, samples, anchors = [], [], []
    compiles[1] = True
    with tracer:
        with annotate("perfbench.window"):
            t_start = time.perf_counter()
            deadline = t_start + seconds
            i = 0
            while True:
                pi = int(order[i % pool_n])
                t0 = time.perf_counter()
                anchors.append(t0)
                with annotate("perfbench.solve"):
                    x, res = solve(pool[pi])
                t1 = time.perf_counter()
                records.append({"pool": pi, "wall": t1 - t0,
                                "iterations": int(res.iterations),
                                "converged": bool(res.converged),
                                "reason": int(res.reason)})
                # a reservoir sample of the window's solutions, from the seed
                if len(samples) < sample_k:
                    samples.append((i, pi, x.data))
                else:
                    j = int(rng.integers(0, i + 1))
                    if j < sample_k:
                        samples[j] = (i, pi, x.data)
                del x
                i += 1
                if t1 >= deadline:
                    break
            t_end = t1
    compiles[1] = False
    window_s = t_end - t_start
    n = len(records)
    log(f"compiles_in_window={compiles[0]}")
    print(f"compiles_in_window={compiles[0]}", flush=True)

    mem = [d.memory_stats() or {} for d in devices]
    memory_peak = max(int(m.get("peak_bytes_in_use", 0)) for m in mem)
    window_spans = []
    red = None
    if profile:
        spans = telemetry.flight_recorder.spans()
        window_spans = [s for s in spans if s["t0"] >= t_start]
        telemetry.disable()
        flat = [f for s in window_spans for f in _flatten(s)]
        if not own_dir:     # a kept trace keeps what labels its gaps
            with open(os.path.join(trace_dir, "spans.json"), "w") as f:
                json.dump({"anchors": anchors, "spans": flat}, f)
        tr = load_module("", "trace_reduce")
        red = tr.reduce(tr.find_xplane(trace_dir), anchors=anchors,
                        spans=flat)
        if own_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    # ---- the check: fp64 reference on the sampled solutions ------------
    host = [(np.asarray(xd), np.asarray(pool[pi].data))
            for _, pi, xd in samples]
    checked = [s[0] for s in samples]
    del samples, pool, ksp, op
    correct, checks, rels = judge(host, [r["converged"] for r in records],
                                  cfg, traffic, refmod)
    failed = checks["unconverged_solves"]["value"]
    log(f"checked solves {checked} of {n}: relres "
        f"{[f'{r:.4e}' for r in rels]}")

    # ---- metrics ---------------------------------------------------------
    d0 = devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    walls = [r["wall"] for r in records]
    run = Run(spec=spec, cfg=cfg, traffic=traffic, info=info, chips=chips,
              solves=records, window_s=window_s, setup_s=setup_s,
              setup_spans=setup_spans, window_spans=window_spans,
              trace=red, device_kind=d0.device_kind)
    metrics = {}
    if cpu:
        pass        # no device number from a CPU run, under any name
    elif not trace:
        e2e = {"setup_s": setup_s, "solve_s": window_s / n}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    else:
        for m in spec["per_layer"]:
            v = load_module("metrics", m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
    result = {"correct": correct, "attempted": n, "failed": failed,
              "metrics": metrics, "device": device}
    if profile:
        result["breakdown"] = tr.breakdown(red)
    result["checks"] = checks
    log(f"first walls {[round(w, 4) for w in walls[:3]]} median "
        f"{statistics.median(walls):.4f} max {max(walls):.4f}")
    log(f"solves={n} window_s={window_s:.4f} solve_s={window_s / n:.6f} "
        f"iterations={sorted({r['iterations'] for r in records})} "
        f"memory_peak_bytes={memory_peak}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the trace here (default: a temporary dir)")
    args = ap.parse_args(argv)
    try:
        spec = cell_spec(args.workload)
        set_cache_env()
        devices = cell_devices(int(spec["config"]["chips"]))
        result = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                          devices, trace_dir=args.trace_dir)
    except Exception:  # noqa: BLE001 — any failure: no result, non-zero
        traceback.print_exc()
        return 1
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
