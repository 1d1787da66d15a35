"""``trace_lower_s``: seconds of set-up in which JAX traced a program to
a jaxpr or lowered it to MLIR, Python on the host: the union of set-up's
``compile.trace`` and ``compile.lower`` spans (telemetry/
compile_events.py) over every program set-up builds (both solve
programs, the RHS pool's, the zero vector's). The union, not the sum: a
jit traced inside another's trace reports a span of its own inside the
outer one's. None where the program records no compile spans. Moves
setup_s."""

import run as _bench

NAMES = ("compile.trace", "compile.lower")


def read(run):
    return _bench.load_module("metrics", "compile_load_s").seconds_in(
        run, NAMES)
