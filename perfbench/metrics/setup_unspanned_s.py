"""``setup_unspanned_s``: the part of ``setup_s`` that no program span
covers: ``setup_s`` less the union of set-up's root spans (each
``ksp.solve``; a ``compile.group`` root counts as its ``compile.*``
children, the programs built outside a solve, and not as the gaps
between them). What is left is process start: imports, the backend's
start, the benchmark's own steps between the program's calls. Moves
setup_s."""

import run as _bench

GROUP = "compile.group"


def _intervals(roots):
    for s in roots:
        if s["name"] == GROUP:
            yield from ((c["t0"], c["t1"]) for c in s["children"])
        else:
            yield s["t0"], s["t1"]


def read(run):
    if not run.setup_spans:
        return None
    union_s = _bench.load_module("metrics", "compile_load_s").union_s
    return run.setup_s - union_s(_intervals(run.setup_spans))
