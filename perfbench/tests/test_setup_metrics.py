"""The set-up readers (``trace_lower_s``, ``compile_load_s``,
``setup_unspanned_s``) on synthetic span trees: a trace nested in
another's counts once, the three never count a second twice, and a run
with no compile spans (a program without them) reads None, not an
error."""

import pytest

import run as bench_run


def _read(name, spans, setup_s=10.0):
    run = bench_run.Run(setup_spans=spans, setup_s=setup_s)
    return bench_run.load_module("metrics", name).read(run)


def _sp(name, t0, t1, *children):
    return {"name": name, "t0": t0, "t1": t1, "children": list(children)}


# two warm-up solves with their programs' compile spans, and a pool
# program built outside any span (a compile.group root over 1.0-1.6)
SPANS = [
    _sp("compile.group", 1.0, 1.6,
        _sp("compile.trace", 1.0, 1.2),
        _sp("compile.lower", 1.2, 1.3),
        _sp("compile.backend", 1.3, 1.6)),
    _sp("ksp.solve", 2.0, 5.0,
        _sp("ksp.setup", 2.0, 2.5,
            _sp("pc.setup", 2.1, 2.4)),
        _sp("ksp.dispatch", 2.5, 4.9,
            _sp("compile.trace", 2.6, 3.6),
            _sp("compile.trace", 2.8, 3.0),      # a jit inside the trace
            _sp("compile.lower", 3.6, 3.8),
            _sp("compile.backend", 3.8, 4.8))),
    _sp("ksp.solve", 6.0, 7.0,
        _sp("ksp.dispatch", 6.0, 6.9,
            _sp("compile.trace", 6.1, 6.3),
            _sp("compile.lower", 6.3, 6.4),
            _sp("compile.backend", 6.4, 6.8))),
]


def test_nested_trace_counts_once():
    assert _read("trace_lower_s", SPANS) == pytest.approx(
        0.3 + 1.2 + 0.3)
    assert _read("compile_load_s", SPANS) == pytest.approx(
        0.3 + 1.0 + 0.4)


def test_unspanned_is_setup_less_roots():
    assert _read("setup_unspanned_s", SPANS) == pytest.approx(
        10.0 - (0.6 + 3.0 + 1.0))


def test_group_counts_its_spans_not_its_gaps():
    """Two programs built outside any span, 2 s apart, share one
    compile.group root; the gap between them is unspanned."""
    group = [_sp("compile.group", 1.0, 4.0,
                 _sp("compile.trace", 1.0, 1.5),
                 _sp("compile.backend", 1.5, 2.0),
                 _sp("compile.trace", 3.0, 3.5),
                 _sp("compile.backend", 3.5, 4.0))]
    assert _read("setup_unspanned_s", group) == pytest.approx(10.0 - 2.0)
    assert _read("trace_lower_s", group) == pytest.approx(1.0)
    assert _read("compile_load_s", group) == pytest.approx(1.0)


def test_nothing_counted_twice():
    parts = sum(_read(m, SPANS) for m in
                ("trace_lower_s", "compile_load_s", "setup_unspanned_s"))
    assert parts <= 10.0


def test_program_without_compile_spans():
    plain = [_sp("ksp.solve", 2.0, 5.0, _sp("ksp.dispatch", 2.5, 4.9))]
    assert _read("trace_lower_s", plain) is None
    assert _read("compile_load_s", plain) is None
    assert _read("setup_unspanned_s", plain) == pytest.approx(7.0)


@pytest.mark.parametrize("name", ["trace_lower_s", "compile_load_s",
                                  "setup_unspanned_s"])
def test_untraced_run_reads_none(name):
    assert _read(name, []) is None
