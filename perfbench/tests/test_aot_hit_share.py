"""The ``aot_hit_share`` reader on recorded span trees, in the style of
test_setup_metrics.py: only the solve-program builds count (the
``ksp.setup`` spans with an ``aot`` attribute, at any depth), and a run
whose program records no such attribute reads None, not an error."""

import pytest

import run as bench_run


def _read(spans):
    run = bench_run.Run(setup_spans=spans, setup_s=10.0)
    return bench_run.load_module("metrics", "aot_hit_share").read(run)


def _sp(name, t0, t1, *children, **attrs):
    return {"name": name, "t0": t0, "t1": t1, "attrs": attrs,
            "children": list(children)}


def _solve(t0, aot, *nested):
    """A ``ksp.solve``: its PC set-up's ``ksp.setup`` (no attribute), its
    program build's (``aot``), a dispatch and any nested re-entries."""
    return _sp("ksp.solve", t0, t0 + 1.0,
               _sp("ksp.setup", t0, t0 + 0.1, _sp("pc.setup", t0, t0 + 0.1)),
               _sp("ksp.setup", t0 + 0.1, t0 + 0.2, aot=aot),
               _sp("ksp.dispatch", t0 + 0.2, t0 + 0.9), *nested)


@pytest.mark.parametrize("aots,want", [
    (("hit", "hit"), 100.0),
    (("miss", "miss"), 0.0),
    (("hit", "fallback"), 50.0),
    (("hit", "off"), 50.0),
])
def test_share_of_program_builds(aots, want):
    spans = [_solve(2.0 * i, a) for i, a in enumerate(aots)]
    assert _read(spans) == pytest.approx(want)


def test_nested_reentry_counts():
    """The true-residual gate's re-entry is a ``ksp.solve`` nested in the
    first: its build counts like any other."""
    spans = [_solve(0.0, "hit", _solve(0.5, "miss"))]
    assert _read(spans) == pytest.approx(50.0)


@pytest.mark.parametrize("spans", [
    [],
    [_sp("ksp.solve", 0.0, 1.0, _sp("ksp.setup", 0.0, 0.5),
         _sp("ksp.dispatch", 0.5, 0.9))],
], ids=["untraced", "no_attribute"])
def test_without_the_attribute_reads_none(spans):
    assert _read(spans) is None


def test_spans_without_attrs_key():
    """The synthetic trees of the other set-up readers carry no
    ``attrs``; the reader takes them as builds without the attribute."""
    assert _read([{"name": "ksp.setup", "t0": 0.0, "t1": 1.0,
                   "children": []}]) is None
