"""The trace reduction on a small recorded v5e trace (``data/``: the
512x512x2048 CG+MG on four chips, a 0.3 s traced window, PR 22; the cell
itself left the benchmark, see PERF.md Open questions), with the spans and anchors
that run.py kept beside it."""

import gzip
import json
import os
import shutil

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
# read from the recorded trace once, by hand and by this reduction (PR 22)
BUSY_S = 0.34859285575
WINDOW_S = 0.354560955
PALLAS_S = 0.1384379805
COLLECTIVE_S = 0.00425348425
TOP_GAP = "ksp.fetch"


@pytest.fixture(scope="module")
def red(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "p3d2048z-x4.xplane.pb"
    with gzip.open(os.path.join(DATA, "p3d2048z-x4.xplane.pb.gz")) as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    with open(os.path.join(DATA, "p3d2048z-x4.spans.json")) as f:
        kept = json.load(f)
    spans = [tuple(s) for s in kept["spans"]]
    return tr.reduce(str(path), anchors=kept["anchors"], spans=spans)


@pytest.mark.parametrize("text,expect", [
    ('%stencil3d_dot_pallas.9 = (f32[256,256,256]{2,1,0:T(8,128)}, '
     'f32[1]{0:T(128)}) custom-call(f32[256,256,256]{2,1,0:T(8,128)} %a), '
     'custom_call_target="tpu_custom_call"',
     ("stencil3d_dot_pallas", "custom-call", "pallas")),
    ('%add_select_fusion.3 = (f32[8]{0}, f32[8]{0}) fusion(f32[8]{0} %a), '
     'kind=kLoop', ("add_select_fusion", "fusion", "xla")),
    ('%all-reduce.1 = f32[1]{0:T(128)} all-reduce(f32[1]{0} %p), '
     'replica_groups={{0,1,2,3}}', ("all-reduce", "all-reduce",
                                    "collective")),
    ('%collective-permute-done.2 = f32[1,512,512]{2,1,0:T(8,128)} '
     'collective-permute-done((f32[1,512,512]) %s)',
     ("collective-permute-done", "collective-permute-done", "collective")),
    ('%while.28 = (pred[]{:T(512)}, s32[]) while((pred[], s32[]) %t), '
     'condition=%c', ("while", "while", "xla")),
])
def test_parse_op(text, expect):
    assert tr.parse_op(text) == expect


def test_devices_and_window(red):
    assert red["devices"] == [f"/device:TPU:{i}" for i in range(4)]
    assert 0 < red["busy_s"] <= red["window_s"]
    assert all(0 < b <= red["window_s"] for b in red["busy_s_per_device"])
    assert red["busy_s"] == pytest.approx(BUSY_S, rel=1e-9)
    assert red["window_s"] == pytest.approx(WINDOW_S, rel=1e-9)


def test_categories(red):
    cats = red["category_s"]
    assert set(cats) <= {"pallas", "collective", "xla"}
    assert cats["pallas"] > 0 and cats["collective"] > 0
    # leaf ops may overlap (async halves), so their sum may pass busy time
    # only by that overlap
    assert sum(cats.values()) <= 1.1 * red["busy_s"]
    assert cats["pallas"] == pytest.approx(PALLAS_S, rel=1e-9)
    assert cats["collective"] == pytest.approx(COLLECTIVE_S, rel=1e-9)


def test_gap_labels(red):
    idle = red["idle_by_label_s"]
    assert red["clock_offset_ns"] is not None
    assert sum(idle.values()) == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    # the program's spans label the gaps, not only the benchmark's own
    assert any(k.startswith("ksp.") for k in idle)
    assert max(idle, key=idle.get) == TOP_GAP


def test_breakdown_shape(red):
    bd = tr.breakdown(red)
    assert set(bd) == {"device_ops", "idle_gaps"}
    for key in bd:
        assert 0 < len(bd[key]) <= 10
        assert all(isinstance(n, str) and v > 0 for n, v in bd[key])
    vals = [v for _, v in bd["device_ops"]]
    assert vals == sorted(vals, reverse=True)
