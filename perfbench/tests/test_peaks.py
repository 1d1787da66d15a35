"""The peak table: v5e is there with its source; any other kind is an
error, never a default."""

import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader():
    spec = importlib.util.spec_from_file_location(
        "hbm_roofline_pct", os.path.join(BENCH, "metrics",
                                         "hbm_roofline_pct.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_v5e_peaks_and_source():
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)
    assert "TPU v5e" in table["source"]
    p = reader().device_peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["vmem_bytes"] == 128 * 2 ** 20


@pytest.mark.parametrize("kind", ["TPU v4", "TPU v5p", "cpu", ""])
def test_unknown_device_kind_raises(kind):
    with pytest.raises(KeyError):
        reader().device_peaks(kind)
