"""Export-cache blobs (the program's utils/aot.py) in a temporary
directory of each test's own: a test that patches a traced function and
builds its programs anew (tests/test_faults.py) must trace them, not load
a blob that another test or an earlier run left in the checkout."""

import pytest


@pytest.fixture(autouse=True)
def aot_cache_dir(tmp_path_factory, monkeypatch):
    d = str(tmp_path_factory.mktemp("aot"))
    monkeypatch.setenv("TPU_SOLVE_AOT_DIR", d)
    return d
