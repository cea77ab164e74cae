"""The control of ``correct``: the exact histogram in bfloat16 is refused
in every cell, while the same histogram in float32 passes."""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import control  # noqa: E402
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench")), "cpu")


@pytest.mark.parametrize("cell", tiny.cells())
@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_control_fails_and_reference_passes(root, cell, seed):
    r = control.readings(root, cell, seed)
    assert r["control_fails"], r
    assert r["reference_passes"], r
