"""A run with the timed path broken underneath reads ``correct`` false:
a step that leaves the state unchanged, half of a batch left out, an answer
altered where it is produced, summaries coarser than the configuration
states (``bench/faults.py``).  (The cells run on one chip: no exchange
between chips to leave out.)"""
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import faults  # noqa: E402
import harness  # noqa: E402
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax

    return tiny.make_root(str(tmp_path_factory.mktemp("bench")), jax.devices()[0].device_kind)


@pytest.mark.parametrize("cell, fault", [
    ("logstats.planner", "half_of_each_panel"),
    ("logstats.planner", "altered_answer"),
    ("logstats.backfill", "state_unchanged"),
    ("logstats.backfill", "half_of_each_ingest"),
    ("logstats.backfill", "altered_answer"),
])
def test_broken_path_is_not_correct(root, monkeypatch, cell, fault):
    faults.plant(monkeypatch, fault)
    result, lines = harness.run_cell(root, cell, 123, 0.5, False, platform="cpu", cache=False)
    assert not result["correct"], lines


@pytest.mark.parametrize("cell", tiny.cells())
def test_coarse_summaries_are_not_correct(root, monkeypatch, cell):
    faults.plant(monkeypatch, "coarse_summaries")
    result, lines = harness.run_cell(root, cell, 123, 0.5, False, platform="cpu", cache=False)
    assert not result["correct"], lines
    checks = result["checks"]
    assert checks["eps_over_bound"]["value"] > checks["eps_over_bound"]["limit"], lines
