"""Each cell end to end on the CPU at tiny sizes, through the same entry
point the chip runs use (only the device check and the compilation cache
are steered)."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import harness  # noqa: E402
import tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    import jax

    return tiny.make_root(str(tmp_path_factory.mktemp("bench")), jax.devices()[0].device_kind)


def run(root, cell, trace, seed=2**31 + 11):
    return harness.run_cell(root, cell, seed, 0.6, trace, platform="cpu", cache=False)


@pytest.mark.parametrize("trace", [False, True], ids=["e2e", "traced"])
@pytest.mark.parametrize("cell", tiny.cells())
def test_cell_runs_and_is_correct(root, cell, trace):
    result, lines = run(root, cell, trace)
    spec = harness.load_spec(root)
    wanted = [m for m in spec["per_layer" if trace else "end_to_end"]
              if cell in m.get("workloads", [cell])]
    assert result["correct"], lines
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert set(result["checks"]) == {"mass_gap", "bad_bounds", "err_over_eps", "eps_over_bound"}
    # the program reports exactly the bound the benchmark works out itself
    assert result["checks"]["eps_over_bound"]["value"] == pytest.approx(1.0)
    assert result["device"]["platform"] == "cpu"
    got = result["metrics"]
    if trace:
        # the CPU has no device plane: only the counters and host clocks read
        assert {"busy_s", "window_s"} <= set(result["device"])
        for m in wanted:
            if m["source"] != "device_trace":
                assert m["name"] in got, m["name"]
        assert all(v["value"] == 0 for k, v in got.items() if k.startswith("compiles_in_window"))
    else:
        assert {m["name"] for m in wanted} == set(got)
        assert all(v["value"] > 0 for v in got.values())
    for k, v in got.items():
        assert v["unit"] == next(m["unit"] for m in wanted if m["name"] == k)
    json.dumps(result)


def test_same_seed_same_inputs(root):
    import generator

    w, config, traffic, parts, _, _ = harness.cell_parts(root, "logstats.planner")
    a = generator.Cell(config, traffic, 5, parts)
    b = generator.Cell(config, traffic, 5, parts)
    c = generator.Cell(config, traffic, 6, parts)
    assert (a.data.pooled(1, 0, 9) == b.data.pooled(1, 0, 9)).all()
    assert not (a.data.pooled(1, 0, 9) == c.data.pooled(1, 0, 9)).all()
    assert [a.clients[0].panel() for _ in range(20)] == [b.clients[0].panel() for _ in range(20)]
