"""The benchmark is driven by data: ``BENCHMARK.json`` is well formed,
every name it holds has its file, a configuration, mix and metric
dropped in as new files are found with no edit to an existing one, and the
command refuses to run off the chip."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import control  # noqa: E402
import faults  # noqa: E402
import generator  # noqa: E402
import harness  # noqa: E402
import tiny  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec(REPO)


def test_benchmark_json_is_well_formed(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 64 * 1024
    assert spec["paths"] == ["bench"] and spec["command"][1].startswith("bench/")
    assert 1 <= spec["run_seconds"] <= 51
    configs = {c["name"]: c for c in spec["configs"]}
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(REPO, c["file"])) as f:
            body = json.load(f)
        assert c["file"].startswith("bench/") and set(c["reduced"]) == set(body["reduced"])
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [x["name"] for x in spec["configs"] + spec["workloads"]
             + spec["end_to_end"] + spec["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    cells = {w["name"]: w for w in spec["workloads"]}
    for w in cells.values():
        assert w["config"] in configs and w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert os.path.exists(os.path.join(BENCH, "traffic", f"{w['traffic']}.json"))
        harness.cell_parts(REPO, w["name"])  # its value model and client kinds have files
        reported = [m for m in spec["end_to_end"] if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
        assert any(w["name"] in m["workloads"] for m in spec["per_layer"])
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(BENCH, "metrics", f"{m['name']}.py")), m["name"]
    for m in spec["per_layer"]:
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", [cell]), (m["name"], cell)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    layers = {}
    for m in spec["per_layer"]:
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_new_config_mix_and_metric_are_found_as_files(tmp_path):
    root = tiny.make_root(str(tmp_path), "cpu")
    with open(os.path.join(root, "bench", "configs", "logstats-daily.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-extra", metrics=2, windows=24)
    with open(os.path.join(root, "bench", "configs", "tiny-extra.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench", "traffic", "scan.json"), "w") as f:
        json.dump({"preload": "all", "check_answers": 4, "clients": [
            {"kind": "query", "batch": 3, "span": {"choice": ["windows"]}}]}, f)
    with open(os.path.join(root, "bench", "metrics", "panels_in_window.py"), "w") as f:
        f.write("def read(run, before, after):\n"
                "    return sum(s.attempted for s in run.clients('query'))\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": "tiny-extra", "source": "a test", "reduced": [],
                            "file": "bench/configs/tiny-extra.json", "why": "a test"})
    spec["workloads"].append({"name": "extra.scan", "config": "tiny-extra", "traffic": "scan",
                              "chips": 1, "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] == "query_p95_ms":
            m["workloads"].append("extra.scan")
    spec["per_layer"].append({"name": "panels_in_window", "unit": "panels", "better": "higher",
                              "source": "host_clock", "layer": "load generator",
                              "moves": "query_p95_ms", "workloads": ["extra.scan"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    result, lines = harness.run_cell(root, "extra.scan", 9, 0.3, True, platform="cpu", cache=False)
    assert result["correct"], lines
    assert result["metrics"]["panels_in_window"]["value"] == result["attempted"] > 0
    result, _ = harness.run_cell(root, "extra.scan", 9, 0.3, False, platform="cpu", cache=False)
    assert set(result["metrics"]) == {"query_p95_ms", "setup_s"}


WALK = '''"""A clamped random walk on ``values.range``: ties at the clamps."""
import numpy as np

from generator import Windows


def build(config, traffic, seed):
    spec = config["values"]
    per_window = int(config["values_per_window"])
    lo, hi = spec["range"]

    def draw(rng, n):
        x = np.empty(n * per_window, np.float32)
        v = rng.uniform(lo, hi)
        for i, step in enumerate(rng.normal(0.0, spec["step"], x.size)):
            v = x[i] = min(max(v + step, lo), hi)
        return x.reshape(n, per_window)

    return Windows(config, traffic, seed, draw)
'''

WRITER = '''"""An open loop writing the next window of each metric in turn at
``rate`` windows a second through ``svc.record``, each ack timed from
when its write was due."""
import time

from generator import Panel, Request


class Client:
    role = "ingest"

    def __init__(self, spec, config, data, seed, index):
        self.data = data
        self.interval = 1.0 / float(spec["rate"])
        self.first = int(config["windows"])  # the window after the preload
        self.newest = {}  # metric -> its newest acked window

    def warm(self, open_service, load):
        with open_service() as scratch:
            load(scratch)
            for m, name in enumerate(self.data.names):
                scratch.record(name, self.first, self.data.window(m, self.first))

    def run(self, svc, t_end, annotate, stats):
        due, n = time.perf_counter(), 0
        while due < t_end:
            time.sleep(max(0.0, due - time.perf_counter()))
            m, w = n % self.data.metrics, self.first + n // self.data.metrics
            stats.attempted += 1
            with annotate("bench.record"):
                svc.record(self.data.names[m], w, self.data.window(m, w))
            stats.requests.append(Request(due, time.perf_counter(), self.data.per_window))
            self.newest[m] = w
            due, n = due + self.interval, n + 1

    def check_panels(self):
        return [Panel(self.data.names[m], m, 0, w) for m, w in self.newest.items()]
'''


def _add_cell(root, name, config, mix, e2e):
    """Add a cell as a later change would: new files, and its entries in
    ``BENCHMARK.json``."""
    with open(os.path.join(root, "bench", "configs", f"{config['name']}.json"), "w") as f:
        json.dump(config, f)
    with open(os.path.join(root, "bench", "traffic", f"{name}.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"].append({"name": config["name"], "source": "a test", "reduced": [],
                            "file": f"bench/configs/{config['name']}.json", "why": "a test"})
    spec["workloads"].append({"name": f"extra.{name}", "config": config["name"],
                              "traffic": name, "chips": 1, "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] in e2e:
            m["workloads"].append(f"extra.{name}")
    # a latency that no cell reports end to end yet (the backfill's ack tail
    # is per layer) comes in with the cell, read by its existing reader
    for metric in sorted(set(e2e) - {m["name"] for m in spec["end_to_end"]}):
        assert metric.endswith("_ms"), metric
        spec["end_to_end"].append({"name": metric, "unit": "ms", "better": "lower", "bound": 0.1,
                                   "source": "host_clock", "workloads": [f"extra.{name}"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return f"extra.{name}"


def _tiny_config(root, **changes):
    with open(os.path.join(root, "bench", "configs", "logstats-daily.json")) as f:
        cfg = json.load(f)
    cfg.update(changes)
    return cfg


def test_new_value_model_and_client_kind_are_found_as_files(tmp_path):
    root = tiny.make_root(str(tmp_path), "cpu")
    with open(os.path.join(root, "bench", "values", "walk.py"), "w") as f:
        f.write(WALK)
    with open(os.path.join(root, "bench", "clients", "writer.py"), "w") as f:
        f.write(WRITER)
    cfg = _tiny_config(root, name="tiny-walk", metrics=2, windows=16,
                       values={"distribution": "walk", "range": [0.0, 100.0], "step": 5.0})
    cell = _add_cell(root, "live", cfg, {"preload": "all", "check_answers": 6, "clients": [
        {"kind": "query", "batch": 4, "span": {"uniform": [1, 4]}},
        {"kind": "writer", "rate": 40}]}, {"query_p95_ms", "ingest_ack_p95_ms"})
    result, lines = harness.run_cell(root, cell, 2**31 + 5, 0.6, False, platform="cpu",
                                     cache=False)
    assert result["correct"], lines
    assert result["failed"] == 0
    got = result["metrics"]
    assert set(got) == {"query_p95_ms", "ingest_ack_p95_ms", "setup_s"}
    assert got["query_p95_ms"]["value"] > 0 and got["ingest_ack_p95_ms"]["value"] > 0
    # the walk clamps: its values hold ties at both ends of the range
    _w, config, traffic, parts, _e2e, _layer = harness.cell_parts(root, cell)
    values = generator.Cell(config, traffic, 1, parts).data.pooled(0, 0, 15)
    assert values.dtype == np.float32 and values.min() == 0.0 and values.max() == 100.0


@pytest.mark.parametrize("part", ["distribution", "kind"])
def test_an_unknown_part_fails_before_any_work(tmp_path, monkeypatch, part):
    root = tiny.make_root(str(tmp_path), "cpu")
    cfg = _tiny_config(root, name="tiny-odd")
    mix = {"preload": "all", "clients": [{"kind": "query", "batch": 3,
                                          "span": {"choice": [2]}}]}
    if part == "distribution":
        cfg["values"] = dict(cfg["values"], distribution="no-such-model")
    else:
        mix["clients"].append({"kind": "no-such-kind"})
    cell = _add_cell(root, "odd", cfg, mix, {"query_p95_ms"})

    def no_work(*args, **kwargs):
        raise AssertionError("work began")

    monkeypatch.setattr(generator.Cell, "__init__", no_work)
    with pytest.raises(FileNotFoundError, match="no-such-"):
        harness.run_cell(root, cell, 1, 0.1, False, platform="cpu", cache=False)


def _checkout(dest):
    """A copy of this checkout's benchmark files, as a later change starts from."""
    shutil.copytree(BENCH, dest / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest / "BENCHMARK.json")
    os.symlink(os.path.join(REPO, "src"), dest / "src")
    return str(dest)


def _bench_files(source):
    """Every file under ``source``'s ``bench/``, with its bytes."""
    files = {}
    for folder, _, names in os.walk(os.path.join(source, "bench")):
        for name in names:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                files[os.path.relpath(path, source)] = f.read()
    return files


def _write_json(source, path, body):
    with open(os.path.join(source, path), "w") as f:
        json.dump(body, f)


def test_a_cell_brought_as_new_files_gets_its_rehearsal(tmp_path, monkeypatch):
    """The contract's guard: a cell that brings a configuration, a value
    model, a client kind, a mix and its two rehearsal files, with its
    entries in ``BENCHMARK.json``, is rehearsed, controlled and faulted
    with no edit to any file the benchmark has."""
    source = _checkout(tmp_path / "source")
    before = _bench_files(source)
    with open(os.path.join(source, "bench", "values", "walk.py"), "w") as f:
        f.write(WALK)
    with open(os.path.join(source, "bench", "clients", "writer.py"), "w") as f:
        f.write(WRITER)
    cfg = _tiny_config(source, name="fleet-walk", metrics=10, windows=1440,
                       values_per_window=24000,
                       values={"distribution": "walk", "range": [0.0, 100.0], "step": 1.0})
    cell = _add_cell(source, "live", cfg, {"preload": "all", "check_answers": 32, "clients": [
        {"kind": "query", "batch": 64, "span": {"uniform": [1, 60]}},
        {"kind": "writer", "rate": 100}]}, {"query_p95_ms", "ingest_ack_p95_ms"})
    _write_json(source, "bench/rehearsal/configs/fleet-walk.json", {
        "metrics": 2, "windows": 16, "values_per_window": 256, "beta": 8, "T": 64,
        "values": {"distribution": "walk", "range": [0.0, 100.0], "step": 5.0}})
    _write_json(source, "bench/rehearsal/traffic/live.json", {
        "preload": "all", "check_answers": 6, "clients": [
            {"kind": "query", "batch": 4, "span": {"uniform": [1, 4]}},
            {"kind": "writer", "rate": 40}]})
    after = _bench_files(source)
    assert {p: after[p] for p in before} == before and len(after) == len(before) + 6
    assert cell in tiny.cells(source)

    root = tiny.make_root(str(tmp_path / "tiny"), "cpu", source)
    _w, config, traffic, _parts, _e2e, _layer = harness.cell_parts(root, cell)
    assert config["name"] == "fleet-walk" and config["values_per_window"] == 256
    assert traffic["clients"][1] == {"kind": "writer", "rate": 40}
    result, lines = harness.run_cell(root, cell, 2**31 + 7, 0.6, False, platform="cpu",
                                     cache=False)
    assert result["correct"], lines
    assert set(result["metrics"]) == {"query_p95_ms", "ingest_ack_p95_ms", "setup_s"}
    r = control.readings(root, cell, 2**31 + 7)
    assert r["control_fails"] and r["reference_passes"], r
    faults.plant(monkeypatch, "coarse_summaries")
    result, lines = harness.run_cell(root, cell, 123, 0.5, False, platform="cpu", cache=False)
    assert not result["correct"], lines


@pytest.mark.parametrize("folder", ["configs", "traffic"])
def test_a_cell_without_its_rehearsal_files_fails_in_make_root(tmp_path, folder):
    source = _checkout(tmp_path / "source")
    mix = {"preload": "all", "clients": [{"kind": "query", "batch": 3, "span": {"choice": [2]}}]}
    _add_cell(source, "bare", _tiny_config(source, name="daily-bare"), mix, {"query_p95_ms"})
    brought = {"configs": ("daily-bare", {"windows": 8}), "traffic": ("bare", mix)}
    other = "traffic" if folder == "configs" else "configs"
    name, body = brought[other]
    _write_json(source, f"bench/rehearsal/{other}/{name}.json", body)
    missing = f"bench/rehearsal/{folder}/{brought[folder][0]}.json"
    with pytest.raises(FileNotFoundError, match=re.escape(missing)):
        tiny.make_root(str(tmp_path / "tiny"), "cpu", source)
    assert not os.path.exists(tmp_path / "tiny")


def test_a_device_missing_from_the_peaks_table_fails(tmp_path):
    root = tiny.make_root(str(tmp_path), "not this device")
    with pytest.raises(KeyError, match="peaks"):
        harness.run_cell(root, "logstats.planner", 1, 0.1, False, platform="cpu", cache=False)


def test_merge_bytes_counts_the_least_work():
    sys.path.insert(0, os.path.join(BENCH, "metrics"))
    import merge_roofline

    # one panel of 3 nodes at T = 4, beta = 2: 3 rows of 5 + 4 floats read,
    # 3 + 2 floats written
    assert merge_roofline.merge_bytes(3, 1, 4, 2) == 4 * (3 * 9 + 5)
    assert merge_roofline.merge_bytes(0, 0, 2032, 254) == 0


@pytest.mark.parametrize("lo, hi", [(0, 0), (0, 511), (3, 300), (17, 18), (255, 256), (1, 1022)])
def test_cover_size_is_the_programs_decomposition(lo, hi):
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.core.interval_tree import canonical_decomposition

    assert generator.cover_size(lo, hi) == len(canonical_decomposition(lo, hi))


def _run(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "logstats.planner", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_run_refuses_without_a_tpu(tmp_path):
    p = _run(REPO, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert p.returncode == 2 and p.stdout.strip() == ""
    assert "tpu" in p.stderr


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    p = _run(str(tmp_path), {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert p.returncode != 0 and p.stdout.strip() == ""
