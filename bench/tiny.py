"""A tiny copy of the benchmark for rehearsals on the CPU.

:func:`make_root` lays out a checkout in a directory: this benchmark's own
files, the program's ``src`` (linked), and a ``BENCHMARK.json`` whose cells
are the real ones at tiny sizes — the same traffic kinds and readers, a few
metrics of a few windows of 256 values, β = 8.
"""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)

CONFIGS = {
    "tiny-daily": {"metrics": 4, "windows": 64, "values_per_window": 256, "beta": 8, "T": 64,
                   "values": {"distribution": "gumbel", "loc": [0.0, 100.0],
                              "scale": [0.5, 20.0]}},
}
TRAFFIC = {
    "planner": {
        "preload": "all", "check_answers": 8,
        "clients": [{"kind": "query", "batch": 12, "span": {"uniform": [1, "windows"]}}],
    },
    "backfill": {
        "preload": 0, "value_pool": 16, "check_answers": 8,
        "clients": [{"kind": "ingest_many", "windows_per_call": "windows", "lead_in_calls": 2}],
    },
}
CELLS = {
    "logstats.planner": ("tiny-daily", "planner"),
    "logstats.backfill": ("tiny-daily", "backfill"),
}


def make_root(dest: str, kind: str) -> str:
    """Write a tiny checkout under ``dest`` for devices of ``kind`` and return it."""
    root = os.path.join(dest, "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        spec = json.load(f)
    full = {c["name"]: c for c in spec["configs"]}
    configs = []
    for name, sizes in CONFIGS.items():
        base = "logstats-daily"
        with open(os.path.join(REPO, full[base]["file"])) as f:
            cfg = json.load(f)
        cfg.update(sizes, name=name)
        path = f"bench/configs/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(cfg, f)
        configs.append({**full[base], "name": name, "file": path})
    for name, mix in TRAFFIC.items():
        with open(os.path.join(root, "bench", "traffic", f"{name}.json"), "w") as f:
            json.dump(mix, f)
    for w in spec["workloads"]:
        w["config"], w["traffic"] = CELLS[w["name"]]
    spec["configs"] = configs
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    peaks_path = os.path.join(root, "bench", "peaks.json")
    with open(peaks_path) as f:
        peaks = json.load(f)
    peaks[kind] = dict(peaks["TPU v5 lite"], source="a rehearsal's stand-in")
    with open(peaks_path, "w") as f:
        json.dump(peaks, f)
    return root
