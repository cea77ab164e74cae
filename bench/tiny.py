"""A tiny copy of the benchmark for rehearsals on the CPU.

A cell's rehearsal is data the cell brings as files of its own, found by
name as its value model and client kinds are:

- ``bench/rehearsal/configs/<config>.json``: overrides of the cell's
  configuration file at tiny sizes (``metrics``, ``windows``,
  ``values_per_window``, ``beta``, ``T``, and the value model's keys).  Each
  key replaces the configuration's key of that name whole, so a nested
  group such as ``values`` is given whole.
- ``bench/rehearsal/traffic/<traffic>.json``: the cell's mix at tiny
  sizes, whole.

:func:`make_root` lays out a checkout in a directory from a source
checkout: its benchmark files, its ``src`` (linked), and its
``BENCHMARK.json`` as it stands, every cell's configuration and mix file
replaced by its tiny form.  A cell without its rehearsal files fails there,
before any work, naming the missing path.  :func:`cells` lists every cell,
for the tests that rehearse each one; a cell added as new files and
``BENCHMARK.json`` entries gets its rehearsal with no edit here.
"""
from __future__ import annotations

import json
import os
import shutil

BENCH = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _spec(source: str) -> dict:
    return _load(os.path.join(source, "BENCHMARK.json"))


def cells(source: str = REPO) -> list[str]:
    """The names of the cells in ``source``'s ``BENCHMARK.json``, sorted."""
    return sorted(w["name"] for w in _spec(source)["workloads"])


def _rehearsal(source: str, folder: str, name: str) -> dict:
    path = os.path.join("bench", "rehearsal", folder, f"{name}.json")
    if not os.path.isfile(os.path.join(source, path)):
        raise FileNotFoundError(f"no rehearsal file {path} for {name!r}")
    return _load(os.path.join(source, path))


def config(name: str, source: str = REPO) -> dict:
    """The configuration ``name`` of ``source`` at its rehearsal sizes."""
    entry = next(c for c in _spec(source)["configs"] if c["name"] == name)
    cfg = _load(os.path.join(source, entry["file"]))
    cfg.update(_rehearsal(source, "configs", name))
    return cfg


def traffic(name: str, source: str = REPO) -> dict:
    """The mix ``name`` of ``source`` at its rehearsal sizes."""
    return _rehearsal(source, "traffic", name)


def make_root(dest: str, kind: str, source: str = REPO) -> str:
    """Write a tiny copy of the checkout ``source`` under ``dest`` for
    devices of ``kind`` and return it."""
    spec = _spec(source)
    files = {c["name"]: c["file"] for c in spec["configs"]}
    tiny = {}  # path in the checkout -> its tiny body, every one read before any work
    for w in spec["workloads"]:
        tiny[files[w["config"]]] = config(w["config"], source)
        tiny[os.path.join("bench", "traffic", f"{w['traffic']}.json")] = traffic(w["traffic"], source)
    root = os.path.join(dest, "checkout")
    shutil.copytree(os.path.join(source, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "test_*"))
    os.symlink(os.path.join(source, "src"), os.path.join(root, "src"))
    shutil.copy(os.path.join(source, "BENCHMARK.json"), os.path.join(root, "BENCHMARK.json"))
    for path, body in tiny.items():
        with open(os.path.join(root, path), "w") as f:
            json.dump(body, f)
    peaks_path = os.path.join(root, "bench", "peaks.json")
    peaks = _load(peaks_path)
    peaks[kind] = dict(peaks["TPU v5 lite"], source="a rehearsal's stand-in")
    with open(peaks_path, "w") as f:
        json.dump(peaks, f)
    return root
