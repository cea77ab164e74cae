"""The served path's host spans (core/spans.py) and the counters beside
them: every span named in ``src/`` is declared and every declared span has
a site and a row in PERF.md's span table; a traced service records each
call's phases nested inside the call's top span, on one thread line."""
import ast
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.core import spans
from repro.serve import HistogramService

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every span's enclosing span in an ingest_many then query_many
PARENT = {
    "hist.tenant.create": "hist.ingest",
    "hist.wal.append": "hist.ingest",
    "hist.wal.roll": "hist.wal.append",
    "hist.wal.fsync": "hist.ingest",
    "hist.summarize": "hist.ingest",
    "hist.summarize.pack": "hist.summarize",
    "hist.summarize.upload": "hist.summarize",
    "hist.summarize.wait": "hist.summarize",
    "hist.pullup": "hist.ingest",
    "hist.pullup.pack": "hist.pullup",
    "hist.pullup.wait": "hist.pullup",
    "hist.pullup.write": "hist.pullup",
    "hist.ingest.finish": "hist.ingest",
    "hist.query.select": "hist.query",
    "hist.query.pack": "hist.query",
    "hist.arena.upload": "hist.query.pack",
    "hist.query.merge": "hist.query",
    "hist.query.wait": "hist.query",
    "hist.query.assemble": "hist.query",
}
TOP = {"hist.ingest", "hist.query"}


def _span_calls():
    """``(name or None, path, line)`` of every ``span(...)`` call in src/."""
    out = []
    for path in glob.glob(os.path.join(REPO, "src", "**", "*.py"), recursive=True):
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if (f.id if isinstance(f, ast.Name) else getattr(f, "attr", None)) != "span":
                continue
            arg = node.args[0] if node.args else None
            name = arg.value if isinstance(arg, ast.Constant) else None
            out.append((name, os.path.relpath(path, REPO), node.lineno))
    return out


def test_every_span_in_src_is_a_declared_literal():
    calls = _span_calls()
    assert calls
    assert [c for c in calls if c[0] is None] == []
    assert {c[0] for c in calls} <= spans.SITES, sorted({c[0] for c in calls} - spans.SITES)


def test_every_declared_span_has_a_site():
    assert spans.SITES <= {c[0] for c in _span_calls()}


def test_perf_md_span_table_lists_every_span():
    with open(os.path.join(REPO, "PERF.md")) as f:
        rows = set(re.findall(r"^\| `(hist\.[a-z.]+)`", f.read(), re.M))
    assert rows == spans.SITES


def test_the_parent_table_covers_every_span():
    assert set(PARENT) | TOP == spans.SITES


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One ``ingest_many`` of six windows, then two ``query_many`` batches,
    under a profiler trace; returns the ``hist.*`` events and the service's
    counters."""
    from jax.profiler import ProfileData

    d = tmp_path_factory.mktemp("spans")
    svc = HistogramService(str(d / "data"), num_buckets=16, shared_arena=True)
    svc.registry._wal.segment_bytes = 4096  # a few rolls inside one call
    rng = np.random.default_rng(0)
    parts = {w: rng.standard_normal(300).astype(np.float32) for w in range(6)}
    jax.profiler.start_trace(str(d / "trace"))
    try:
        svc.registry.ingest_many("m", parts)
        first = svc.query_many([("m", 0, 5), ("m", 1, 3)], beta=4)
        second = svc.query_many([("m", 0, 5), ("m", 2, 4)], beta=4)
    finally:
        jax.profiler.stop_trace()
    counters = {"wal": svc.wal_stats(), "cache": svc.registry.cache_stats(),
                "plane_bytes": 4 * (2 * 16 + 1) * svc.registry.arena._planes[16].capacity}
    svc.close()
    assert all(a[0] is not None for a in first + second)
    (path,) = glob.glob(str(d / "trace" / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("hist."):
                    events.append({"name": e.name, "line": (plane.name, line.name),
                                   "start": e.start_ns, "end": e.start_ns + e.duration_ns,
                                   "stats": dict(e.stats)})
    return events, counters


def test_every_span_is_recorded(traced):
    events, _ = traced
    assert {e["name"] for e in events} == spans.SITES


def test_phases_nest_inside_their_parent_on_one_thread_line(traced):
    events, _ = traced
    for c in events:
        if c["name"] in TOP:
            continue
        around = [p for p in events if p is not c and p["line"] == c["line"]
                  and p["start"] <= c["start"] and c["end"] <= p["end"]]
        assert around, c["name"]
        inner = min(around, key=lambda p: p["end"] - p["start"])
        assert inner["name"] == PARENT[c["name"]], (c["name"], inner["name"])


def test_top_spans_carry_the_call_and_its_counts(traced):
    events, _ = traced
    top = sorted((e for e in events if e["name"] in TOP), key=lambda e: e["start"])
    assert [e["name"] for e in top] == ["hist.ingest", "hist.query", "hist.query"]
    assert len({e["stats"]["call"] for e in top}) == 3
    assert top[0]["stats"]["windows"] == 6 and top[0]["stats"]["values"] == 1800
    # the second batch finds (m, 0, 5) in the answer cache
    assert [(e["stats"]["queries"], e["stats"]["misses"], e["stats"]["hits"])
            for e in top[1:]] == [(2, 2, 0), (2, 1, 1)]


def test_wal_rolls_are_counted_apart_from_commit_fsyncs(traced):
    events, counters = traced
    wal = counters["wal"]
    rolls = sum(1 for e in events if e["name"] == "hist.wal.roll")
    # the first span opens the log's first segment; each later one rolls
    assert wal["rolls"] == rolls - 1 >= 1
    assert wal["roll_fsync_seconds_total"] > 0
    assert wal["fsyncs"] == 1  # the one group commit of the call


def test_arena_uploads_are_counted_once_per_plane_change(traced):
    events, counters = traced
    cache = counters["cache"]
    assert cache["device_uploads"] == 1
    assert cache["device_upload_bytes"] == counters["plane_bytes"]
    (up,) = [e for e in events if e["name"] == "hist.arena.upload"]
    assert up["stats"]["bytes"] == counters["plane_bytes"]
