"""Trace reduction: busy union, per-program sums, idle gaps and their
labels, on hand-made events and on a small trace recorded on the CPU."""
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import trace_reduce as tr  # noqa: E402

FIXTURE = os.path.join(BENCH, "fixtures", "cpu_trace.xplane.pb")
DEV = "/device:TPU:0"


def ev(name, start, end, plane=DEV, line="XLA Modules"):
    return tr.Event(plane, line, name, start, end)


def host(name, start, end):
    return ev(name, start, end, plane="/host:CPU", line="python")


def test_reduce_hand_made_events():
    events = [
        host("bench.window", 1.0, 2.0),
        host("bench.query_many", 1.0, 1.5),
        host("bench.wait", 1.5, 2.0),
        ev("jit_merge_stacks(123)", 0.9, 1.2),  # clipped to the window
        ev("jit_merge_stacks(456)", 1.1, 1.3),  # overlaps the first
        ev("jit__gather_rows(7)", 1.6, 1.7),
        ev("XlaLinearize", 1.55, 1.65, plane="/host:CPU", line="pjrt-tpu-tasks/1"),
        ev("tpu::System::TransferToDevice", 1.6, 1.68, plane="/host:CPU", line="pjrt"),
    ]
    t = tr.reduce(events)
    assert t.window_s == pytest.approx(1.0)
    assert t.busy_s == pytest.approx(0.3 + 0.1)
    assert t.devices == 1
    assert t.programs == pytest.approx({"jit_merge_stacks": 0.4, "jit__gather_rows": 0.1})
    assert t.h2d_s == pytest.approx(0.13)
    # idle: 1.3..1.6 while the query ran, 1.7..2.0 while the benchmark waited
    assert t.gaps == [("bench.query_many", pytest.approx(0.3)), ("bench.wait", pytest.approx(0.3))]
    assert sum(g for _, g in t.gaps) == pytest.approx(t.window_s - t.busy_s)
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["jit_merge_stacks", pytest.approx(0.4)]
    assert b["idle_gaps"][1] == ["bench.wait", pytest.approx(0.3)]


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        tr.reduce([ev("jit_merge_stacks(1)", 0.0, 1.0)])


def test_union_merges_overlaps_and_touching():
    assert tr.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def _cpu_ops(e):
    return (e.line.startswith("tf_XLAPjRtCpuClient") and not e.name.startswith("end:")
            and "Thunk" not in e.name)


def test_reduce_recorded_cpu_trace():
    events = tr.load_events(FIXTURE)
    t = tr.reduce(events, is_device=_cpu_ops)
    window = [e for e in events if e.name == tr.WINDOW_SPAN][0]
    ops = [e for e in events if _cpu_ops(e)]
    assert ops and t.devices == 1
    # brute force: a microsecond grid over the window
    grid = np.zeros(int(round((window.end - window.start) * 1e6)) + 1, bool)
    for e in ops:
        a = int(round((max(e.start, window.start) - window.start) * 1e6))
        b = int(round((min(e.end, window.end) - window.start) * 1e6))
        grid[a:b] = True
    assert t.busy_s == pytest.approx(grid.sum() * 1e-6, abs=5e-6 * len(ops))
    sums = {}
    for e in ops:
        sums[e.name] = sums.get(e.name, 0.0) + min(e.end, window.end) - max(e.start, window.start)
    assert t.programs == pytest.approx(sums)
    assert sum(g for _, g in t.gaps) <= t.window_s - t.busy_s + 1e-9
    # the recorder slept in bench.wait spans: the longest gaps are those
    assert t.gaps[0][0] == "bench.wait"


def test_recorded_cpu_trace_gaps_are_labelled_by_the_innermost_hist_span():
    events = tr.load_events(FIXTURE)
    before = tr.reduce(events, is_device=_cpu_ops)
    window = [e for e in events if e.name == tr.WINDOW_SPAN][0]
    busy = tr.union((max(e.start, window.start), min(e.end, window.end))
                    for e in events if _cpu_ops(e))
    s, e = max(tr.idle(busy, window.start, window.end), key=lambda g: g[1] - g[0])
    assert e - s == pytest.approx(before.gaps[0][1])
    # the program's spans over that gap's middle, nested in the benchmark's span there
    mid = (s + e) / 2
    outer = min((x for x in events if x.name.startswith("bench.") and x.name != tr.WINDOW_SPAN
                 and x.start <= mid <= x.end), key=lambda x: x.end - x.start)
    a, b = max(s, outer.start), min(e, outer.end)
    program = [tr.Event(outer.plane, outer.line, "hist.query", a, b),
               tr.Event(outer.plane, outer.line, "hist.query.wait", (a + mid) / 2, (mid + b) / 2)]
    t = tr.reduce(events + program, is_device=_cpu_ops)
    assert t.gaps[0] == ("hist.query.wait", pytest.approx(e - s))
    assert t.gaps[1:] == before.gaps[1:]
    assert tr.breakdown(t)["idle_gaps"][0] == ["hist.query.wait", pytest.approx(e - s)]
