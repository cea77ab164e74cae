"""Span reduction (``span_reduce``): span totals clipped to the window, idle
time cut by the innermost span, ``hist.*`` labels inside ``bench.*`` ones;
and the readers of the program's spans and arena counter."""
import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)

import generator  # noqa: E402
import harness  # noqa: E402
import span_reduce as sr  # noqa: E402
import trace_reduce as tr  # noqa: E402
from test_bench_trace import FIXTURE, _cpu_ops, ev, host  # noqa: E402


def _query_events():
    """One query batch, then the benchmark waiting: device busy 1.1–1.3
    and 1.6–1.7 of the window 1.0–2.0."""
    return [
        host("bench.window", 1.0, 2.0),
        host("bench.query_many", 1.0, 1.5),
        host("hist.query", 1.05, 1.49),
        host("hist.query.wait", 1.3, 1.48),
        host("bench.wait", 1.5, 2.0),
        ev("jit_merge_stacks(1)", 1.1, 1.3),
        ev("jit__gather_rows(2)", 1.6, 1.7),
    ]


def test_hist_spans_win_the_gap_label_over_an_enclosing_bench_span():
    t = sr.reduce(_query_events())
    assert t.gaps[0] == ("hist.query.wait", pytest.approx(0.3))
    # the gaps of trace_reduce, which the result line's breakdown carries, alike
    assert tr.reduce(_query_events()).gaps == t.gaps


def test_idle_is_cut_where_the_innermost_span_changes():
    t = sr.reduce(_query_events())
    assert t.idle_by_span == pytest.approx({
        "bench.query_many": 0.05 + 0.01,  # 1.0–1.05, 1.49–1.5
        "hist.query": 0.05 + 0.01,  # 1.05–1.1, 1.48–1.49
        "hist.query.wait": 0.18,  # 1.3–1.48
        "bench.wait": 0.1 + 0.3,  # 1.5–1.6, 1.7–2.0
    })
    assert sum(t.idle_by_span.values()) == pytest.approx(t.idle_s)
    assert t.idle_s == pytest.approx(0.7)
    assert t.idle_under("hist.") == pytest.approx(0.24)


def test_idle_nowhere_under_a_span_is_host():
    events = [host("bench.window", 0.0, 1.0), host("hist.ingest", 0.15, 0.4),
              ev("jit_merge_stacks(1)", 0.4, 0.5)]
    t = sr.reduce(events)
    assert t.idle_by_span == pytest.approx({"host": 0.15 + 0.5, "hist.ingest": 0.25})
    assert t.gaps == [("host", pytest.approx(0.5)), ("hist.ingest", pytest.approx(0.4))]


def test_span_totals_are_clipped_to_the_window():
    events = [host("bench.window", 1.0, 2.0), host("hist.ingest", 0.5, 1.25),
              host("hist.ingest", 1.4, 1.5), host("hist.ingest", 1.75, 2.5),
              host("hist.wal.append", 0.2, 0.9)]
    t = sr.reduce(events)
    assert t.spans == pytest.approx({"hist.ingest": 0.25 + 0.1 + 0.25})
    assert t.self_s("hist.ingest", "hist.wal.append") == pytest.approx(0.6)


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        sr.reduce([host("hist.query", 0.0, 1.0)])


def test_recorded_cpu_trace_agrees_with_trace_reduce():
    events = tr.load_events(FIXTURE)
    old = tr.reduce(events, is_device=_cpu_ops)
    t = sr.reduce(events, is_device=_cpu_ops)
    assert t.window_s == old.window_s
    assert t.idle_s == pytest.approx(old.window_s - old.busy_s, abs=1e-6)
    assert sum(t.idle_by_span.values()) == pytest.approx(t.idle_s)
    # the fixture holds bench.* spans only: the same gaps, the same labels
    assert [(n, pytest.approx(s)) for n, s in old.gaps] == t.gaps
    assert set(t.spans) == {e.name for e in events
                            if e.name.startswith(("bench.", "hist.")) and e.name != tr.WINDOW_SPAN}


def _reader(name):
    return generator.load_module(os.path.join(BENCH, "metrics", f"{name}.py"))


def _run(kind, requests, work=1):
    """A run of one client of ``kind`` (``query`` or ``ingest_many``)."""
    stats = generator.ClientStats(kind, "query" if kind == "query" else "ingest")
    stats.requests = [generator.Request(0.0, 1.0, work) for _ in range(requests)]
    cell = types.SimpleNamespace(config={"values_per_window": 10})
    return harness.Run(cell, 1.0, 0.0, [stats], 0, None, {})


def _summary(**spans):
    return sr.SpanSummary(1.0, 0.5, {k.replace("_", "."): v for k, v in spans.items()}, {}, [])


def test_span_readers_read_self_time_per_unit_of_work():
    s = _summary(hist_query=0.4, hist_query_wait=0.3, hist_wal_append=0.2,
                 hist_summarize=0.5, hist_summarize_wait=0.1, hist_pullup=0.3,
                 hist_pullup_wait=0.2)
    assert _reader("query_host_ms_per_batch").read(_run("query", 4), None, s) == pytest.approx(25.0)
    ingest = _run("ingest_many", 4, work=20)  # 4 calls of 2 windows
    assert _reader("wal_append_ms_per_ack").read(ingest, None, s) == pytest.approx(50.0)
    assert _reader("summarize_host_ms_per_window").read(ingest, None, s) == pytest.approx(50.0)
    assert _reader("pullup_host_ms_per_call").read(ingest, None, s) == pytest.approx(25.0)


@pytest.mark.parametrize("name, kind", [
    ("query_host_ms_per_batch", "query"), ("wal_append_ms_per_ack", "ingest_many"),
    ("summarize_host_ms_per_window", "ingest_many"), ("pullup_host_ms_per_call", "ingest_many"),
])
def test_span_readers_find_nothing_in_a_program_without_spans(name, kind, tmp_path):
    reader = _reader(name)
    # no trace beside the service's data directory (before the window)
    svc = types.SimpleNamespace(data_dir=str(tmp_path / "data"))
    assert reader.snapshot(svc) is None
    assert reader.read(_run(kind, 3, work=10), None, None) is None
    # a trace with only the benchmark's spans (a program without hist.* spans)
    assert reader.read(_run(kind, 3, work=10), None, _summary(bench_query_many=1.0)) is None


def test_arena_upload_reader_reads_the_counter_per_batch():
    reader = _reader("arena_upload_bytes_per_batch")
    assert reader.read(_run("query", 4), 100, 1100) == 250.0
    # a program without the counter
    assert reader.read(_run("query", 4), None, None) is None
    svc = types.SimpleNamespace(registry=types.SimpleNamespace(cache_stats=lambda: {"hits": 0}))
    assert reader.snapshot(svc) is None


def test_run_keeps_two_clients_of_one_kind_apart_and_reads_by_role():
    q1, q2 = generator.ClientStats("query", "query"), generator.ClientStats("query", "query")
    q1.requests = [generator.Request(0.0, 0.1, 3)]
    q2.requests = [generator.Request(0.0, 0.3, 5), generator.Request(1.0, 1.2, 4)]
    w = generator.ClientStats("writer", "ingest")
    w.requests = [generator.Request(0.5, 0.55, 10)]
    run = harness.Run(None, 2.0, 0.0, [q1, q2, w], 0, None, {})
    assert run.clients("query") == [q1, q2] and run.clients("ingest") == [w]
    assert run.work("query") == 12 and run.work("ingest") == 10
    assert run.latencies("query") == pytest.approx([0.1, 0.3, 0.2])
    assert _reader("query_panels_per_s").read(run, None, None) == 6.0
    assert _reader("ingest_values_per_s").read(run, None, None) == 5.0
    assert _reader("query_p95_ms").read(run, None, None) == pytest.approx(
        1e3 * harness.p95([0.1, 0.3, 0.2]))
