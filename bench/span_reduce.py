"""Host spans in a traced window: the program's own (``hist.*``, see
``src/repro/core/spans.py``) beside the benchmark's (``bench.*``).

From the same ``.xplane.pb`` that ``trace_reduce`` reads, on the same
clock as the device's program lines:

- ``spans``: seconds by span name, each span clipped to ``bench.window``;
- ``idle_by_span``: the first device's idle time inside the window, cut
  wherever the innermost covering span changes and summed by that span's
  name (``host`` where no span covers it);
- ``gaps``: the longest idle gaps, longest first, each labelled by the
  innermost span that covers its midpoint.

Innermost is the shortest covering span, as in ``trace_reduce``, whose
gap labels these are.

The harness removes the trace once it has reduced it, after the
per-layer readers' closing ``snapshot``.  A reader that needs spans calls
:func:`for_service` there: it finds the trace the harness wrote beside the
served service's data directory, reduces it once for all readers, and
prints the idle attribution to standard error.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import os
import sys

import trace_reduce

@dataclasses.dataclass
class SpanSummary:
    window_s: float
    idle_s: float  # the first device's idle time inside the window
    spans: dict[str, float]  # seconds by span name, clipped to the window
    idle_by_span: dict[str, float]  # idle seconds by innermost span
    gaps: list[tuple[str, float]]  # idle gaps, longest first

    def self_s(self, name: str, child: str) -> float:
        """Seconds of span ``name`` less those of its child span ``child``."""
        return self.spans.get(name, 0.0) - self.spans.get(child, 0.0)

    def idle_under(self, prefix: str) -> float:
        return sum(t for n, t in self.idle_by_span.items() if n.startswith(prefix))


def _idle(events, is_device, w0: float, w1: float) -> list[tuple[float, float]]:
    """Idle intervals of the first device inside ``[w0, w1]``."""
    dev = [e for e in events if is_device(e) and e.end > w0 and e.start < w1]
    planes = sorted({e.plane for e in dev})
    busy = trace_reduce.union(
        (max(e.start, w0), min(e.end, w1)) for e in dev if e.plane == planes[0]
    ) if planes else []
    return trace_reduce.idle(busy, w0, w1)


def _attribute(idle, labels) -> dict[str, float]:
    """Cut each idle interval where the innermost covering label changes;
    sum the pieces by label name.  One sweep over both sorted lists."""
    points = sorted({x for s, e in idle for x in (s, e)}
                    | {x for e in labels for x in (e.start, e.end)})
    by_start = sorted(labels, key=lambda e: e.start)
    active: list[tuple[float, int, trace_reduce.Event]] = []  # (length, i, span)
    out: dict[str, float] = {}
    nxt = gap = 0
    for a, b in zip(points, points[1:]):
        while gap < len(idle) and idle[gap][1] <= a:
            gap += 1
        if gap == len(idle):
            break
        while nxt < len(by_start) and by_start[nxt].start <= a:
            e = by_start[nxt]
            heapq.heappush(active, (e.end - e.start, nxt, e))
            nxt += 1
        if idle[gap][0] > a:
            continue  # [a, b] is busy
        # drop ended spans from the top: the top is then the shortest span
        # open over [a, b] (ended ones deeper down are dropped in turn)
        while active and active[0][2].end <= a:
            heapq.heappop(active)
        name = active[0][2].name if active else "host"
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def reduce(events, is_device=trace_reduce.is_device_program, top: int = 10) -> SpanSummary:
    window = [e for e in events if e.name == trace_reduce.WINDOW_SPAN]
    if not window:
        raise ValueError(f"no {trace_reduce.WINDOW_SPAN!r} span in the trace")
    w0, w1 = window[0].start, window[0].end
    labels = trace_reduce.labels(events, w0, w1)
    spans: dict[str, float] = {}
    for e in labels:
        spans[e.name] = spans.get(e.name, 0.0) + min(e.end, w1) - max(e.start, w0)
    idle = _idle(events, is_device, w0, w1)
    return SpanSummary(
        window_s=w1 - w0,
        idle_s=sum(e - s for s, e in idle),
        spans=spans,
        idle_by_span=_attribute(idle, labels),
        gaps=trace_reduce.label_gaps(idle, labels, top),
    )


def report(t: SpanSummary, top: int = 10) -> list[str]:
    """The attribution as text lines, for standard error."""
    hist = t.idle_under("hist.")
    lines = [f"idle under hist.* spans: {hist} of {t.idle_s} s "
             f"({100.0 * hist / t.idle_s if t.idle_s else 0.0}%)"]
    ranked = sorted(t.idle_by_span.items(), key=lambda kv: -kv[1])[:top]
    lines += [f"idle by span {name}: {secs} s" for name, secs in ranked]
    lines += [f"idle gap under {name}: {secs} s" for name, secs in t.gaps[:top]]
    lines += [f"span {name}: {secs} s" for name, secs in sorted(t.spans.items())]
    return lines


_REDUCED: dict[str, SpanSummary] = {}


def for_service(svc) -> SpanSummary | None:
    """The span summary of the trace written beside ``svc``'s data
    directory, or ``None`` while there is none (before the window)."""
    found = glob.glob(os.path.join(os.path.dirname(svc.data_dir), "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        return None
    path = found[0]
    if path not in _REDUCED:
        _REDUCED[path] = reduce(trace_reduce.load_events(path))
        for line in report(_REDUCED[path]):
            print(line, file=sys.stderr)
    return _REDUCED[path]
