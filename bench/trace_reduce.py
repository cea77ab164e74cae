"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time,
per-program device time, host transfer time and labelled idle gaps.

The device lines are the ``XLA Modules`` lines of ``/device:*`` planes: one
event per program run, named ``<program>(<fingerprint>)``.  The window is
the benchmark's own ``bench.window`` host span; every interval is clipped
to it.  Idle gaps are labelled by the innermost host span, the
benchmark's (``bench.*``) or the program's (``hist.*``), that covers the
gap's midpoint (``host`` when none does): innermost is the shortest, and
spans on one thread nest, so that is the deepest one.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

WINDOW_SPAN = "bench.window"
# host spans that name what the host was doing: the benchmark's own and
# the program's (src/repro/core/spans.py)
PREFIXES = ("bench.", "hist.")
# host events that move arrays to the device (the PJRT TPU client's names)
H2D_EVENTS = ("tpu::System::TransferToDevice", "XlaLinearize")


@dataclasses.dataclass
class Event:
    plane: str
    line: str
    name: str
    start: float  # seconds
    end: float


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # union of device program intervals, averaged over devices
    devices: int
    programs: dict[str, float]  # device seconds by program name
    h2d_s: float  # union of host transfer-to-device intervals
    gaps: list[tuple[str, float]]  # idle gaps, longest first

    def program_s(self, prefix: str) -> float:
        return sum(t for name, t in self.programs.items() if name == prefix)


def load_events(path: str) -> list[Event]:
    """Every event of the trace at ``path`` (a file, or a directory that holds one)."""
    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
        if len(found) != 1:
            raise FileNotFoundError(f"expected one .xplane.pb under {path}, found {found}")
        path = found[0]
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                out.append(Event(plane.name, line.name, e.name, s, s + e.duration_ns * 1e-9))
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _program(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def is_device_program(e: Event) -> bool:
    return e.plane.startswith("/device:") and e.line == "XLA Modules"


def reduce(events: list[Event], is_device=is_device_program, top: int = 10) -> TraceSummary:
    spans = [e for e in events if e.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    w0, w1 = spans[0].start, spans[0].end

    def clip(e):
        return max(e.start, w0), min(e.end, w1)

    dev = [e for e in events if is_device(e) and e.end > w0 and e.start < w1]
    planes = sorted({e.plane for e in dev})
    busy_by_plane = [
        union(clip(e) for e in dev if e.plane == p) for p in planes
    ]
    busy = sum(e - s for u in busy_by_plane for s, e in u) / max(len(planes), 1)
    programs: dict[str, float] = {}
    for e in dev:
        s, t = clip(e)
        programs[_program(e.name)] = programs.get(_program(e.name), 0.0) + (t - s)
    h2d = union(
        clip(e) for e in events
        if e.plane.startswith("/host:") and e.name in H2D_EVENTS and e.end > w0 and e.start < w1
    )
    # idle gaps on the first device, labelled by what the host was doing
    gaps = label_gaps(idle(busy_by_plane[0] if busy_by_plane else [], w0, w1),
                      labels(events, w0, w1), top)
    return TraceSummary(
        window_s=w1 - w0,
        busy_s=busy,
        devices=len(planes),
        programs=programs,
        h2d_s=sum(e - s for s, e in h2d),
        gaps=gaps,
    )


def labels(events, w0: float, w1: float) -> list[Event]:
    """The host spans (:data:`PREFIXES`) that overlap the window ``[w0, w1]``."""
    return [
        e for e in events
        if e.name.startswith(PREFIXES) and e.name != WINDOW_SPAN and e.end > w0 and e.start < w1
    ]


def idle(busy: list[tuple[float, float]], w0: float, w1: float) -> list[tuple[float, float]]:
    """The intervals of ``[w0, w1]`` outside ``busy`` (a :func:`union`)."""
    edges = [w0] + [x for s, e in busy for x in (s, e)] + [w1]
    return [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]


def label_gaps(gaps, spans: list[Event], top: int) -> list[tuple[str, float]]:
    """The ``top`` longest of ``gaps``, longest first, each labelled by the
    innermost of ``spans`` that covers its midpoint (``host`` where none does)."""
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (s + e) / 2
        cover = [x for x in spans if x.start <= mid <= x.end]
        out.append((min(cover, key=lambda x: x.end - x.start).name if cover else "host", e - s))
    return out


def breakdown(t: TraceSummary, top: int = 10) -> dict:
    ops = sorted(t.programs.items(), key=lambda kv: -kv[1])[:top]
    return {
        "device_ops": [[name, secs] for name, secs in ops],
        "idle_gaps": [[label, secs] for label, secs in t.gaps[:top]],
    }
