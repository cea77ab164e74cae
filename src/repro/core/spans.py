"""Named host spans on the served path, on the device trace's clock.

Each span is a ``jax.profiler.TraceAnnotation``: while a profiler trace is
active (``jax.profiler.start_trace`` … ``stop_trace``) it lands in the same
``.xplane.pb`` as the device's ``XLA Modules`` lines, on the thread's host
line, so a device idle gap can be named by the host work that was running
in it.  With no trace active a span records nothing; it costs one
constructor call (about a microsecond).  There is no switch to turn spans
on: start a trace.

Conventions
-----------
* Names are ``hist.<layer>.<phase>``.  Spans sit on phases of a call,
  never inside a per-query or per-row loop.
* The top span of a call (``hist.query``, ``hist.ingest``) carries
  ``call=<registry sequence number>`` and the call's counts; its phases
  nest inside it on the same thread line, which is how a phase finds its
  request.
* A phase in which the host blocks on a device result (``np.asarray`` of a
  dispatched program) is a child named ``*.wait``.  A layer's host time is
  its span less its ``.wait`` children.

``SITES`` is the single list of span names: every ``span("...")`` in
``src/`` names a member, and ``PERF.md``'s span table lists each one
(``tests/test_spans.py``).
"""
from __future__ import annotations

from jax.profiler import TraceAnnotation

__all__ = ["SITES", "span"]

SITES: frozenset[str] = frozenset({
    # TenantRegistry.query_many
    "hist.query",
    "hist.query.select",
    "hist.query.pack",
    "hist.query.merge",
    "hist.query.wait",
    "hist.query.assemble",
    # NodeArena.device: the plane's upload after it changed
    "hist.arena.upload",
    # TenantRegistry.ingest / ingest_many
    "hist.ingest",
    "hist.tenant.create",
    "hist.wal.append",
    "hist.wal.roll",
    "hist.wal.fsync",
    "hist.summarize",
    "hist.summarize.pack",
    "hist.summarize.upload",
    "hist.summarize.wait",
    "hist.pullup",
    "hist.pullup.pack",
    "hist.pullup.wait",
    "hist.pullup.write",
    "hist.ingest.finish",
})


def span(name: str, **stats) -> TraceAnnotation:
    """A context manager that records ``name`` (with ``stats``) as a host
    span while a profiler trace is active; ``set_metadata(**stats)`` on it
    adds stats known only once the phase has run."""
    return TraceAnnotation(name, **stats)
