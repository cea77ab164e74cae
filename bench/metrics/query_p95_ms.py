"""95th percentile of query latency over every request of every query client
in the window, from the request's start (when it was sent, or due) until
its answers are on the host."""
from harness import p95


def read(run, before, after):
    v = p95(run.latencies("query"))
    return None if v is None else v * 1e3
