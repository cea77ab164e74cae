"""95th percentile of ``query_many`` batch latency over every batch of the
window, from the moment the batch is sent until its answers are on the host."""
from harness import p95


def read(run, before, after):
    v = p95(run.latencies("query"))
    return None if v is None else v * 1e3
