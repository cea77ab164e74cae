"""95th percentile of ingest ack latency over every ``ingest_many`` call of
the window, from the call until its ack."""
from harness import p95


def read(run, before, after):
    v = p95(run.latencies("ingest_many"))
    return None if v is None else v * 1e3
