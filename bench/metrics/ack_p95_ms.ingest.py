"""95th percentile of ingest ack latency over every request of every ingest
client in the traced window, from the request's start until its ack: the
backfill's tail, which its runs spread too widely to bound end to end."""
from harness import p95


def read(run, before, after):
    v = p95(run.latencies("ingest"))
    return None if v is None else v * 1e3
