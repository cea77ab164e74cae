"""WAL fsync time (``wal_stats()["fsync_seconds_total"]``) per ingest call
acked in the window, in milliseconds."""


def snapshot(svc):
    return (svc.wal_stats() or {}).get("fsync_seconds_total", 0.0)


def read(run, before, after):
    calls = sum(1 for r in run.requests("ingest") if r.work)
    return None if calls == 0 else 1e3 * (after - before) / calls
