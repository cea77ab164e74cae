"""WAL append time per ingest call acked: the program's ``hist.wal.append``
spans, which hold the segment rolls and their fsyncs, in milliseconds."""
import span_reduce


def snapshot(svc):
    return span_reduce.for_service(svc)


def read(run, before, after):
    calls = sum(1 for r in run.requests("ingest") if r.work)
    if after is None or calls == 0 or "hist.wal.append" not in after.spans:
        return None
    return 1e3 * after.spans["hist.wal.append"] / calls
