"""Host time of the tree pull-up per ingest call acked: the program's
``hist.pullup`` spans less their ``hist.pullup.wait`` children (the host
blocked on each level's merge), in milliseconds."""
import span_reduce


def snapshot(svc):
    return span_reduce.for_service(svc)


def read(run, before, after):
    calls = sum(1 for r in run.requests("ingest") if r.work)
    if after is None or calls == 0 or "hist.pullup" not in after.spans:
        return None
    return 1e3 * after.self_s("hist.pullup", "hist.pullup.wait") / calls
