"""Host time of the tree pull-up per ingest call acked: the program's
``hist.pullup`` spans less their ``hist.pullup.wait`` children (the host
blocked on each level's merge), in milliseconds."""
import span_reduce


def snapshot(svc):
    return span_reduce.for_service(svc)


def read(run, before, after):
    s = run.stats.get("ingest_many")
    calls = 0 if s is None else sum(1 for r in s.requests if r.work)
    if after is None or calls == 0 or "hist.pullup" not in after.spans:
        return None
    return 1e3 * after.self_s("hist.pullup", "hist.pullup.wait") / calls
