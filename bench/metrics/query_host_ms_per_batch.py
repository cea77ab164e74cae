"""Host time of ``query_many`` per query batch: the program's ``hist.query``
spans less their ``hist.query.wait`` children (the host blocked on the
merge's result), in milliseconds."""
import span_reduce


def snapshot(svc):
    return span_reduce.for_service(svc)


def read(run, before, after):
    s = run.stats.get("query")
    if after is None or s is None or not s.requests or "hist.query" not in after.spans:
        return None
    return 1e3 * after.self_s("hist.query", "hist.query.wait") / len(s.requests)
