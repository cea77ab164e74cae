"""Host time of ``query_many`` per query batch: the program's ``hist.query``
spans less their ``hist.query.wait`` children (the host blocked on the
merge's result), in milliseconds."""
import span_reduce


def snapshot(svc):
    return span_reduce.for_service(svc)


def read(run, before, after):
    batches = len(run.requests("query"))
    if after is None or not batches or "hist.query" not in after.spans:
        return None
    return 1e3 * after.self_s("hist.query", "hist.query.wait") / batches
