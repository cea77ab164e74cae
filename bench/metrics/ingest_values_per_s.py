"""Raw values durably acked (WAL-fsynced and summarized) per second over the
whole window."""


def read(run, before, after):
    s = run.stats.get("ingest_many")
    if s is None or not s.requests:
        return None
    return s.work() / run.window_s
