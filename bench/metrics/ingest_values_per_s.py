"""Raw values durably acked (WAL-fsynced and summarized) per second over the
whole window, by every ingest client."""


def read(run, before, after):
    if not run.requests("ingest"):
        return None
    return run.work("ingest") / run.window_s
