"""Panels answered (fresh, not degraded) per second over the whole window."""


def read(run, before, after):
    s = run.stats.get("query")
    if s is None or not s.requests:
        return None
    return s.work() / run.window_s
