"""Panels answered (fresh, not degraded) per second over the whole window, by
every query client."""


def read(run, before, after):
    if not run.requests("query"):
        return None
    return run.work("query") / run.window_s
