"""Tree pull-up merge dispatches (``interval_tree.PULLUP_STATS``) per ingest
call acked in the window."""


def snapshot(svc):
    from repro.core.interval_tree import PULLUP_STATS

    return PULLUP_STATS["dispatches"]


def read(run, before, after):
    calls = sum(1 for r in run.requests("ingest") if r.work)
    return None if calls == 0 else (after - before) / calls
