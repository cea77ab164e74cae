"""Arena layer per query batch: device time of the merge-stack gather
(``jit__gather_rows``) plus the host's transfer-to-device time (the arena
plane's upload after it changed), in milliseconds."""

PROGRAM = "jit__gather_rows"


def read(run, before, after):
    batches = len(run.requests("query"))
    if run.trace is None or not batches:
        return None
    secs = run.trace.programs.get(PROGRAM, 0.0) + run.trace.h2d_s
    return 1e3 * secs / batches
