"""Device time of the batched exact summarizer
(``jit_build_exact_padded_batched``) per window acked, in milliseconds."""

PROGRAM = "jit_build_exact_padded_batched"


def read(run, before, after):
    if run.trace is None:
        return None
    windows = run.work("ingest") // int(run.cell.config["values_per_window"])
    secs = run.trace.programs.get(PROGRAM, 0.0)
    if windows == 0 or secs <= 0:
        return None
    return 1e3 * secs / windows
