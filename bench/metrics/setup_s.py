"""Set-up: process start to the window's first request (host clock)."""


def read(run, before, after):
    return run.setup_s
