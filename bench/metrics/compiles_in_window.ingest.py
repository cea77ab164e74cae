"""Programs lowered inside the window (JAX's compile events): 0 once set-up
has warmed every shape the traffic uses."""


def read(run, before, after):
    return run.compiles
