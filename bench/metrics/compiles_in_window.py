"""Steps: backend compiles while the window was open, from JAX's
monitoring events.  Every program should come from warm-up: 0."""


def read(run):
    return run.compiles_in_window
