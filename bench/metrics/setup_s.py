"""Seconds from process start to window open: imports, weights, engine,
compilation or persistent-cache reads, warm-up and the traffic's warm
phase."""


def read(run):
    return run.setup_s
