"""Output tokens received in the window over the window's seconds."""


def read(run):
    n = sum(t.tokens for t in run.ticks if run.in_window(t.t1))
    return n / run.seconds if n else None
