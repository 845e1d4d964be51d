"""Scheduler: mean over the traced ticks of the engine's ``engine.step``
span less its ``engine.sync`` and ``engine.first_token`` spans, ms: the
host work of a tick that the device waits behind.  Reads the engine
spans of a ``bench.core.scopes.ScopedTrace``; None without them."""


def read(run):
    host = getattr(run.trace, "tick_host_s", None)
    if host is None:
        return None
    secs = host(run.trace_lo, run.trace_hi)
    return 1e3 * sum(secs) / len(secs) if secs else None
