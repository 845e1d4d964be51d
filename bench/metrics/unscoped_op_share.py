"""Model step: share of the device self time in the traced window that
runs under none of the program's named scopes, %: the layer scan's
control flow, norms, residual adds and copies XLA inserts."""
from bench.core.scopes import UNSCOPED, ScopedTrace


def read(run):
    if not isinstance(run.trace, ScopedTrace):
        return None
    secs = run.trace.scope_seconds(run.trace_lo, run.trace_hi)
    total = sum(secs.values())
    if total <= 0 or set(secs) == {UNSCOPED}:
        return None
    return 100.0 * secs.get(UNSCOPED, 0.0) / total
