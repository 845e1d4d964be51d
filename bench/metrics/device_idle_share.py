"""Device: share of the traced window in which no operation ran on the
device, %: 1 - (union of device-op intervals) / window."""


def read(run):
    if run.trace is None:
        return None
    window = run.trace_hi - run.trace_lo
    busy = run.trace.busy_s(run.trace_lo, run.trace_hi)
    if window <= 0 or busy <= 0:
        return None
    return 100.0 * (1.0 - busy / window)
