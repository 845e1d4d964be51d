"""Scheduler: mean time from a request's due time to its leaving the
engine's queue (observed by the harness after each tick), ms, over the
requests that left it in the window."""


def read(run):
    waits = [r.admitted - r.arrival for r in run.reqs
             if run.in_window(r.admitted)]
    return 1e3 * sum(waits) / len(waits) if waits else None
