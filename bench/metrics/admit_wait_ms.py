"""Scheduler: mean time from a request's due time to the engine's own
stamp of its first leaving the queue (``Request.t_admit``), ms, over the
requests admitted in the window.  None where the engine has no stamp."""


def read(run):
    waits = [r.obj.t_admit - r.arrival for r in run.reqs
             if run.in_window(getattr(r.obj, "t_admit", None))]
    return 1e3 * sum(waits) / len(waits) if waits else None
