"""Steps: mean time from a request's admission to its first token, both
stamped by the engine (``Request.t_first - Request.t_admit``), ms, over
the requests admitted in the window that have a first token: the prefill
chunks, one a tick, and the ticks' decode between them."""


def read(run):
    spans = [r.obj.t_first - r.obj.t_admit for r in run.reqs
             if run.in_window(getattr(r.obj, "t_admit", None))
             and r.obj.t_first is not None]
    return 1e3 * sum(spans) / len(spans) if spans else None
