"""Scheduler: mean host time of one engine.step() (which ends on its own
device_get), ms, over the ticks of the traced part of the window."""


def read(run):
    ticks = run.traced_ticks()
    if not ticks:
        return None
    return 1e3 * sum(t.t1 - t.t0 for t in ticks) / len(ticks)
