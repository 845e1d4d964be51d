"""90th percentile over requests completed in the window of the time per
output token after the first, ms: (t_last - t_first) / (n_out - 1)."""
from bench.core.traffic import percentile


def read(run):
    tpot = [(r.last - r.first) / (r.n_out - 1) for r in run.reqs
            if run.in_window(r.done) and r.n_out > 1]
    return 1e3 * percentile(tpot, 90) if tpot else None
