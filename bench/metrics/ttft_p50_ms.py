"""Median time to first token, ms: host time the first token is received
minus the request's due time, over requests whose first token fell in the
window."""
from bench.core.traffic import percentile


def read(run):
    ttft = [r.first - r.arrival for r in run.reqs if run.in_window(r.first)]
    return 1e3 * percentile(ttft, 50) if ttft else None
