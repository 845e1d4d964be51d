"""90th percentile of time to first token, ms (see ttft_p50_ms)."""
from bench.core.traffic import percentile


def read(run):
    ttft = [r.first - r.arrival for r in run.reqs if run.in_window(r.first)]
    return 1e3 * percentile(ttft, 90) if ttft else None
