"""Cache backend: device self time under the program scope
``page_gather`` (the block-table gather of page pools into contiguous
views) per traced tick, ms."""
from bench.core.scopes import scope_ms_per_tick


def read(run):
    return scope_ms_per_tick(run, "page_gather")
