"""Cache backend: device self time under the program scope ``kv_write``
(the writes of new K/V into the page pools and slabs) per traced tick,
ms."""
from bench.core.scopes import scope_ms_per_tick


def read(run):
    return scope_ms_per_tick(run, "kv_write")
