"""dev_ms_per_tick.kv_copy: device milliseconds per tick in the ``kv_copy``
scope, the attended cache slice and its merge back into the full cache,
from the profiler trace's leaf ops (spans.py)."""

import spans


def read(rec):
    return spans.dev_ms_per_tick(rec, "kv_copy")
