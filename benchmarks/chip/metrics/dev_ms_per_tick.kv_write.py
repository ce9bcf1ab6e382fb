"""dev_ms_per_tick.kv_write: device milliseconds per tick in the
``kv_write`` scope, the KV cache writes, from the profiler trace's leaf
ops (spans.py)."""

import spans


def read(rec):
    return spans.dev_ms_per_tick(rec, "kv_write")
