"""dev_ms_per_tick.attn: device milliseconds per tick in the ``attn``
scope, attention (q/k/v, scores, output projection), from the profiler
trace's leaf ops (spans.py)."""

import spans


def read(rec):
    return spans.dev_ms_per_tick(rec, "attn")
