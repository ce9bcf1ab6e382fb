"""dev_ms_per_tick.mlp: device milliseconds per tick in the ``mlp`` scope,
the MLP, from the profiler trace's leaf ops (spans.py)."""

import spans


def read(rec):
    return spans.dev_ms_per_tick(rec, "mlp")
