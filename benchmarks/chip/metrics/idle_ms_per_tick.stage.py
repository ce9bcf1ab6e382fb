"""idle_ms_per_tick.stage: device idle milliseconds per tick spent staging
inputs and the jitted calls (idle under ``*.put``, ``*.dispatch`` and
``spec.stage``), from the profiler trace (spans.py)."""

import spans


def read(rec):
    return spans.idle_ms_per_tick(rec, "stage")
