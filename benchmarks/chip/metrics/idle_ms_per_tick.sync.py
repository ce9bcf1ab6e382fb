"""idle_ms_per_tick.sync: device idle milliseconds per tick spent waiting
on the device and copying results back (idle under the engines' ``*.wait``
and ``*.pull`` regions), from the profiler trace (spans.py)."""

import spans


def read(rec):
    return spans.idle_ms_per_tick(rec, "sync")
