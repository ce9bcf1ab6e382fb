"""idle_ms_per_tick.sched: device idle milliseconds per tick under any
other program region (``sched.*``, ``spec.round``, ``spec.reconcile``,
an engine call's own bookkeeping), from the profiler trace (spans.py)."""

import spans


def read(rec):
    return spans.idle_ms_per_tick(rec, "sched")
