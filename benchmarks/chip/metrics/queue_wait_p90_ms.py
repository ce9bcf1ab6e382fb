"""queue_wait_p90_ms: 90th percentile of admission time - due time over
the requests due in the window (``Request.admitted_at``, stamped by the
scheduler); one not admitted by the window's end counts with the time it
has waited."""

import harness


def read(rec):
    vals = []
    for r in rec.due_in_window():
        a = r.handle.admitted_at if r.handle is not None else None
        vals.append((a if a is not None and a <= rec.t_end else rec.t_end)
                    - r.due)
    return harness.nearest_rank(vals, 0.90) * 1e3 if vals else None
