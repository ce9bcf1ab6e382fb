"""ttft_p90_ms: 90th percentile, over every request due in the window,
of the time from its due time to its first output token.  A request
with no first token by the window's end counts with the time it has
waited; a failed one counts as infinite (reported as 1e12 ms)."""

import harness


def read(rec):
    vals = []
    for r in rec.due_in_window():
        h = r.handle
        if h is None:                  # fell due during the last tick
            vals.append(rec.t_end - r.due)
        elif h.status in ("timeout", "shed", "failed"):
            vals.append(float("inf"))
        elif h.first_token_at is not None and h.first_token_at <= rec.t_end:
            vals.append(h.first_token_at - r.due)
        else:
            vals.append(rec.t_end - r.due)
    if not vals:
        return None
    return min(harness.nearest_rank(vals, 0.90) * 1e3, 1e12)
