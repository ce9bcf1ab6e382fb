"""tpot_p90_ms: 90th percentile, over the requests due in the window
that have a first token, of (finish - first token) / (output tokens - 1);
a request unfinished at the window's end counts with (window end - first
token) over the tokens it has committed."""

import harness


def read(rec):
    vals = []
    for r in rec.due_in_window():
        h = r.handle
        if h is None or h.first_token_at is None or h.first_token_at > rec.t_end:
            continue
        if r.finished_by(rec.t_end):
            vals.append((h.finished_at - h.first_token_at)
                        / max(r.out_tokens() - 1, 1))
        else:
            n = rec.tokens_at(r, rec.snap_end, rec.t_end)
            vals.append((rec.t_end - h.first_token_at) / max(n, 1.0))
    if not vals:
        return None
    return harness.nearest_rank(vals, 0.90) * 1e3
