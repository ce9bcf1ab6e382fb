"""gen_lag_p99_ms: how late the load generator submitted: 99th
percentile of submit time - due time over the requests due in the
window (a tick in progress when a request falls due delays it; one
still unsubmitted at the window's end counts with its lag so far)."""

import harness


def read(rec):
    lags = [(r.submitted if r.submitted is not None else rec.t_end) - r.due
            for r in rec.due_in_window()]
    return harness.nearest_rank(lags, 0.99) * 1e3 if lags else None
