"""out_tok_s: output tokens (thinking + answer) committed inside the
window, over the window.  Requests finished inside it count exactly,
from their results; requests in flight at either edge count by the step
records they gained between the edges (``snapshot()``), at the run's
own tokens per record."""


def read(rec):
    return sum(rec.committed(r) for r in rec.reqs
               if r.handle is not None) / rec.window_s
