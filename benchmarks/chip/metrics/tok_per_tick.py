"""tok_per_tick: output tokens the scheduler committed in the window
(its ``committed_tokens`` counter at the window's end minus at its
start, from ``snapshot()``), over the window's ticks; with
``tick_ms.think`` it splits ``out_tok_s`` into tokens a tick and ticks
a second."""


def read(rec):
    a = rec.snap_start.counts.get("committed_tokens")
    b = rec.snap_end.counts.get("committed_tokens")
    if a is None or b is None or not rec.ticks:
        return None
    return (b - a) / len(rec.ticks)
