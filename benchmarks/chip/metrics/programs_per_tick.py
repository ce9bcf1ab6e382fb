"""programs_per_tick: device program executions in the traced window
over the ticks in it."""


def read(rec):
    if rec.trace is None or not rec.ticks:
        return None
    return rec.trace["programs"] / len(rec.ticks)
