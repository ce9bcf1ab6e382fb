"""tick_ms.bestof: the window over the ticks in it, from the benchmark's
own spans around ``tick()``."""

import harness


def read(rec):
    return harness.tick_ms(rec)
