"""Host milliseconds per serving tick: the wall time of each traced
``tick_once`` minus the time the chip was busy inside it, averaged."""

import numpy as np


def read(ctx):
    ticks = ctx.host("bench.tick")
    if not ticks:
        return None
    return 1e3 * float(np.mean([t.dur * 1e-9 - ctx.busy_s(t.start, t.end)
                                for t in ticks]))
