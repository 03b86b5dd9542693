"""Host milliseconds per training step: from one batch's construction to
the next, minus the time the chip was busy in between, averaged over
the traced steps."""

import numpy as np


def read(ctx):
    starts = [b.start for b in ctx.host("bench.batch")]
    if len(starts) < 2:
        return None
    return 1e3 * float(np.mean([(b - a) * 1e-9 - ctx.busy_s(a, b)
                                for a, b in zip(starts, starts[1:])]))
