"""Device milliseconds per call of the pim decode program (the engine's
compiled schedule, the XLA module ``jit_fn``), averaged over its calls
in the traced window."""

import numpy as np

MODULE = "jit_fn"


def read(ctx):
    calls = ctx.module_calls(MODULE)
    if not calls:
        return None
    return 1e3 * float(np.mean([c.dur * 1e-9 for c in calls]))
