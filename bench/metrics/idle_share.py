"""Percent of the traced window in which no op ran on the chip: the host
time that the loop leaves between programs. One reader for every cell's
``idle_share.<cell>``."""

import tracing


def read(ctx):
    return tracing.idle_share(ctx)
