"""Roofline share of the paged decode attention kernel: the least time
the attention of each traced decode call needs (each slot's cached keys
read once at bf16, every layer) over the device time of the
``paged_decode_attention`` custom calls in that call, in percent."""

import trace_reduce as tr
import tracing
import work

MODULE = "jit_fn"
KERNEL = "paged_decode_attention"


def read(ctx):
    cfg = ctx.cell.config
    least = kernel = 0.0
    for mod, ann in tracing.decode_calls(ctx, MODULE):
        rows, keys = tracing.decode_lengths(ann)
        least += work.least_seconds(tracing.attention_work(cfg, rows, keys),
                                    ctx.peaks)
        kernel += sum(o.dur for o in ctx.ops_in([mod])
                      if tr.op_name(o) == KERNEL) * 1e-9
    return tracing.roofline(ctx, least, kernel)
