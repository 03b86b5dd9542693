"""Roofline share of the placed-matmul kernels in the decode program: the
least time the model's weight matmuls of each traced decode call could
take on the chip (unpadded shapes, bf16 weights) over the device time of
the ``pim_matmul*`` custom calls in that call, in percent."""

import trace_reduce as tr
import tracing
import work

MODULE = "jit_fn"


def read(ctx):
    cfg = ctx.cell.config
    least = kernel = 0.0
    for mod, ann in tracing.decode_calls(ctx, MODULE):
        rows, _ = tracing.decode_lengths(ann)
        least += sum(work.least_seconds(w, ctx.peaks)
                     for w in work.decoder_matmuls(cfg, rows))
        kernel += sum(o.dur for o in ctx.ops_in([mod])
                      if tr.op_name(o).startswith("pim_matmul")) * 1e-9
    return tracing.roofline(ctx, least, kernel)
