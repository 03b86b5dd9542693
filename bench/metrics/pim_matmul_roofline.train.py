"""Roofline share of the placed-matmul kernels in the train step: the
least time of the step's matrix products (forward, weight and input
gradients, f32 operands at unpadded shapes) over the device time of the
``pim_matmul*`` custom calls, per traced step, in percent."""

import trace_reduce as tr
import tracing
import work

MODULE = "jit_fn"


def read(ctx):
    calls = ctx.module_calls(MODULE)
    if not calls:
        return None
    per = sum(work.least_seconds(w, ctx.peaks) for w in work.lenet_step_matmuls(
        ctx.cell.config, ctx.cell.traffic["batch"]))
    kernel = sum(o.dur for o in ctx.ops_in(calls)
                 if tr.op_name(o).startswith("pim_matmul")) * 1e-9
    return tracing.roofline(ctx, per * len(calls), kernel)
