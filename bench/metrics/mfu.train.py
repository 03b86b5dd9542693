"""Model FLOP/s utilization of the training cell: forward and backward
operations per image times the images of the steps completed in the
traced window, over the window times the chip's bf16 peak, in
percent."""

import work


def read(ctx):
    steps = ctx.host("bench.step")
    if not steps:
        return None
    images = len(steps) * ctx.cell.traffic["batch"]
    flops = work.lenet_step_flops_per_image(ctx.cell.config) * images
    return 100.0 * flops / (ctx.window_s * ctx.peaks["bf16_flops_per_s"])
