"""Model FLOP/s utilization of the chat cell's ticks: the model operations
of the decode calls and the prefills inside the traced ticks, over the
ticks' summed wall time times the chip's bf16 peak, in percent."""

import trace_reduce as tr
import tracing
import work


def read(ctx):
    cfg = ctx.cell.config
    ticks = ctx.host("bench.tick")
    if not ticks:
        return None
    spans = [(t.start, t.end) for t in ticks]
    flops = sum(tracing.decode_work(cfg, a).flops
                for a in tr.inside(ctx.host("bench.decode"), spans))
    flops += sum(work.prefill(cfg, int(a.stats["tokens"])).flops
                 for a in tr.inside(ctx.host("bench.prefill"), spans))
    if not flops:
        return None
    wall = sum(t.dur for t in ticks) * 1e-9
    return 100.0 * flops / (wall * ctx.peaks["bf16_flops_per_s"])
