"""Model FLOP utilization of the whole serving step in the traced
window: the FLOPs the tokens received there need (``counts.model_flops``,
each at its own context length) over the traced window's seconds times
the chip's bf16 peak."""
import counts


def read(ctx):
    t0, t1 = ctx.run.trace_t
    contexts = [c for e in ctx.run.emissions if t0 <= e.t <= t1
                for c in e.contexts]
    if not contexts:
        return None
    flops = counts.model_flops(ctx.arch, contexts)
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks.bf16_flops)
