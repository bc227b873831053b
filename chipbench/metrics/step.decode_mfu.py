"""Model FLOP utilization of the whole serving step in the traced
window: the FLOPs the tokens received there need (the configuration's
``model_flops``, by default ``counts.model_flops``, each at its own
context length) over the traced window's seconds times
the chip's bf16 peak."""


def read(ctx):
    t0, t1 = ctx.run.trace_t
    contexts = [c for e in ctx.run.emissions if t0 <= e.t <= t1
                for c in e.contexts]
    if not contexts:
        return None
    flops = ctx.model.model_flops(ctx.arch, contexts)
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks.bf16_flops)
