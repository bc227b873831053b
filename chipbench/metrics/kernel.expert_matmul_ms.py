"""Device time of the expert matmuls (the ``ragged-dot`` operations of
the MoE FFN's grouped GEMMs) in the traced window, per decode forward
logged in it, in milliseconds.  Admission prefills in the window run
their experts through the same operations and are counted with them."""
EXPERTS = r"^ragged-dot"


def read(ctx):
    secs = ctx.trace.op_seconds(EXPERTS)
    t0, t1 = ctx.run.trace_t
    forwards = sum(1 for s in ctx.run.steps if t0 <= s.t <= t1)
    return 1e3 * secs / forwards if secs and forwards else None
