"""Tokens the host received in the window per decode forward the
scheduler logged in it (``ServingLoop.step_log`` entries): a count."""


def read(ctx):
    run = ctx.run
    forwards = run.forwards_between(run.w0, run.w1)
    return run.tokens_between(run.w0, run.w1) / forwards if forwards else None
