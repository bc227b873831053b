"""Share of the traced window in which no operation ran on the device:
one minus the union of the operations' intervals over the window."""


def read(ctx):
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
