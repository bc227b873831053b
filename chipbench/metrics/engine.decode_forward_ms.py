"""Mean device time of one run of the engine's paged decode program
(``_decode_paged_fn``) in the traced window, in milliseconds."""
PROGRAM = "_decode_paged_fn"


def read(ctx):
    runs = ctx.trace.module_seconds(PROGRAM)
    return 1e3 * sum(runs) / len(runs) if runs else None
