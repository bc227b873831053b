"""Device time of the admission programs (``_prefill_fn`` and the
scatter into the pool, ``_scatter_prefill``) in the traced window, per
thousand prompt tokens admitted in it."""
PROGRAMS = ("_prefill_fn", "_scatter_prefill")


def read(ctx):
    t0, t1 = ctx.run.trace_t
    tokens = sum(n for t, n in ctx.run.admits if t0 <= t <= t1)
    secs = sum(sum(ctx.trace.module_seconds(p)) for p in PROGRAMS)
    if not tokens or not secs:
        return None
    return 1e3 * secs / (tokens / 1e3)
