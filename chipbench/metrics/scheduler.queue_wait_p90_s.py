"""90th percentile, over the requests admitted in the window, of the
wait from the due time to the start of the admission that took the
request (the benchmark's own timestamps)."""
import stats


def read(ctx):
    run = ctx.run
    waits = [r.admit - r.due for r in run.recs.values()
             if run.w0 <= r.admit < run.w1]
    return stats.percentile(waits, 90) if waits else None
