"""90th percentile, over the requests admitted in the window, of the
wait from submission to the first admission, as the program stamps them
(``Request.t_submit`` and ``Request.t_admit``, on the host's clock)."""
import stats


def read(ctx):
    run = ctx.run
    waits = []
    for r in run.recs.values():
        t_admit = getattr(r.req, "t_admit", None)
        if t_admit is not None and run.w0 <= t_admit < run.w1:
            waits.append(t_admit - r.req.t_submit)
    return stats.percentile(waits, 90) if waits else None
