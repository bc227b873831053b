"""Tokens served per decode forward a request's row took part in: the
tokens of the requests finished in the window over the forwards their
rows were in (``Request.forwards``, the program's own counter,
refinements and commits both).  The paper's algorithmic token
utilisation of a parallel decoder: 1 for greedy decoding; for block
diffusion, the tokens of a block over its refinements and commit."""


def read(ctx):
    run = ctx.run
    tokens = forwards = 0
    for r in run.recs.values():
        n = getattr(r.req, "forwards", None)
        if n and r.n == r.max_tokens and run.w0 <= r.last <= run.w1:
            tokens += r.n
            forwards += n
    return tokens / forwards if forwards else None
