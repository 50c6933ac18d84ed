"""Mean wall time of a cold plan to convergence (host clock): the summed
seconds of every plan started in the window over their number."""


def read(ctx):
    if not ctx.requests:
        return None
    return sum(r.seconds for r in ctx.requests) / len(ctx.requests)
