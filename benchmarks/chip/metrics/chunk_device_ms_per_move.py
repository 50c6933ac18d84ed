"""Device time of the chunk program (``_plan_chunk``) per move planned in
the traced window, in ms (device trace)."""


def read(ctx):
    if ctx.reduced is None:
        return None
    moves = sum(len(r.moves) for r in ctx.traced)
    seconds = sum(v for k, v in ctx.reduced["modules"].items()
                  if "_plan_chunk" in k)
    if not moves or not seconds:
        return None
    return seconds * 1e3 / moves
