"""Share of the traced window in which no operation ran on the device, %."""


def read(ctx):
    if ctx.reduced is None:
        return None
    return 100.0 * ctx.reduced["idle_share"]
