"""Host self time per plan call, in ms: the program's ``planner.plan``
span minus the ``batch.chunk`` spans inside it (build, delta absorption,
reconcile)."""

from benchmarks.chip.spans import plan_self_ms


def read(ctx):
    return plan_self_ms(ctx.spans)
