"""Host time of the dense build per plan call, in ms: the program's
``batch.build`` spans inside each ``planner.plan`` span (the device
mirror built from the cluster, its tables and their upload)."""

from benchmarks.chip.plan_spans import plan_span_ms


def read(ctx):
    return plan_span_ms(ctx.spans, "batch.build")
