"""Host time of the reconcile per plan call, in ms: the program's
``batch.reconcile`` spans inside each ``planner.plan`` span (every
planned move applied to the cluster and checked again)."""

from benchmarks.chip.plan_spans import plan_span_ms


def read(ctx):
    return plan_span_ms(ctx.spans, "batch.reconcile")
