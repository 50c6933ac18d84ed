"""Share of the destination axis that the class mask leaves live, in %:
per plan call, the ``batch.dst_cols_live`` counter over ``batch.dst_cols``
(for each move, the columns of its row's legality tile that the row's
rule-step class admits, over the columns the chunk program evaluates).
None where the program keeps neither counter."""

from benchmarks.chip.spans import per_plan_counter


def read(ctx):
    cols = per_plan_counter(ctx.spans, "batch.dst_cols")
    live = per_plan_counter(ctx.spans, "batch.dst_cols_live")
    if not cols or live is None:
        return None
    return 100.0 * live / cols
