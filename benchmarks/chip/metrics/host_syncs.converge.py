"""Device-to-host fetches per plan call (``batch.host_syncs`` counter)."""

from benchmarks.chip.spans import per_plan_counter


def read(ctx):
    return per_plan_counter(ctx.spans, "batch.host_syncs")
