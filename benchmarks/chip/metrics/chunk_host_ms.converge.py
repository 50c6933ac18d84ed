"""Host time of the chunk loop per plan call, in ms: the program's
``batch.chunk`` spans inside each ``planner.plan`` span less the
``batch.fetch`` spans inside them (dispatch, move decode, stash)."""

from benchmarks.chip.plan_spans import chunk_host_ms


def read(ctx):
    return chunk_host_ms(ctx.spans)
