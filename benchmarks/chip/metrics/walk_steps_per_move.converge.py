"""Iterations of the chunk program's legality walk per move planned, in
steps/move: over the window's plan calls, the ``batch.walk_steps``
counter (every walk iteration, the fruitless final walk of a converged
plan included) over the moves the calls returned.  None where the
program keeps no such counter."""


def read(ctx):
    plans = [r for r in ctx.spans or []
             if r.get("ev") == "span" and r["name"] == "planner.plan"]
    steps = sum(p["args"].get("counters", {}).get("batch.walk_steps", 0)
                for p in plans)
    moves = sum(p["args"].get("moves", 0) for p in plans)
    if not steps or not moves:
        return None
    return steps / moves
