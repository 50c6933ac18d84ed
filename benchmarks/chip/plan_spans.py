"""Readings from the program's child spans inside each ``planner.plan``
call (``repro.obs`` records of the window): the summed time of the spans
of one name, and the chunk loop's host time outside its fetches."""

from __future__ import annotations

import statistics


def _per_plan(spans, name: str, via: str | None = None):
    """Per ``planner.plan`` id: the summed ``dur`` (us) of the spans
    named ``name`` inside it (and, given ``via``, inside a span of that
    name inside it); None where no span is named ``name``."""
    recs = [r for r in spans or [] if r.get("ev") == "span"]
    if not any(r["name"] == name for r in recs):
        return None
    by_id = {r["id"]: r for r in recs}
    total = {r["id"]: 0.0 for r in recs if r["name"] == "planner.plan"}
    for r in recs:
        if r["name"] != name:
            continue
        p, inside = r["parent"], via is None
        while p in by_id and p not in total:
            inside = inside or by_id[p]["name"] == via
            p = by_id[p]["parent"]
        if p in total and inside:
            total[p] += r["dur"]
    return total


def plan_span_ms(spans, name: str) -> float | None:
    """Mean summed time of the spans named ``name`` inside a plan call,
    in ms."""
    per_plan = _per_plan(spans, name)
    if not per_plan:
        return None
    return statistics.fmean(per_plan.values()) * 1e-3


def chunk_host_ms(spans) -> float | None:
    """Mean host time of a plan call's chunk loop, in ms: its
    ``batch.chunk`` spans less the ``batch.fetch`` spans inside them."""
    chunks = _per_plan(spans, "batch.chunk")
    fetches = _per_plan(spans, "batch.fetch", via="batch.chunk")
    if not chunks or fetches is None:
        return None
    return statistics.fmean(chunks[p] - fetches[p] for p in chunks) * 1e-3
