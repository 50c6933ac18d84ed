"""Readings from the program's own spans and counters (``repro.obs``
records of the window): per ``planner.plan`` call, its host self time
(the span minus the ``batch.chunk`` spans inside it) and its counters."""

from __future__ import annotations

import statistics


def _plans(spans):
    return [r for r in spans or []
            if r.get("ev") == "span" and r["name"] == "planner.plan"]


def plan_self_ms(spans) -> float | None:
    """Mean host self time of a plan call, in ms."""
    plans = _plans(spans)
    if not plans:
        return None
    parent = {r["id"]: r["parent"] for r in spans if r.get("ev") == "span"}
    inner = {p["id"]: 0.0 for p in plans}
    for r in spans:
        if r.get("ev") == "span" and r["name"] == "batch.chunk":
            p = r["parent"]
            while p and p not in inner:
                p = parent.get(p, 0)
            if p:
                inner[p] += r["dur"]
    return statistics.fmean((p["dur"] - inner[p["id"]]) * 1e-3
                            for p in plans)


def per_plan_counter(spans, name: str) -> float | None:
    """Mean increment of a program counter per plan call."""
    plans = _plans(spans)
    if not plans:
        return None
    return statistics.fmean(p["args"].get("counters", {}).get(name, 0)
                            for p in plans)
