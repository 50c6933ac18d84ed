"""Idle gaps named after the program's own spans.

:func:`benchmarks.chip.trace.reduce` names each idle gap of the first
device after the ``bench.*`` annotation that overlaps it most.  While a
``repro.obs`` tracer is installed the program writes its own spans
(``planner.*``, ``batch.*``) into the profiler's trace too, on the
device events' clock.  :func:`reduce` here gives what ``trace.reduce``
gives, in the same pass over the op events, except that each idle gap is
cut at the boundaries of the program spans it crosses and each piece is
named after the innermost program span covering it; a piece that none
covers keeps the old name (the annotation overlapping it most, ``host``
where none does).  The program spans do not move the window, so a trace
without them reduces exactly as ``trace.reduce`` reduces it.

``idle_gaps`` stays the ``top`` longest pieces, as ``[name, seconds]``.
"""

from __future__ import annotations

import heapq
from dataclasses import replace

from benchmarks.chip import trace as _trace

PREFIXES = ("planner.", "batch.")


def program_spans(path: str, prefixes=PREFIXES) -> list:
    """The host events of an ``.xplane.pb`` whose names start with one of
    ``prefixes``, as ``(name, start_ns, dur_ns)`` in start order."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events if e.name.startswith(prefixes)]
    spans.sort(key=lambda s: s[1])
    return spans


def _segments(spans, lo, hi):
    """Cut the window at every boundary of the program spans, in one
    sweep.  Returns (starts, names): segment ``i`` runs from
    ``starts[i]`` to ``starts[i + 1]`` (the last to ``hi``) under the
    innermost span covering it, the one started last and ended first
    (None where no span covers it)."""
    edges = []
    for name, s, d in spans:
        a, b = max(s, lo), min(s + d, hi)
        if a < b:
            key = (s, -(s + d), name)
            edges += [(a, 1, key), (b, 0, key)]
    edges.sort(key=lambda e: (e[0], e[1]))
    starts, names, active = [lo], [], []
    i = 0
    while True:
        while i < len(edges) and edges[i][0] <= starts[-1]:
            _, opens, key = edges[i]
            if opens:
                active.append(key)
            else:
                active.remove(key)
            i += 1
        names.append(max(active)[2] if active else None)
        if i == len(edges) or edges[i][0] >= hi:
            return starts, names
        starts.append(edges[i][0])


class _GapPieces:
    """The ``top`` longest pieces of the idle gaps of one device, which
    come in time order; each gap is cut where a segment ends."""

    def __init__(self, starts, names, top):
        self.starts, self.names, self.top = starts, names, top
        self.i = 0
        self.heap: list = []

    def add(self, g0, g1):
        starts, i = self.starts, self.i
        while i + 1 < len(starts) and starts[i + 1] <= g0:
            i += 1
        self.i = i
        while g0 < g1:
            b = min(g1, starts[i + 1]) if i + 1 < len(starts) else g1
            heapq.heappush(self.heap, (b - g0, g0, b, self.names[i]))
            if len(self.heap) > self.top:
                heapq.heappop(self.heap)
            g0, i = b, i + 1

    def named(self, annotations):
        return [[name or _trace._label((g0, g1), annotations), ns * 1e-9]
                for ns, g0, g1, name in sorted(self.heap, reverse=True)]


def _watch(events, lo, hi, pieces):
    """Yield ``events`` unchanged, handing each idle gap inside
    [lo, hi) to ``pieces`` as the events pass."""
    cursor = lo                         # end of the busy union so far
    for ev in events:
        yield ev
        _, s, d = ev
        if s + d <= lo or s >= hi:
            continue
        if s > cursor:
            pieces.add(cursor, s)
        cursor = max(cursor, min(s + d, hi))
    if cursor < hi:
        pieces.add(cursor, hi)


def reduce(tl: _trace.Timeline, spans, top: int = 10,
           kernels=_trace.KERNELS) -> dict:
    """``trace.reduce`` of ``tl``, its idle gaps cut and named after the
    program ``spans`` ((name, start_ns, dur_ns))."""
    if not tl.annotations or not tl.ops:
        raise ValueError("trace has no annotated window or no device")
    lo = min(s for _, s, _ in tl.annotations)
    hi = max(s + d for _, s, d in tl.annotations)
    pieces = _GapPieces(*_segments(spans, lo, hi), top)
    ops = [_watch(tl.ops[0], lo, hi, pieces)] + list(tl.ops[1:])
    out = _trace.reduce(replace(tl, ops=ops), top, kernels)
    out["idle_gaps"] = pieces.named(tl.annotations)
    return out


def read(path: str, top: int = 10) -> dict:
    """:func:`reduce` of the ``.xplane.pb`` at ``path``."""
    return reduce(_trace.from_xplane(path), program_spans(path), top)
