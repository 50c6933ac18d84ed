"""Reduction of a profiler trace to device busy time, idle gaps, per-op
and per-program device time, and the calls of named kernels.

A :class:`Timeline` holds, per device, the operation events of the TPU
plane's ``XLA Ops`` line and the program events of its ``XLA Modules``
line, and the host spans the benchmark annotated around its calls into
the program (``bench.plan``, ``bench.mutate``, ``bench.copy``).
:func:`from_xplane` reads one lazily from the ``.xplane.pb`` that
``jax.profiler`` writes (a cold plan of cluster B gives millions of op
events), and :func:`reduce` makes one pass over it, so the same code
reduces a small recorded Timeline in the tests.

* The window runs from the first annotated host span's start to the last
  one's end.
* Busy time is the union of a device's operation intervals inside the
  window; the idle share is 1 minus busy over the window.  Busy seconds
  are averaged over the devices.
* Each idle gap of the first device is named after the annotated host
  span that overlaps it most (``host`` where none does).
* An operation is named by its HLO instruction (``fusion.12``); the
  events of a kernel named in ``kernels`` are also counted by the rows of
  their first 2-D operand, as the event's HLO text gives it.
"""

from __future__ import annotations

import heapq
import json
import re
from dataclasses import dataclass, field

ANNOTATIONS = ("bench.plan", "bench.mutate", "bench.copy")
KERNELS = ("masked_select",)


@dataclass
class Timeline:
    # per device: iterables of (name, start_ns, dur_ns), in start order
    ops: list = field(default_factory=list)
    modules: list = field(default_factory=list)
    annotations: list = field(default_factory=list)  # [(name, start, dur)]

    def to_json(self) -> str:
        return json.dumps({"ops": [list(d) for d in self.ops],
                           "modules": [list(d) for d in self.modules],
                           "annotations": self.annotations})

    @classmethod
    def from_json(cls, text: str) -> "Timeline":
        d = json.loads(text)
        return cls([[tuple(e) for e in dev] for dev in d["ops"]],
                   [[tuple(e) for e in dev] for dev in d["modules"]],
                   [tuple(e) for e in d["annotations"]])


def _line_events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.duration_ns


def from_xplane(path: str, annotations=ANNOTATIONS) -> Timeline:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tl = Timeline()
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            tl.ops.append(_line_events(lines["XLA Ops"])
                          if "XLA Ops" in lines else [])
            tl.modules.append(list(_line_events(lines["XLA Modules"]))
                              if "XLA Modules" in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                tl.annotations += [(e.name, e.start_ns, e.duration_ns)
                                   for e in line.events
                                   if e.name in annotations]
    tl.annotations.sort(key=lambda a: a[1])
    return tl


def short_name(name: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


_SHAPE = re.compile(r"\[(\d+),(\d+)\]")


def operand_rows(hlo_text: str) -> int | None:
    """Rows of the first 2-D operand in an instruction's HLO text."""
    m = _SHAPE.search(hlo_text.split("custom-call(", 1)[-1])
    return int(m.group(1)) if m else None


def _label(gap, annotations):
    best, best_ns = "host", 0.0
    for name, s, d in annotations:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best_ns:
            best, best_ns = name, ov
    return best


def reduce(tl: Timeline, top: int = 10, kernels=KERNELS) -> dict:
    """One pass over the timeline; times in seconds."""
    if not tl.annotations or not tl.ops:
        raise ValueError("trace has no annotated window or no device")
    lo = min(s for _, s, _ in tl.annotations)
    hi = max(s + d for _, s, d in tl.annotations)
    busy_total = 0.0
    gaps: list = []                     # heap of the longest gaps, dev 0
    per_op: dict = {}
    calls = {k: {} for k in kernels}    # kernel -> {rows: [count, ns]}
    for dev, events in enumerate(tl.ops):
        cur_s = cur_e = None
        busy = 0.0
        cursor = lo
        for name, s, d in events:
            e = s + d
            if e <= lo or s >= hi:
                continue
            key = short_name(name)
            per_op[key] = per_op.get(key, 0.0) + d
            for k in kernels:
                if key.startswith(k):
                    rows = operand_rows(name)
                    c = calls[k].setdefault(rows, [0, 0.0])
                    c[0] += 1
                    c[1] += d
            s, e = max(s, lo), min(e, hi)
            if cur_e is not None and s <= cur_e:
                cur_e = max(cur_e, e)
                continue
            if cur_e is not None:
                busy += cur_e - cur_s
                cursor = cur_e
            if dev == 0 and s > cursor:
                heapq.heappush(gaps, (s - cursor, cursor, s))
                if len(gaps) > top:
                    heapq.heappop(gaps)
            cur_s, cur_e = s, e
        if cur_e is not None:
            busy += cur_e - cur_s
            cursor = cur_e
        if dev == 0 and cursor < hi:
            heapq.heappush(gaps, (hi - cursor, cursor, hi))
            if len(gaps) > top:
                heapq.heappop(gaps)
        busy_total += busy
    n_dev = len(tl.ops)
    window_ns = hi - lo
    modules: dict = {}
    for dev in tl.modules:
        for name, s, d in dev:
            if lo <= s < hi:
                modules[name] = modules.get(name, 0.0) + d / n_dev * 1e-9
    return {
        "window_s": window_ns * 1e-9,
        "busy_s": busy_total / n_dev * 1e-9,
        "idle_share": 1.0 - busy_total / n_dev / window_ns,
        "device_ops": [[k, v / n_dev * 1e-9] for k, v in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[_label((g0, g1), tl.annotations), ns * 1e-9]
                      for ns, g0, g1 in sorted(gaps, reverse=True)],
        "modules": modules,
        "kernels": {k: {rows: (c[0], c[1] * 1e-9) for rows, c in v.items()}
                    for k, v in calls.items()},
    }
