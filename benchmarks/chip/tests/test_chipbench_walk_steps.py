"""``walk_steps_per_move.converge`` reads the program's ``batch.walk_steps``
counter over the moves of the window's plan calls, and leaves the metric
out where the program keeps no such counter."""

import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import clustergen, harness  # noqa: E402

SMALL = "benchmarks/chip/tests/data/small-hybrid.json"


def _plan(steps, moves):
    return {"ev": "span", "name": "planner.plan", "id": 1, "parent": 0,
            "dur": 10.0, "args": {"moves": moves, "counters": {
                "batch.host_syncs": 2, "batch.walk_steps": steps}}}


def _read(spans):
    ctx = harness.Context(n_osds=4, setup_s=0.0, requests=[], spans=spans)
    return harness.load_metric("walk_steps_per_move.converge").read(ctx)


def test_walk_steps_per_move_reads_the_program_counter():
    from benchmarks.chip.program import Program
    from repro import obs
    with open(ROOT / SMALL) as f:
        cfg = json.load(f)
    dep = clustergen.generate(cfg, 5)
    session = Program(dep, cfg["planner"]).session()
    reg = obs.registry()
    snap = reg.snapshot()
    obs.start_tracing(None)
    try:
        _, moves = session.plan(None)
    finally:
        spans = obs.stop_tracing()
    steps = reg.deltas_since(snap)["batch.walk_steps"]
    assert moves and steps >= len(moves)
    assert _read(spans) == pytest.approx(steps / len(moves))


def test_walk_steps_per_move_is_steps_over_moves_of_every_plan():
    assert _read([_plan(30, 10), _plan(10, 10)]) == pytest.approx(2.0)


def test_walk_steps_per_move_is_absent_without_the_counter():
    plan = {"ev": "span", "name": "planner.plan", "id": 1, "parent": 0,
            "dur": 10.0, "args": {"moves": 5,
                                  "counters": {"batch.host_syncs": 2}}}
    for spans in (None, [], [plan], [_plan(0, 0)]):
        assert _read(spans) is None
