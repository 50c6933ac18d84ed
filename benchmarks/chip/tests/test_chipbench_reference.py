"""The plain reference planner and the seeded traffic."""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import check, clustergen, driver, reference  # noqa: E402
from benchmarks.chip.reference import RefState  # noqa: E402

SMALL = json.loads((ROOT / "benchmarks/chip/tests/data/small.json")
                   .read_text())


def _faithful(dep, budget=None):
    from benchmarks.chip.program import to_cluster_state
    from repro.core.planner import create_planner
    state = to_cluster_state(dep)
    res = create_planner("equilibrium_faithful").plan(state, budget=budget)
    row_of = {pg: i for i, pg in enumerate(dep.pgs)}
    return [(row_of[m.pg], m.slot, m.src_osd, m.dst_osd) for m in res.moves]


@pytest.mark.parametrize("seed,budget", [(1, None), (2**31 + 7, None),
                                         (3, 10)])
def test_reference_equals_faithful_planner(seed, budget):
    dep = clustergen.generate(SMALL, seed)
    want = _faithful(dep, budget)
    got = reference.plan(RefState(dep), budget, **SMALL["planner"])
    assert len(want) > 0
    assert got == want


def test_reference_equals_faithful_after_growth_and_outage():
    """Warm-tick inputs: a grown pool and a drained OSD."""
    from benchmarks.chip.program import to_cluster_state
    from repro.core.cluster import Movement
    from repro.core.planner import create_planner
    dep = clustergen.generate(SMALL, 9)
    st = RefState(dep)
    state = to_cluster_state(dep)
    reference.plan(st, None, **SMALL["planner"])
    create_planner("equilibrium_faithful").plan(state)
    st.grow(0, 3e13)
    state.grow_pool(0, 3e13)
    state.mark_out(4)
    for r, s, src, dst in driver.drain(st, 4, np.random.default_rng(1)):
        pg = dep.pgs[r]
        state.apply(Movement(pg, s, src, dst, state.shard_sizes[pg]))
    assert np.array_equal(st.used, state.used())
    res = create_planner("equilibrium_faithful").plan(state, budget=10)
    row_of = {pg: i for i, pg in enumerate(dep.pgs)}
    want = [(row_of[m.pg], m.slot, m.src_osd, m.dst_osd) for m in res.moves]
    assert len(want) == 10
    assert reference.plan(st, 10, **SMALL["planner"]) == want


def test_float32_control_is_caught():
    """The control (the reference in float32) differs from the float64
    plan, so the comparison reads it as not correct."""
    dep = clustergen.generate(SMALL, 4)
    want = reference.plan(RefState(dep), None, **SMALL["planner"])
    got = reference.plan(RefState(dep, np.float32), None, **SMALL["planner"])
    assert check.moves_off(got, want) > 0


def test_drained_moves_are_legal():
    dep = clustergen.generate(SMALL, 5)
    st = RefState(dep)
    probe = st.copy()
    moves = driver.drain(st, 7, np.random.default_rng(3))
    assert moves and not st.shards_on(7)[0].size
    probe.set_out(7, True)
    for r, s, src, dst in moves:
        assert src == 7 and probe.is_legal(r, s, dst)
        probe.apply(r, s, src, dst)


def _walk(seed, ticks):
    dep = clustergen.generate(SMALL, 1)
    traffic = driver.load_traffic("tick")
    sched = driver.Schedule(dep, traffic["growth_share"], traffic["outage"],
                            seed, 0x71C4)
    return [sched.events(t) for t in range(ticks)]


def test_tick_schedule_repeats_exactly_from_its_seed():
    a = _walk(2**31 + 5, 320)
    assert a == _walk(2**31 + 5, 320)
    b = _walk(6, 320)
    assert a != b
    # every seed gets the same number of growth, out and in events
    kinds = [[e[0] for e in evs] for evs in a]
    assert kinds == [[e[0] for e in evs] for evs in b]
    outs = [t for t, evs in enumerate(a) if any(e[0] == "out" for e in evs)]
    ins = [t for t, evs in enumerate(a) if any(e[0] == "in" for e in evs)]
    assert outs == [99, 199, 299] and ins == [149, 249]
    assert all(e[2] == a[0][0][2] for evs in a for e in evs
               if e[0] == "grow")
