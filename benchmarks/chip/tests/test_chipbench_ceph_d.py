"""Paper cluster D in the benchmark: the frozen generator at
``configs/ceph-d.json`` builds the program's ``cluster_d()``, whole runs
of a small cluster of D's shape (the hybrid 1 SSD + 2 HDD rule) read
correct with the program and not correct with the float32 control, and
``class_live_share.converge`` reads the program's per-class counters."""

import json
import shutil
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[3]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from benchmarks.chip import clustergen, harness, reference  # noqa: E402
from benchmarks.chip.check import moves_off  # noqa: E402
from benchmarks.chip.control import RefProgram  # noqa: E402
from benchmarks.chip.reference import RefState  # noqa: E402

HERE = ROOT / "benchmarks/chip"
SMALL = "benchmarks/chip/tests/data/small-hybrid.json"

# recorded from the program's cluster_d() when the configuration was
# added; not recomputed from program code
FINGERPRINT = \
    "a141fae624db15201e395707edf48182e68da35967b1f773815383e1e3774625"
SIZES = (306, 4181, 12543)


def _ceph_d():
    with open(HERE / "configs/ceph-d.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def dep_d():
    return clustergen.paper_cluster(_ceph_d())


def test_ceph_d_matches_recorded_fingerprint(dep_d):
    assert (dep_d.n_dev, len(dep_d.pgs),
            int((dep_d.acting >= 0).sum())) == SIZES
    assert clustergen.fingerprint(dep_d) == FINGERPRINT
    hybrid = (("ssd", 1, "host"), ("hdd", 2, "host"))
    assert [p.steps for p in dep_d.pools].count(hybrid) == 2
    assert sum(p.is_user_data for p in dep_d.pools) == 6


def test_ceph_d_is_the_programs_cluster_d(dep_d):
    from repro.core.clustergen import cluster_d
    state = cluster_d()
    assert dep_d.cap.tolist() == [d.capacity for d in state.devices]
    assert dep_d.dev_class == [d.device_class for d in state.devices]
    assert dep_d.host == [d.host for d in state.devices]
    assert dep_d.rack == [d.rack for d in state.devices]
    assert dep_d.pgs == sorted(state.acting)
    assert [[o for o in row if o >= 0] for row in dep_d.acting.tolist()] \
        == [state.acting[pg] for pg in dep_d.pgs]
    assert dep_d.size.tolist() == [state.shard_sizes[pg]
                                   for pg in dep_d.pgs]


def test_float32_reference_departs_from_the_ceph_d_plan():
    """The control's precision, on one whole plan of the cell's own
    cluster: the float64 reference converges in a few hundred moves and
    the float32 one plans another sequence."""
    dep = clustergen.generate(_ceph_d(), 2**31 + 7)
    cfg = _ceph_d()["planner"]
    want = reference.plan(RefState(dep), None, **cfg)
    got = reference.plan(RefState(dep, np.float32), None, **cfg)
    assert 250 < len(want) < 450
    assert moves_off(got, want) > 0


# -- whole runs of a small cluster of D's shape on the CPU -------------------


def _bench_with_hybrid_cell():
    bench = harness.load_benchmark()
    bench["configs"].append({"name": "small-hybrid", "file": SMALL})
    bench["workloads"].append({"name": "h-converge",
                               "config": "small-hybrid",
                               "traffic": "converge", "chips": 1})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "d-converge" in m.get("workloads", []):
            m["workloads"].append("h-converge")
    return bench


def _run(subject=None, seconds=1.5):
    return harness.run_cell(_bench_with_hybrid_cell(), "h-converge",
                            2**31 + 11, seconds, False, time.perf_counter(),
                            subject=subject, log=lambda d: None)


def test_small_hybrid_run_is_correct():
    out = _run()
    assert out["correct"], out
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"] == {"moves_off": {"value": 0, "limit": 0}}
    assert set(out["metrics"]) == {"setup_s", "plan_s"}


def test_float32_control_on_small_hybrid_is_not_correct():
    out = _run(subject=lambda d, c: RefProgram(d, c, np.float32),
               seconds=3.0)
    assert not out["correct"], out
    assert out["failed"] == out["attempted"] > 0


# -- class_live_share.converge -----------------------------------------------


def _traced_plan_spans(cfg_path, seed):
    from benchmarks.chip.program import Program
    from repro import obs
    with open(ROOT / cfg_path) as f:
        cfg = json.load(f)
    dep = clustergen.generate(cfg, seed)
    session = Program(dep, cfg["planner"]).session()
    obs.start_tracing(None)
    try:
        _, moves = session.plan(None)
    finally:
        spans = obs.stop_tracing()
    return dep, moves, spans


def test_class_live_share_reads_the_per_class_counters():
    dep, moves, spans = _traced_plan_spans(SMALL, 5)
    assert moves
    cls = {p.id: [c for c, n, _ in p.steps for _ in range(n)]
           for p in dep.pools}
    n_class = {c: dep.dev_class.count(c) for c in set(dep.dev_class)}
    live = sum(n_class[cls[dep.pgs[row][0]][slot]]
               for row, slot, _, _ in moves)
    want = 100.0 * live / (len(moves) * dep.n_dev)
    read = harness.load_metric("class_live_share.converge").read
    ctx = harness.Context(n_osds=dep.n_dev, setup_s=0.0, requests=[],
                          spans=spans)
    assert read(ctx) == pytest.approx(want)
    assert 0.0 < read(ctx) < 100.0


def test_class_live_share_is_absent_without_the_counters():
    """A program that keeps neither counter leaves the metric out."""
    read = harness.load_metric("class_live_share.converge").read
    plan = {"ev": "span", "name": "planner.plan", "id": 1, "parent": 0,
            "dur": 10.0, "args": {"counters": {"batch.host_syncs": 2}}}
    for spans in (None, [], [plan]):
        ctx = harness.Context(n_osds=4, setup_s=0.0, requests=[],
                              spans=spans)
        assert read(ctx) is None
