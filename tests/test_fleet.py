"""Fleet planning (:mod:`repro.fleet`): one vmapped dispatch planning N
independent clusters must be *bit-identical per cluster* to N serial
:class:`BatchPlanner` runs — same move sequences, same convergence-tail
stats — including under streaming growth/out/movement deltas, a
mid-stream SLO cutoff (which may only re-chunk the stream, never change
it), and heterogeneous-shape re-packs (which must leave every other
lane's carry, certificates included, bitwise untouched)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from _hypothesis_compat import given, settings, strategies as st

from repro.core import EquilibriumConfig, GiB, Movement
from repro.core.clustergen import sim_cluster
from repro.core.planner import create_planner
from repro.fleet import (BucketShape, CarryDims, FleetLoadGen, FleetPlanner,
                         FleetService)

CH, RB = 8, 8          # small chunk/row-block: padding boundaries get hit
TiB = 1024 * GiB

#: per-plan stats that must match the serial engine bit-for-bit (wall
#: times and engine labels legitimately differ)
STAT_KEYS = ("bound_hits", "pruned_sources", "tail_moves",
             "sources_tried_hist")


def as_tuples(moves):
    return [(m.pg, m.slot, m.src_osd, m.dst_osd) for m in moves]


def _twin_pair(n_hdd: int, seed: int):
    """Two independently built, identical cluster states."""
    def mk():
        return sim_cluster(seed=seed, n_hdd=n_hdd, n_ssd=0, fill=0.6)
    return mk(), mk()


def _serial_planner():
    """The serial comparator, configured exactly like a fleet lane."""
    return create_planner("equilibrium_batch", chunk=CH, row_block=RB,
                          select_backend="ref", legality_cache=False,
                          source_bounds=True)


def _first_legal_move(state) -> Movement:
    for pg in sorted(state.acting):
        for slot, osd in enumerate(state.acting[pg]):
            for dev in state.devices:
                if dev.id != osd and state.move_is_legal(pg, slot, dev.id):
                    return Movement(pg, slot, osd, dev.id,
                                    state.shard_sizes[pg])
    raise AssertionError("no legal move in test cluster")


def _mutate(t: int, key_idx: int, state) -> None:
    """Deterministic per-tick delta stream: growth, a device out/in
    flip, and a foreign (externally decided) movement."""
    kind = (t + key_idx) % 3
    if kind == 0:
        state.grow_pool(0, 512 * GiB)
    elif kind == 1:
        osd = state.devices[-1].id
        state.mark_out(osd, osd not in state.out_osds)
    else:
        state.apply(_first_legal_move(state))


def _run_fleet_vs_serial(specs, ticks, budget, *, deltas=True,
                         slo_cut_tick=None, drain=8):
    """Drive twin fleets — one vmapped FleetPlanner vs N serial
    BatchPlanners on identically-built states — and assert per-cluster
    bit-identity of the move streams (per tick when no SLO cut is in
    play; as concatenated streams otherwise, since a cut only re-chunks
    the deterministic sequence)."""
    fp = FleetPlanner(chunk=CH, row_block=RB)
    fleet_states, serial_states, serial = {}, {}, {}
    for j, (n_hdd, seed) in enumerate(specs):
        key = f"c{j}"
        fleet_states[key], serial_states[key] = _twin_pair(n_hdd, seed)
        fp.add_cluster(key, fleet_states[key])
        serial[key] = _serial_planner()
    keys = list(fleet_states)
    stream_f = {k: [] for k in keys}
    stream_s = {k: [] for k in keys}

    def one_round(budgets, slo):
        res_s = {k: serial[k].plan(serial_states[k], budget=budgets[k])
                 for k in keys}
        res_f = fp.plan_fleet(budgets, slo_seconds=slo)
        for k in keys:
            stream_f[k] += as_tuples(res_f[k].moves)
            stream_s[k] += as_tuples(res_s[k].moves)
        return res_f, res_s

    for t in range(ticks):
        if deltas and any(len(stream_f[k]) < len(stream_s[k]) for k in keys):
            # an SLO cut left the fleet behind the serial planners: plan
            # the shortfall (fleet only, no deadline) before the next
            # deltas, so both sides receive each delta at the same point
            # of the move stream — a cut re-chunks the stream, but a
            # delta landing earlier in it is a different input
            short = {k: len(stream_s[k]) - len(stream_f[k]) for k in keys
                     if len(stream_f[k]) < len(stream_s[k])}
            res_f = fp.plan_fleet(short, slo_seconds=None)
            for k in short:
                stream_f[k] += as_tuples(res_f[k].moves)
        if deltas:
            for j, k in enumerate(keys):
                _mutate(t, j, fleet_states[k])
                _mutate(t, j, serial_states[k])
        cut = slo_cut_tick is not None and t == slo_cut_tick
        res_f, res_s = one_round({k: budget for k in keys},
                                 0.0 if cut else None)
        if slo_cut_tick is None:
            # no cut anywhere: ticks must agree move-for-move AND on the
            # convergence-tail stats
            for k in keys:
                assert as_tuples(res_f[k].moves) == as_tuples(res_s[k].moves)
                for sk in STAT_KEYS:
                    assert res_f[k].stats[sk] == res_s[k].stats[sk], \
                        (k, sk)
    for _ in range(drain):          # run both sides to convergence
        one_round({k: budget for k in keys}, None)
    for k in keys:
        assert stream_f[k] == stream_s[k], k
        assert np.isclose(fleet_states[k].utilization_variance(),
                          serial_states[k].utilization_variance())
        fleet_states[k].check_valid()
    return fp, fleet_states


# ---------------------------------------------------------------------------
# tentpole: vmapped fleet == N serial planners, bit for bit


def test_fleet_of_one_matches_serial():
    _run_fleet_vs_serial([(9, 0)], ticks=2, budget=CH, deltas=False)


def test_fleet_three_heterogeneous_under_delta_stream():
    """Three clusters of two different sizes (sharing one shape bucket)
    with interleaved growth / device-out / foreign-movement deltas."""
    _run_fleet_vs_serial([(9, 0), (12, 1), (9, 2)], ticks=3, budget=CH)


def test_fleet_multi_bucket():
    """Cluster sizes that land in *different* shape buckets still plan
    correctly in one tick (one dispatch per bucket)."""
    _run_fleet_vs_serial([(9, 3), (18, 4)], ticks=2, budget=CH,
                         deltas=False)


def test_fleet_rounds_coscheduled():
    """A round dispatches every bucket before its single host sync: with
    two shape buckets in play the overlapped-round counter ticks, and the
    blocking-transfer count stays one per round regardless of how many
    buckets dispatched."""
    from repro.obs import registry
    reg = registry()
    snap = reg.snapshot()
    _run_fleet_vs_serial([(9, 3), (18, 4)], ticks=2, budget=CH,
                         deltas=False)
    d = reg.deltas_since(snap)
    rounds = int(d.get("fleet.rounds", 0))
    assert rounds >= 1
    assert int(d.get("fleet.round_syncs", 0)) == rounds
    assert int(d.get("fleet.rounds.overlapped", 0)) >= 1


def test_fleet_slo_cutoff_stream_identical():
    """An SLO cut mid-stream (deadline 0 on tick 1) may shrink that
    tick's plans but the concatenated per-cluster streams stay
    bit-identical to serial — a cut re-chunks, never re-plans."""
    _run_fleet_vs_serial([(9, 0), (12, 1), (9, 2)], ticks=3, budget=CH,
                         slo_cut_tick=1, drain=12)


# deterministic spine (hypothesis is optional in the container image)
@pytest.mark.parametrize("seed_base,sizes,cut", [
    (0, [9, 12, 9], 1),
    (9945, [9, 9, 9], 1),   # a cut that leaves one lane a whole tick behind
])
def test_fleet_matches_serial_cases(seed_base, sizes, cut):
    specs = [(n, seed_base + i) for i, n in enumerate(sizes)]
    _run_fleet_vs_serial(specs, ticks=3, budget=CH,
                         slo_cut_tick=1 if cut else None, drain=12)


@settings(max_examples=3, deadline=None)
@given(st.integers(min_value=0, max_value=10_000),
       st.lists(st.sampled_from([9, 12]), min_size=3, max_size=4),
       st.integers(min_value=0, max_value=1))
def test_fleet_matches_serial_property(seed_base, sizes, cut):
    """Property form: N>=3 random small clusters, random sizes/seeds,
    delta streams, optionally a mid-stream SLO cutoff."""
    specs = [(n, seed_base + i) for i, n in enumerate(sizes)]
    _run_fleet_vs_serial(specs, ticks=3, budget=CH,
                         slo_cut_tick=1 if cut else None, drain=12)


# ---------------------------------------------------------------------------
# SLO-bounded plans are valid partial plans


def test_slo_partial_plan_is_legal():
    """A deadline-0 tick returns partial plans whose every move is legal
    when replayed, in order, on an untouched twin state."""
    fp = FleetPlanner(chunk=CH, row_block=RB, slo_seconds=0.0)
    a, b = _twin_pair(9, 5)
    fp.add_cluster("c", a)
    res = fp.plan_fleet({"c": 64})
    assert res["c"].stats["slo_expired"]
    # progress guarantee: the first dispatch of a tick always runs
    assert len(res["c"].moves) > 0
    for mv in res["c"].moves:
        assert b.move_is_legal(mv.pg, mv.slot, mv.dst_osd)
        b.apply(mv)
    b.check_valid()
    # lifting the deadline finishes the job on the stashed carry
    total = len(res["c"].moves)
    for _ in range(10):
        more = fp.plan_fleet({"c": 64}, slo_seconds=None)
        total += len(more["c"].moves)
        if more["c"].stats["converged"]:
            break
    assert more["c"].stats["converged"]
    assert total >= 64 or more["c"].stats["converged"]


# ---------------------------------------------------------------------------
# satellite: heterogeneous-shape re-pack must not disturb other lanes


def test_rebucket_leaves_other_lanes_bitwise_untouched():
    """Re-packing one cluster's slice to the next row bucket must leave
    every other lane — including pruned-source certificates (dyn[13])
    and the legality cache triple — bitwise identical."""
    fp = FleetPlanner(chunk=CH, row_block=RB)
    states = {}
    for j, seed in enumerate([0, 1, 2]):
        key = f"c{j}"
        states[key], _ = _twin_pair(9, seed)
        fp.add_cluster(key, states[key])
    fp.plan_fleet({k: CH for k in states})   # pack + populate certificates
    pack = fp._pack
    shape0, lane0 = pack.where["c0"]
    bucket = pack.buckets[shape0]
    others = {k: i for k, (s, i) in pack.where.items()
              if k != "c0" and s == shape0}
    assert others, "test expects shared bucket"
    before = {k: jax.device_get(bucket.slice_dyn(i))
              for k, i in others.items()}
    old_lane0 = jax.device_get(bucket.slice_dyn(lane0))

    new_shape, new_lane = pack.rebucket("c0")
    assert new_shape.r_cap == shape0.next_r_cap().r_cap

    for k, i in others.items():
        assert pack.where[k] == (shape0, i)          # untouched lanes stay
        after = jax.device_get(pack.buckets[shape0].slice_dyn(i))
        for arr_b, arr_a in zip(before[k], after):
            assert arr_b.dtype == arr_a.dtype
            np.testing.assert_array_equal(arr_b, arr_a)
    # the moved lane is the serial re-pad: row axes padded with -1/0,
    # everything else carried over bitwise
    moved = jax.device_get(pack.buckets[new_shape].slice_dyn(new_lane))
    rows_b, rows_a = old_lane0[7], moved[7]
    np.testing.assert_array_equal(rows_a[:, :rows_b.shape[1]], rows_b)
    assert (rows_a[:, rows_b.shape[1]:] == -1).all()
    np.testing.assert_array_equal(moved[13], old_lane0[13])  # certificates


def test_bucket_shape_geometry():
    dims = CarryDims(n_dev=9, r_cap=48, n_sh=672, n_pg=224, n_slots=3,
                     n_pools=3, n_levels=2, k=9)
    shape = BucketShape.for_dims(dims, rb=8)
    assert shape.n_dev == 16 and shape.fits(dims)
    assert shape.r_cap >= 48 and shape.r_cap % 8 == 0
    assert shape.next_r_cap().r_cap == 2 * shape.r_cap
    bigger = CarryDims(n_dev=12, r_cap=shape.r_cap * 2, n_sh=672, n_pg=224,
                       n_slots=3, n_pools=3, n_levels=2, k=12)
    grown = shape.grown_to(bigger, rb=8)
    assert grown.fits(dims) and grown.fits(bigger)
    assert grown.r_cap == shape.r_cap * 2     # escalations are sticky


# ---------------------------------------------------------------------------
# service + registry surface


def test_fleet_planner_is_registered():
    p = create_planner("fleet", chunk=CH, row_block=RB)
    assert isinstance(p, FleetPlanner)
    a, b = _twin_pair(9, 6)
    res = p.plan(a, budget=CH)               # protocol single-cluster path
    ref = _serial_planner().plan(b, budget=CH)
    assert as_tuples(res.moves) == as_tuples(ref.moves)
    assert res.stats["fleet_clusters"] == 1


def test_fleet_lane_counts_the_bucket_width_as_its_destination_axis():
    """A lane's chunk program evaluates the bucket's padded device axis:
    9 devices pad to 16 columns for every move."""
    from repro.obs import registry
    reg = registry()
    snap = reg.snapshot()
    a, _ = _twin_pair(9, 6)
    moves = create_planner("fleet", chunk=CH, row_block=RB).plan(
        a, budget=CH).moves
    d = reg.deltas_since(snap)
    assert moves and a.n_devices == 9
    assert d["batch.dst_cols"] == 16 * len(moves)
    assert sum(v for k, v in d.items()
               if k.startswith("batch.moves_by_class")) == len(moves)


def test_fleet_service_tick_and_ingest():
    svc = FleetService(chunk=CH, row_block=RB)
    a, b = _twin_pair(9, 7)
    a2, b2 = _twin_pair(12, 8)
    svc.attach("x", a)
    svc.attach("y", a2)
    tick = svc.tick({"x": CH, "y": CH})
    assert set(tick.results) == {"x", "y"}
    assert tick.total_moves == sum(len(r.moves) for r in tick.results.values())
    assert len(tick) == 2 and tick.wall_seconds > 0
    # streamed deltas reach the right lane: mutate the attached states,
    # next tick matches serial twins receiving the same mutations
    sx, sy = _serial_planner(), _serial_planner()
    sx.plan(b, budget=CH)
    sy.plan(b2, budget=CH)
    for st_ in (a, b):
        st_.grow_pool(0, 512 * GiB)
    tick2 = svc.tick({"x": CH, "y": CH})
    assert as_tuples(tick2.results["x"].moves) == \
        as_tuples(sx.plan(b, budget=CH).moves)
    assert as_tuples(tick2.results["y"].moves) == \
        as_tuples(sy.plan(b2, budget=CH).moves)
    svc.detach("y")
    assert set(svc.tick({"x": CH}).results) == {"x"}


def test_fleet_service_detach_midstream_and_reattach():
    """Full daemon lifecycle on one lane: attach → delta stream + ticks
    (absorb-only, one rebuild at pack time), detach mid-stream while
    deltas are still arriving, then re-attach the same lifecycle — the
    re-pack costs exactly one rebuild, and the lane's plans match a
    serial twin planner fed the same mutations throughout."""
    from repro.core.equilibrium_batch import dense_rebuild_count

    svc = FleetService(chunk=CH, row_block=RB)
    a, b = _twin_pair(9, 31)
    serial = _serial_planner()

    before = dense_rebuild_count()
    svc.attach("lane", a)
    svc.tick({"lane": CH})
    serial.plan(b, budget=CH)
    assert dense_rebuild_count() - before >= 1     # the initial pack

    # streamed mutations absorb: no further rebuilds across ticks
    after_pack = dense_rebuild_count()
    for t in range(2):
        _mutate(t, 0, a)
        _mutate(t, 0, b)
        tick = svc.tick({"lane": CH})
        assert as_tuples(tick.results["lane"].moves) == \
            as_tuples(serial.plan(b, budget=CH).moves)
    assert dense_rebuild_count() == after_pack

    # detach mid-stream: the lane is gone, but its state keeps mutating
    # (the cluster lives on without the balancer)
    svc.detach("lane")
    assert set(svc.tick({}).results) == set()
    _mutate(2, 0, a)
    _mutate(2, 0, b)

    # re-attach the same lifecycle: exactly one rebuild (the new pack),
    # and the plans pick up bit-identical to a serial planner rebuilt on
    # the mutated state
    before_reattach = dense_rebuild_count()
    svc.attach("lane", a)
    tick = svc.tick({"lane": CH})
    assert dense_rebuild_count() - before_reattach == 1
    fresh = _serial_planner()
    b2 = b.copy()
    assert as_tuples(tick.results["lane"].moves) == \
        as_tuples(fresh.plan(b2, budget=CH).moves)

    # and the re-attached lane absorbs again: further ticks rebuild-free
    steady = dense_rebuild_count()
    _mutate(3, 0, a)
    _mutate(3, 0, b2)
    tick = svc.tick({"lane": CH})
    assert as_tuples(tick.results["lane"].moves) == \
        as_tuples(fresh.plan(b2, budget=CH).moves)
    assert dense_rebuild_count() == steady


def test_fleet_service_ingest_routes_out_of_band_deltas():
    """ingest() feeds a lane deltas that did not come from the attached
    state object's own subscription (a mirrored cluster's log): absorbable
    deltas return True and the next tick reflects them."""
    from repro.core.cluster import PoolGrowthDelta

    svc = FleetService(chunk=CH, row_block=RB)
    a, b = _twin_pair(9, 33)
    svc.attach("m", a)
    svc.tick({"m": CH})
    serial = _serial_planner()
    serial.plan(b, budget=CH)
    # mutate the attached state silently-equivalently on the twin, then
    # hand the service the twin's delta out-of-band
    a.grow_pool(0, 256 * GiB)
    b.grow_pool(0, 256 * GiB)
    delta = PoolGrowthDelta(a.mutation_epoch, 0, 256 * GiB)
    assert svc.ingest("m", delta) is True      # deduped by epoch, absorbs
    tick = svc.tick({"m": CH})
    assert as_tuples(tick.results["m"].moves) == \
        as_tuples(serial.plan(b, budget=CH).moves)


def test_fleet_pack_lane_reuse():
    """Freed lanes are reused in place; ensure() is a no-op while a
    cluster's carry token is unchanged."""
    fp = FleetPlanner(chunk=CH, row_block=RB)
    for j in range(3):
        s, _ = _twin_pair(9, 20 + j)
        fp.add_cluster(f"c{j}", s)
    fp.plan_fleet({f"c{j}": CH for j in range(3)})
    shape, lane1 = fp._pack.where["c1"]
    fp.remove_cluster("c1")
    assert "c1" not in fp._pack.where
    s, _ = _twin_pair(9, 99)
    fp.add_cluster("c9", s)
    fp.plan_fleet({"c9": CH})
    assert fp._pack.where["c9"] == (shape, lane1)    # freed slot reused


@pytest.mark.slow
def test_fleet_loadgen_absorb_only_rebuilds_once():
    """steady-growth emits only absorbable deltas: each cluster's whole
    lifecycle costs exactly one dense rebuild (the initial pack)."""
    lg = FleetLoadGen(["steady-growth", "steady-growth"], seeds=[0, 1],
                      quick=True)
    metrics = lg.run()
    assert set(metrics) == {"steady-growth-0", "steady-growth-1"}
    summary = lg.summary()
    assert summary["clusters"] == 2
    assert summary["fleet_ticks"] > 0
    for key, acc in summary["per_cluster"].items():
        assert acc["rebuilds"] == 1, key
        assert acc["plans"] > 0 and acc["moves"] >= 0
    assert summary["slo_hit_rate"] == 1.0    # no SLO configured
