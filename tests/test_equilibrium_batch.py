"""The device-resident batched planner must produce *identical* move
sequences to the faithful §3.1 implementation — same shards, same
destinations, same order — across multi-pool, multi-class, hybrid-rule
and EC clusters, every tile shape, padding boundaries, and config
variations; and it must plan whole chunks of moves per host round-trip
(O(1) syncs per chunk, not O(k) per move)."""

import numpy as np
import pytest

from repro.core import EquilibriumConfig, equilibrium_balance, small_test_cluster
from repro.core.clustergen import cluster_a, cluster_c, cluster_f
from repro.core.equilibrium_batch import balance_batch, host_sync_count
from repro.core.equilibrium_jax import DenseState, balance_fast


def as_tuples(moves):
    return [(m.pg, m.slot, m.src_osd, m.dst_osd) for m in moves]


# ---------------------------------------------------------------------------
# bit-identical move sequences vs the faithful planner


def test_batch_matches_faithful_small():
    cfg = EquilibriumConfig()
    faithful_state = small_test_cluster()
    batch_state = small_test_cluster()
    a, _ = equilibrium_balance(faithful_state, cfg)
    b, recs = balance_batch(batch_state, cfg, record_trajectory=True)
    assert as_tuples(a) == as_tuples(b)
    assert np.isclose(faithful_state.utilization_variance(),
                      batch_state.utilization_variance())
    batch_state.check_valid()
    assert all(r.sources_tried >= 1 for r in recs)


def test_batch_matches_faithful_cluster_a():
    """Cluster A: multi-pool replicated, full convergence."""
    cfg = EquilibriumConfig()
    a, _ = equilibrium_balance(cluster_a(), cfg)
    b, _ = balance_batch(cluster_a(), cfg)
    assert as_tuples(a) == as_tuples(b)


@pytest.mark.slow
def test_batch_matches_faithful_cluster_c():
    """Cluster C: two device classes (hdd + nvme), multi-pool, full run."""
    cfg = EquilibriumConfig(max_moves=200)
    a, _ = equilibrium_balance(cluster_c(), cfg)
    b, _ = balance_batch(cluster_c(), cfg)
    assert as_tuples(a) == as_tuples(b)


@pytest.mark.slow
def test_batch_matches_faithful_cluster_f():
    """Cluster F: single-class single-big-pool, 78 OSDs."""
    cfg = EquilibriumConfig(max_moves=200)
    a, _ = equilibrium_balance(cluster_f(), cfg)
    b, _ = balance_batch(cluster_f(), cfg)
    assert as_tuples(a) == as_tuples(b)


@pytest.mark.slow
def test_batch_matches_numpy_hybrid_rule():
    """Cluster D's hybrid 1×ssd+2×hdd rule (multi-step slot geometry);
    compared against the dense-NumPy engine (itself property-equal to the
    faithful planner) to keep runtime reasonable."""
    from repro.core.clustergen import cluster_d
    cfg = EquilibriumConfig(max_moves=120)
    a, _ = balance_fast(cluster_d(), cfg)
    b, _ = balance_batch(cluster_d(), cfg)
    assert as_tuples(a) == as_tuples(b)


@pytest.mark.parametrize("kwargs", [
    dict(count_slack=1.0, k=5),
    dict(headroom=0.1),
    dict(min_variance_delta=1e-12),
    dict(k=100),                    # k > n_devices
])
def test_batch_matches_faithful_config_variants(kwargs):
    cfg = EquilibriumConfig(**kwargs)
    a, _ = equilibrium_balance(small_test_cluster(), cfg)
    b, _ = balance_batch(small_test_cluster(), cfg)
    assert as_tuples(a) == as_tuples(b)


@pytest.mark.parametrize("source_block,row_block,row_capacity,cluster", [
    pytest.param(1, 1, None, "small", id="1-1"),       # minimal tiles
    # ragged blocks (k=25 not a multiple of 3)
    pytest.param(3, 5, None, "small", id="3-5"),
    # the full (k, R_max, n_dev) tensor in one iteration
    pytest.param(25, 64, None, "small", id="25-64"),
    # the engine's default tile, on the single-step rules and on the
    # hybrid multi-step one (rows per device: 6-36 and 4-23)
    pytest.param(1, None, None, "small", id="default-small"),
    pytest.param(1, None, None, "hybrid", id="default-hybrid"),
    *(pytest.param(1, rb, None, "hybrid", id=f"1-{rb}-hybrid")
      for rb in (8, 16, 64)),
    # a width that leaves a short last tile of a source's rows
    pytest.param(1, 13, None, "hybrid", id="1-13-hybrid"),
    pytest.param(2, None, None, "hybrid", id="2-default-hybrid"),
    # the row table as tight as it rounds: destination lists fill up
    # mid-chunk and the host re-pads
    pytest.param(1, None, 1, "hybrid", id="default-tight-hybrid"),
    pytest.param(1, 13, 1, "hybrid", id="1-13-tight-hybrid"),
])
def test_batch_tile_shapes_identical(source_block, row_block, row_capacity,
                                     cluster):
    """Tile shape is a performance knob, never a semantics knob: every
    tile plans the faithful sequence, on the single-step rules and on
    the hybrid multi-step one."""
    make = {"small": small_test_cluster,
            "hybrid": _hybrid_small_cluster}[cluster]
    cfg = EquilibriumConfig()
    a, _ = equilibrium_balance(make(), cfg)
    b, _ = balance_batch(make(), cfg, source_block=source_block,
                         row_block=row_block, row_capacity=row_capacity)
    assert a and as_tuples(a) == as_tuples(b)


def test_batch_row_block_defaults_to_the_engine_tile():
    """Without a ``row_block`` the batch engine and the sharded engine
    walk :data:`ROW_BLOCK` rows a step; a caller's ``row_block`` keeps
    its meaning, and the row table is a whole number of tiles."""
    from repro.core.equilibrium_batch import ROW_BLOCK, BatchPlanner
    from repro.core.shard import ShardedBatchPlanner
    state = small_test_cluster()
    for cls in (BatchPlanner, ShardedBatchPlanner):
        for row_block, rb in ((None, ROW_BLOCK), (8, 8), (13, 13)):
            bp = cls(state, row_block=row_block)
            bp.sync()
            assert bp._rb == rb and bp._r_cap % rb == 0


# ---------------------------------------------------------------------------
# padding boundaries: row_capacity at / over the per-device row count


def test_batch_row_capacity_at_exact_boundary():
    """row_capacity == max rows/device: destinations fill the table and
    force the mid-run re-pad path; the sequence must not change."""
    cfg = EquilibriumConfig()
    a, _ = equilibrium_balance(small_test_cluster(), cfg)
    mx = max(len(s) for s in DenseState(small_test_cluster()).rows_on_dev)
    b, _ = balance_batch(small_test_cluster(), cfg, row_capacity=mx, chunk=4)
    assert as_tuples(a) == as_tuples(b)


def test_batch_row_capacity_clamped_below_occupancy():
    """A row_capacity below the densest device must be clamped up, not
    silently truncate candidate rows."""
    cfg = EquilibriumConfig()
    a, _ = equilibrium_balance(small_test_cluster(), cfg)
    b, _ = balance_batch(small_test_cluster(), cfg, row_capacity=1,
                         chunk=3, row_block=3)
    assert as_tuples(a) == as_tuples(b)


def test_batch_small_chunks_identical():
    """Chunk length only changes host round-trips, never the sequence."""
    cfg = EquilibriumConfig()
    a, _ = equilibrium_balance(small_test_cluster(), cfg)
    b, _ = balance_batch(small_test_cluster(), cfg, chunk=5)
    assert as_tuples(a) == as_tuples(b)


# ---------------------------------------------------------------------------
# host-sync regression: O(1) per chunk, not O(k) per move


def test_batch_host_syncs_constant_per_chunk():
    """The seed jax path blocked on bool(found) once per source per move
    (~k×moves syncs); the batched engine must transfer once per chunk."""
    cfg = EquilibriumConfig()
    state = small_test_cluster()
    before = host_sync_count()
    moves, _ = balance_batch(state, cfg, chunk=8)
    syncs = host_sync_count() - before
    assert len(moves) > 10
    n_chunks = -(-len(moves) // 8) + 1          # +1: the final empty chunk
    assert syncs <= n_chunks + 2, (syncs, len(moves))
    assert syncs < len(moves), "syncing per move defeats the batched design"


def test_batch_use_jax_delegates_to_batched_engine():
    """balance_fast(use_jax=True) is the batched engine (same sequence,
    chunked syncs) — the per-source legacy path is opt-in only."""
    cfg = EquilibriumConfig()
    a, _ = balance_fast(small_test_cluster(), cfg, use_jax=True)
    b, _ = balance_batch(small_test_cluster(), cfg)
    assert as_tuples(a) == as_tuples(b)


# ---------------------------------------------------------------------------
# kernel backend: the Pallas masked-select path is interchangeable


def test_batch_pallas_backend_identical():
    cfg = EquilibriumConfig()
    a, _ = balance_batch(small_test_cluster(), cfg)
    b, _ = balance_batch(small_test_cluster(), cfg, select_backend="pallas")
    assert as_tuples(a) == as_tuples(b)


def test_select_backend_resolution_on_tpu(monkeypatch):
    """On a TPU "auto" names the compiled kernel and the interpreting
    backend is refused; unknown names are refused everywhere."""
    import jax
    from repro.core.equilibrium_batch import BatchPlanner
    with pytest.raises(ValueError, match="unknown select_backend"):
        BatchPlanner(small_test_cluster(), select_backend="mosaic")
    assert BatchPlanner(small_test_cluster()).select_backend == "ref"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert BatchPlanner(small_test_cluster()).select_backend == "pallas-tpu"
    with pytest.raises(ValueError, match="without a TPU"):
        BatchPlanner(small_test_cluster(), select_backend="pallas")


def test_batch_empty_and_degenerate_clusters():
    from repro.core import ClusterState, Device, PlacementRule, Pool, TiB
    devs = [Device(id=0, capacity=8 * TiB, device_class="hdd", host="h0")]
    st = ClusterState(devs, [], {}, {})
    assert balance_batch(st, EquilibriumConfig()) == ([], [])


# ---------------------------------------------------------------------------
# warm start: BatchPlanner reuses the device carry across plan() calls


def test_warm_start_no_rebuild_and_bit_identical():
    """Budget-split warm planning must emit the cold-start sequence with a
    single dense-state build."""
    from repro.core.equilibrium_batch import BatchPlanner, dense_rebuild_count

    init = small_test_cluster()
    cold, _ = balance_batch(init.copy(), EquilibriumConfig())
    assert cold

    state = init.copy()
    planner = BatchPlanner(state, EquilibriumConfig())
    before = dense_rebuild_count()
    seq = []
    for budget in (3, 5, 10_000):
        moves, _ = planner.plan(max_moves=budget)
        seq += moves
    assert as_tuples(seq) == as_tuples(cold)
    assert dense_rebuild_count() - before == 1


def test_warm_start_small_chunks_stash_across_budgets():
    """Budgets that don't align with the chunk size exercise the stash:
    moves the device planned past the budget are emitted by later calls,
    still bit-identical to cold start."""
    from repro.core.equilibrium_batch import BatchPlanner, dense_rebuild_count

    init = small_test_cluster()
    cold, _ = balance_batch(init.copy(), EquilibriumConfig())

    state = init.copy()
    planner = BatchPlanner(state, EquilibriumConfig(), chunk=4)
    before = dense_rebuild_count()
    seq = []
    while True:
        moves, _ = planner.plan(max_moves=3)
        if not moves:
            break
        seq += moves
    assert as_tuples(seq) == as_tuples(cold)
    assert dense_rebuild_count() - before == 1


def test_warm_start_converged_tick_is_noop():
    """Two consecutive rebalance ticks on an unchanged cluster: the second
    must neither rebuild nor emit moves — matching a cold-start planner on
    the same (already converged) state."""
    from repro.core.equilibrium_batch import BatchPlanner, dense_rebuild_count

    state = small_test_cluster()
    planner = BatchPlanner(state, EquilibriumConfig())
    before = dense_rebuild_count()
    first, _ = planner.plan()
    assert first
    second, _ = planner.plan()
    assert second == []
    assert dense_rebuild_count() - before == 1
    cold_again, _ = balance_batch(state.copy(), EquilibriumConfig())
    assert cold_again == []


def test_warm_start_absorbs_growth_into_overshoot_stash():
    """Pool growth arriving while the planner holds an overshoot stash
    (budget 5 < chunk 64: the device planned past the budget) absorbs
    without a rebuild (PR 4): the stashed continuation — planned against
    the pre-growth state and never applied to the ClusterState — is
    discarded and the carry re-derived from the mutated state, so the
    continuation equals a cold plan from the mutated state."""
    from repro.core.equilibrium_batch import BatchPlanner, dense_rebuild_count

    state = small_test_cluster()
    planner = BatchPlanner(state, EquilibriumConfig())
    planner.plan(max_moves=5)
    assert planner._stash, "test premise: budget < chunk leaves a stash"
    state.grow_pool(0, 2.0 * 1024.0 ** 4)
    cold, _ = balance_batch(state.copy(), EquilibriumConfig())
    before = dense_rebuild_count()
    warm, _ = planner.plan()
    assert as_tuples(warm) == as_tuples(cold)
    assert dense_rebuild_count() - before == 0


def test_batch_legality_cache_opt_in_identical():
    """The cross-move legality cache (opt-in since PR 6) is a
    performance knob, never a semantics knob: cached and default
    fresh-evaluation walks both match the faithful sequence."""
    cfg = EquilibriumConfig()
    a, _ = equilibrium_balance(small_test_cluster(), cfg)
    b, _ = balance_batch(small_test_cluster(), cfg, legality_cache=True)
    c, _ = balance_batch(small_test_cluster(), cfg)
    assert as_tuples(a) == as_tuples(b)
    assert as_tuples(a) == as_tuples(c)


def test_out_device_never_a_destination_even_with_count_slack():
    """count_slack >= 1 disables the ideal-count exclusion of empty
    devices, so out devices must be masked explicitly — in every engine,
    identically to the faithful planner's move_is_legal check."""
    init = small_test_cluster()
    init.mark_out(init.devices[1].id)
    out = init.devices[1].id
    cfg = EquilibriumConfig(count_slack=1.0)
    faithful, _ = equilibrium_balance(init.copy(), cfg)
    for engine in ("numpy", "jax-legacy"):
        moves, _ = balance_fast(init.copy(), cfg, engine=engine)
        assert as_tuples(moves) == as_tuples(faithful), engine
    batch, _ = balance_batch(init.copy(), cfg)
    assert as_tuples(batch) == as_tuples(faithful)
    assert all(m.dst_osd != out for m in faithful)


# ---------------------------------------------------------------------------
# the array-built mirror vs the per-row construction it replaced


def _hybrid_small_cluster():
    """Multi-step 1 SSD + 2 HDD rule (cluster D's hybrid geometry) next
    to a replicated pool, at unit-test size."""
    from repro.core.cluster import PlacementRule, Pool, RuleStep, TiB
    from repro.core.clustergen import _make_devices
    from repro.core.crush import build_cluster
    devices = _make_devices([(12, 96 * TiB, "hdd"), (6, 12 * TiB, "ssd")],
                            osds_per_host=2, seed=4)
    hybrid = PlacementRule.hybrid([RuleStep("ssd", 1, "host"),
                                   RuleStep("hdd", 2, "host")])
    pools = [Pool(0, "hyb", 48, hybrid, stored_bytes=6 * TiB),
             Pool(1, "rbd", 32, PlacementRule.replicated(3, "host", "hdd"),
                  stored_bytes=10 * TiB)]
    return build_cluster(devices, pools, seed=4)


def _sparse_ids_cluster():
    """small_test_cluster with OSD ids 7, 10, 13, ... in reversed device
    order: ids are neither contiguous nor the device index."""
    import dataclasses
    from repro.core.cluster import ClusterState
    base = small_test_cluster()
    new_id = {d.id: 3 * d.id + 7 for d in base.devices}
    devices = [dataclasses.replace(d, id=new_id[d.id])
               for d in reversed(base.devices)]
    acting = {pg: [new_id[o] for o in osds]
              for pg, osds in base.acting.items()}
    return ClusterState(devices, list(base.pools.values()), acting,
                        base.shard_sizes)


def _out_device_cluster():
    state = small_test_cluster()
    state.mark_out(state.devices[1].id)
    return state


def _equal_sizes_cluster():
    """No shard-size jitter: every PG of a pool has the same size, so the
    row tables' order rests on the row-ascending tie break."""
    from repro.core.cluster import PlacementRule, Pool, TiB
    from repro.core.clustergen import _make_devices
    from repro.core.crush import build_cluster
    devices = _make_devices([(12, 96 * TiB, "hdd")], osds_per_host=2,
                            seed=0)
    r3 = PlacementRule.replicated(3, "host", "hdd")
    pools = [Pool(0, "rbd", 64, r3, stored_bytes=12 * TiB),
             Pool(1, "fs", 32, r3, stored_bytes=4 * TiB)]
    return build_cluster(devices, pools, seed=0, size_jitter=0.0)


MIRROR_CLUSTERS = {
    "small": small_test_cluster,
    "cluster-a": cluster_a,
    "hybrid-rule": _hybrid_small_cluster,
    "sparse-osd-ids": _sparse_ids_cluster,
    "out-device": _out_device_cluster,
    "equal-sizes": _equal_sizes_cluster,
}


def _per_row_reference(state):
    """The per-shard Python construction the array-built mirror replaced
    (kept as the plain reference): flat table, rule-step attributes,
    acting table, per-device row sets and packed row tables, plus the
    dense engine's membership and domain occupancy."""
    from repro.core import legality
    leg = legality.LegalityState.from_cluster(state)
    pgs = sorted(state.acting)
    pg_index = {pg: i for i, pg in enumerate(pgs)}
    pool_index = {p: i for i, p in enumerate(sorted(state.pools))}
    rows = [(pg, slot) for pg in pgs
            for slot in range(state.pools[pg[0]].size)]
    ref = {"shard_key": rows}
    ref["sh_pg"] = np.array([pg_index[pg] for pg, _ in rows])
    ref["sh_pool"] = np.array([pool_index[pg[0]] for pg, _ in rows])
    ref["sh_size"] = np.array([state.shard_sizes[pg] for pg, _ in rows])
    ref["sh_dev"] = np.array([state.idx(state.acting[pg][slot])
                              for pg, slot in rows])
    lvl_id = {l: i for i, l in enumerate(leg.levels)}
    geometry = {p: legality.rule_slot_steps(state.pools[p].rule)
                for p in state.pools}
    for name in ("level", "class", "step", "slot", "sbase", "scnt"):
        ref["sh_" + name] = np.empty(len(rows), dtype=np.int64)
    for r, (pg, slot) in enumerate(rows):
        si, base, scnt, domain, dev_class = geometry[pg[0]][slot]
        ref["sh_level"][r] = lvl_id[domain]
        ref["sh_class"][r] = (leg.class_id[dev_class]
                              if dev_class is not None else -1)
        ref["sh_step"][r] = si
        ref["sh_slot"][r] = slot
        ref["sh_sbase"][r] = base
        ref["sh_scnt"][r] = scnt

    n_slots = max(p.size for p in state.pools.values())
    acting = np.full((len(pgs), n_slots), -1, np.int32)
    for pg, pgi in pg_index.items():
        osds = state.acting[pg]
        acting[pgi, :len(osds)] = [state.idx(o) for o in osds]
    ref["acting"] = acting
    rows_on_dev = [set() for _ in range(state.n_devices)]
    for r in range(len(rows)):
        rows_on_dev[ref["sh_dev"][r]].add(r)
    ref["rows_on_dev"] = rows_on_dev
    ref["nrows"] = np.array([len(s) for s in rows_on_dev], np.int32)
    ref["rows"] = []
    for s in rows_on_dev:
        ref["rows"].append(sorted(s, key=lambda r: (-ref["sh_size"][r], r)))
    ref["ideal"] = np.stack([state.ideal_shard_count(state.pools[p])
                             for p in sorted(state.pools)])
    ref["pool_counts"] = np.stack([state.pool_counts[p]
                                   for p in sorted(state.pools)]
                                  ).astype(np.float64)
    ref["util"] = state.used() / leg.cap

    n_pg, n_dev = len(pgs), state.n_devices
    ref["member"] = np.zeros((n_pg, n_dev), dtype=bool)
    max_steps = max(len(state.pools[p].rule.steps) for p in state.pools)
    ref["occ"] = {lvl: np.zeros((n_pg, max_steps, leg.n_domains[lvl]),
                                dtype=np.int16) for lvl in leg.levels}
    for r, (pg, slot) in enumerate(rows):
        pgi, di = pg_index[pg], ref["sh_dev"][r]
        ref["member"][pgi, di] = True
        lvl = leg.levels[ref["sh_level"][r]]
        ref["occ"][lvl][pgi, ref["sh_step"][r],
                        leg.dev_domain(lvl)[di]] += 1
    ref["occ_dev"] = np.zeros((n_pg, max_steps, n_dev), dtype=np.int16)
    pg_pool = np.array([pg[0] for pg in pgs])
    for p in sorted(state.pools):
        idx = np.flatnonzero(pg_pool == p)
        for si, rstep in enumerate(state.pools[p].rule.steps):
            lvl = rstep.failure_domain
            ref["occ_dev"][idx, si] = \
                ref["occ"][lvl][idx, si][:, leg.dev_domain(lvl)]
    return ref


def _same(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.dtype == b.dtype and a.shape == b.shape \
        and a.tobytes() == b.tobytes()


@pytest.mark.parametrize("name", sorted(MIRROR_CLUSTERS))
def test_batch_mirror_matches_per_row_reference(name):
    """The cold build's device tables are bitwise the per-row
    construction's, cast to the dtypes the chunk program reads."""
    import jax
    from repro.core.equilibrium_batch import BatchPlanner
    state = MIRROR_CLUSTERS[name]()
    ref = _per_row_reference(state)
    bp = BatchPlanner(state, EquilibriumConfig(), chunk=8)
    with jax.enable_x64(True):
        bp.sync()
        const, dyn = jax.device_get((bp._const, bp._dyn))
    dense = bp._dense
    for key in ("sh_pg", "sh_pool", "sh_size", "sh_dev", "sh_level",
                "sh_class", "sh_step", "sh_slot", "sh_sbase", "sh_scnt"):
        assert _same(getattr(dense, key), ref[key]), key
    for i, key in enumerate(("sh_pg", "sh_pool", "sh_class", "sh_level",
                             "sh_slot", "sh_sbase", "sh_scnt"), start=5):
        assert _same(const[i], ref[key].astype(np.int32)), key
    assert _same(const[4], ref["sh_size"])
    assert _same(const[12], ref["ideal"])
    assert _same(dyn[1], ref["util"])
    assert _same(dyn[4], ref["acting"])
    assert _same(dyn[5], ref["pool_counts"])
    assert _same(dyn[8], ref["nrows"])
    assert dyn[7].shape == (state.n_devices, bp._r_cap)
    assert bp._r_cap >= int(ref["nrows"].max()) + bp.chunk
    for d, want in enumerate(ref["rows"]):
        assert dyn[7][d, :len(want)].tolist() == want, d
        assert (dyn[7][d, len(want):] == -1).all(), d


@pytest.mark.parametrize("name", sorted(MIRROR_CLUSTERS))
def test_dense_state_matches_per_row_reference(name):
    """DenseState builds its flat table through the shared ShardMirror
    and keeps the legacy engine's own membership, domain occupancy and
    per-device row sets exactly as the per-row construction gave them."""
    state = MIRROR_CLUSTERS[name]()
    ref = _per_row_reference(state)
    dense = DenseState(state)
    assert dense.shard_key == ref["shard_key"]
    for key in ("sh_pg", "sh_pool", "sh_size", "sh_dev", "sh_level",
                "sh_class", "sh_step", "sh_slot", "sh_sbase", "sh_scnt",
                "member", "occ_dev"):
        assert _same(getattr(dense, key), ref[key]), key
    assert dense.occ.keys() == ref["occ"].keys()
    for lvl, occ in ref["occ"].items():
        assert _same(dense.occ[lvl], occ), lvl
    assert dense.rows_on_dev == ref["rows_on_dev"]
    assert all(type(r) is int for s in dense.rows_on_dev for r in s)
    assert _same(dense.ideal, ref["ideal"])
    assert _same(dense.pool_counts, ref["pool_counts"])
    assert _same(dense.util, ref["util"])
    assert not dense.stale


# ---------------------------------------------------------------------------
# the per-plan account of moved classes and of the destination axis


def _hand_count(state, moves, width):
    """(moves by class name, summed destination columns, summed columns
    the moved rows' rule-step classes admit), from the cluster alone."""
    from repro.core.legality import rule_slot_steps
    by_class, live = {}, 0
    for m in moves:
        cls = rule_slot_steps(state.pools[m.pg[0]].rule)[m.slot][4]
        name = cls if cls is not None else "any"
        by_class[name] = by_class.get(name, 0) + 1
        live += (width if cls is None else
                 sum(d.device_class == cls for d in state.devices))
    return by_class, width * len(moves), live


def _class_counters(deltas):
    prefix = "batch.moves_by_class{cls="
    return ({k[len(prefix):-1]: v for k, v in deltas.items()
             if k.startswith(prefix)},
            deltas.get("batch.dst_cols", 0),
            deltas.get("batch.dst_cols_live", 0))


@pytest.mark.parametrize("planner,kwargs,pad", [
    ("equilibrium_batch", {}, 0),
    ("equilibrium_batch_sharded", {"n_shards": 1}, 5),
])
def test_move_class_counters_match_a_hand_count(planner, kwargs, pad):
    """Once per plan, over the moved rows: moves by rule-step class, and
    the destination columns of each moved row's tile with the share its
    class admits.  The sharded carry evaluates its pad columns too."""
    from repro.core.planner import create_planner
    from repro.obs import registry
    initial = _hybrid_small_cluster()
    width = initial.n_devices + pad
    if pad:
        kwargs = dict(kwargs, pad_devices=width)
    reg = registry()
    snap = reg.snapshot()
    state = initial.copy()
    result = create_planner(planner, **kwargs).plan(state)
    moves = result.moves
    assert moves
    by_class, cols, live = _class_counters(reg.deltas_since(snap))
    assert sum(by_class.values()) == len(moves)
    assert set(by_class) == {"ssd", "hdd"}
    assert (by_class, cols, live) == _hand_count(initial, moves, width)
    assert 0 < live < cols


def test_move_class_counters_count_a_classless_step_as_all_columns():
    from repro.core.planner import create_planner
    from repro.obs import registry
    state = cluster_a()
    assert all(s.device_class is None for p in state.pools.values()
               for s in p.rule.steps)
    reg = registry()
    snap = reg.snapshot()
    moves = create_planner("equilibrium_batch").plan(state).moves
    by_class, cols, live = _class_counters(reg.deltas_since(snap))
    assert by_class == {"any": len(moves)}
    assert cols == live == len(moves) * state.n_devices


@pytest.mark.parametrize("tracer", [None, {}, {"device_counters": True}])
def test_hybrid_moves_identical_with_the_tracer_on_and_off(tracer):
    from repro import obs
    from repro.core.planner import create_planner
    off = create_planner("equilibrium_batch").plan(_hybrid_small_cluster())
    if tracer is None:
        on = create_planner("equilibrium_batch").plan(
            _hybrid_small_cluster())
    else:
        with obs.tracing(**tracer):
            on = create_planner("equilibrium_batch").plan(
                _hybrid_small_cluster())
    assert off.moves and as_tuples(on.moves) == as_tuples(off.moves)


def test_walk_steps_counter_pins_cluster_d(monkeypatch):
    """``batch.walk_steps`` folds, once per plan, the walk's iterations
    that each chunk returns beside its moves (one count per scan step):
    on cluster D's cold plan it is their exact sum, and the same count as
    the ``batch.tiles_walked`` device counter."""
    from repro import obs
    from repro.core import equilibrium_batch as eb
    from repro.core.clustergen import cluster_d
    from repro.core.planner import create_planner
    fetched = []
    fetch = eb._fetch

    def spy(tree, what):
        out = fetch(tree, what)
        if what == "chunk":
            fetched.append(np.asarray(out[1]))
        return out

    monkeypatch.setattr(eb, "_fetch", spy)
    reg = obs.registry()
    snap = reg.snapshot()
    moves = create_planner("equilibrium_batch").plan(cluster_d()).moves
    steps = reg.deltas_since(snap)["batch.walk_steps"]
    per_step = np.concatenate(fetched)
    assert len(moves) == 333
    assert steps == int(per_step.sum()) == 1650
    assert (per_step[:len(moves)] >= 1).all()
    snap = reg.snapshot()
    with obs.tracing(device_counters=True):
        create_planner("equilibrium_batch").plan(cluster_d())
    d = reg.deltas_since(snap)
    assert d["batch.walk_steps"] == d["batch.tiles_walked"] == steps
