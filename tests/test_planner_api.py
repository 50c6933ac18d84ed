"""The unified Planner protocol (PR 3): registry resolution, PlanResult
equivalence with the legacy entry points, the typed ClusterDelta stream,
and — the heart of it — delta-aware incremental replanning: pool-growth
and device-add deltas absorbed into the BatchPlanner device carry with
*zero* dense rebuilds and move sequences bit-identical to a cold start,
at unit scale and across every registered lifecycle scenario."""

import json

import pytest

from repro.core import (Device, EquilibriumConfig, MgrBalancerConfig,
                        Movement, PlanResult, Planner, TiB,
                        available_planners, create_planner, get_planner_spec,
                        small_test_cluster)
from repro.core.cluster import (DeviceAddDelta, DeviceOutDelta, MovementDelta,
                                PoolCreateDelta, PoolGrowthDelta)
from repro.core.equilibrium import _balance
from repro.core.equilibrium_batch import dense_rebuild_count
from repro.core.mgr_balancer import _balance as _mgr_balance
from repro.sim import SCENARIOS, ScenarioEngine, run_scenario


def tup(moves):
    return [(m.pg, m.slot, m.src_osd, m.dst_osd) for m in moves]


# ---------------------------------------------------------------------------
# registry + protocol surface


def test_registry_has_all_balancers():
    assert {"equilibrium", "equilibrium_faithful", "equilibrium_batch",
            "equilibrium_jax_legacy", "mgr", "none"} \
        <= set(available_planners())


def test_unknown_planner_rejected_with_names():
    with pytest.raises(ValueError, match="equilibrium_batch"):
        create_planner("nope")
    with pytest.raises(ValueError):
        get_planner_spec("nope")


def test_every_registered_planner_satisfies_protocol():
    for name in available_planners():
        planner = create_planner(name)
        assert isinstance(planner, Planner), name
        assert planner.name == name
        result = planner.plan(small_test_cluster(), budget=3)
        assert isinstance(result, PlanResult)
        assert len(result) == len(result.moves) <= 3
        assert result.planner == name
        assert "planning_seconds" in result.stats
        assert planner.observe(PoolGrowthDelta(1, 0, 1.0)) in (True, False)
        planner.reset()


def test_create_planner_drops_unaccepted_kwargs():
    # "none" takes no config; the scenario engine passes cfg+chunk to all
    planner = create_planner("none", cfg=EquilibriumConfig(), chunk=7)
    assert planner.plan(small_test_cluster()).moves == []


def test_spec_names_sim_config_attr():
    assert get_planner_spec("equilibrium_batch").sim_config_attr == \
        "equilibrium"
    assert get_planner_spec("mgr").sim_config_attr == "mgr"
    assert get_planner_spec("none").sim_config_attr is None


# ---------------------------------------------------------------------------
# PlanResult equivalence with the legacy entry points


@pytest.mark.parametrize("name", ["equilibrium_faithful", "equilibrium",
                                  "equilibrium_batch"])
def test_equilibrium_planners_match_reference(name):
    cfg = EquilibriumConfig()
    ref, _ = _balance(small_test_cluster(), cfg)
    result = create_planner(name, cfg=cfg).plan(small_test_cluster())
    assert tup(result.moves) == tup(ref)


def test_mgr_planner_matches_reference_and_normalizes_records():
    cfg = MgrBalancerConfig()
    ref, ref_traj = _mgr_balance(small_test_cluster(), cfg,
                                 record_trajectory=True)
    result = create_planner("mgr", cfg=cfg).plan(small_test_cluster(),
                                                 record_trajectory=True)
    assert tup(result.moves) == tup(ref)
    assert len(result.records) == len(ref)
    assert result.variance_trajectory == [t["variance"] for t in ref_traj]
    assert all(r.sources_tried == 1 for r in result.records)


def test_plan_result_trajectory_and_tuple():
    result = create_planner("equilibrium").plan(small_test_cluster(),
                                                record_trajectory=True)
    assert result.as_tuple() == (result.moves, result.records)
    traj = result.variance_trajectory
    assert len(traj) == len(result.moves)
    assert traj == sorted(traj, reverse=True)  # each move strictly improves


def test_budget_caps_moves():
    result = create_planner("equilibrium").plan(small_test_cluster(),
                                                budget=4)
    assert 0 < len(result.moves) <= 4


def test_deprecated_shims_warn_once_and_delegate():
    from repro.core import (balance_batch, balance_fast, equilibrium_balance,
                            mgr_balance)
    from repro.core._compat import _WARNED
    _WARNED.clear()
    ref, _ = _balance(small_test_cluster(), EquilibriumConfig())
    with pytest.warns(DeprecationWarning):
        moves, _ = equilibrium_balance(small_test_cluster())
    assert tup(moves) == tup(ref)
    with pytest.warns(DeprecationWarning):
        moves, _ = balance_fast(small_test_cluster())
    assert tup(moves) == tup(ref)
    with pytest.warns(DeprecationWarning):
        moves, _ = balance_batch(small_test_cluster())
    assert tup(moves) == tup(ref)
    with pytest.warns(DeprecationWarning):
        mgr_balance(small_test_cluster())


# ---------------------------------------------------------------------------
# the typed delta stream


def test_mutators_emit_contiguous_typed_deltas():
    state = small_test_cluster()
    seen = []
    state.subscribe(seen.append)

    state.grow_pool(0, 1.0 * TiB)
    dev = Device(id=900, capacity=8 * TiB, device_class="hdd", host="hx")
    state.add_device(dev)
    state.mark_out(900)
    mv, _ = _balance(state.copy(), EquilibriumConfig(max_moves=1))
    state.apply(mv[0])

    kinds = [type(d) for d in seen]
    assert kinds == [PoolGrowthDelta, DeviceAddDelta, DeviceOutDelta,
                     MovementDelta]
    assert [d.epoch for d in seen] == \
        list(range(seen[0].epoch, seen[0].epoch + 4))
    assert seen[0].pool_id == 0 and seen[0].user_bytes == 1.0 * TiB
    assert seen[1].device is dev
    assert seen[2].osd_id == 900 and seen[2].out
    assert seen[3].movement == mv[0]
    assert state.mutation_epoch == seen[-1].epoch


def test_pool_create_delta_and_unsubscribe():
    from repro.core import PlacementRule, Pool
    from repro.core.crush import place_pg
    state = small_test_cluster()
    seen = []
    state.subscribe(seen.append)
    rule = PlacementRule.replicated(2, "host", "hdd")
    pool = Pool(55, "p", 4, rule, stored_bytes=0.1 * TiB)
    acting = {(55, i): place_pg(state.devices, pool, i, seed=1)
              for i in range(4)}
    sizes = {(55, i): pool.nominal_shard_size for i in range(4)}
    state.add_pool(pool, acting, sizes)
    assert [type(d) for d in seen] == [PoolCreateDelta]
    assert seen[0].pool_id == 55
    state.unsubscribe(lambda d: None)     # never registered: no-op
    state.unsubscribe(seen.append)
    state.grow_pool(0, 1.0 * TiB)
    assert len(seen) == 1                 # delivery stopped


def test_subscriber_returning_false_is_pruned():
    state = small_test_cluster()
    calls = []

    def once(delta):
        calls.append(delta)
        return False

    state.subscribe(once)
    state.grow_pool(0, 1.0 * TiB)
    state.grow_pool(0, 1.0 * TiB)
    assert len(calls) == 1


def test_copies_do_not_inherit_subscribers():
    state = small_test_cluster()
    seen = []
    state.subscribe(seen.append)
    clone = state.copy()
    clone.grow_pool(0, 1.0 * TiB)
    assert seen == []


# ---------------------------------------------------------------------------
# delta-aware incremental replanning (the tentpole property)


def _warm_vs_cold(mutate, chunk=5, first_budget=5):
    """Plan a bit, mutate externally, then compare the warm continuation
    against a cold start from the mutated state; returns rebuild count."""
    state = small_test_cluster()
    planner = create_planner("equilibrium_batch", chunk=chunk)
    planner.plan(state, budget=first_budget)
    mutate(state)
    cold, _ = _balance(state.copy(), EquilibriumConfig())
    before = dense_rebuild_count()
    warm = planner.plan(state)
    assert tup(warm.moves) == tup(cold)
    return dense_rebuild_count() - before


def test_pool_growth_absorbed_without_rebuild():
    assert _warm_vs_cold(lambda s: s.grow_pool(0, 2.0 * TiB)) == 0


def test_device_add_absorbed_without_rebuild():
    def add(state):
        state.add_device(Device(id=500, capacity=8 * TiB,
                                device_class="hdd", host="hx"))
    assert _warm_vs_cold(add) == 0


def test_new_trailing_device_class_absorbed():
    """A first ssd joining an hdd-only view appends a class id (sorted
    order preserved) — still absorbable."""
    def add(state):
        state.add_device(Device(id=501, capacity=4 * TiB,
                                device_class="zzz-new", host="hz"))
    assert _warm_vs_cold(add) == 0


def test_renumbering_device_class_falls_back_to_rebuild():
    """A new class sorting before existing ones renumbers the carry's
    class ids: absorption must refuse and rebuild, staying identical."""
    def add(state):
        state.add_device(Device(id=502, capacity=4 * TiB,
                                device_class="aaa-first", host="ha"))
    assert _warm_vs_cold(add) == 1


def test_mixed_growth_and_adds_absorbed_in_one_gap():
    def mutate(state):
        state.grow_pool(1, 1.0 * TiB)
        state.add_device(Device(id=503, capacity=6 * TiB,
                                device_class="hdd", host="hy"))
        state.grow_pool(0, 0.5 * TiB)
    assert _warm_vs_cold(mutate) == 0


def _foreign_move(state):
    mv, _ = _balance(state.copy(), EquilibriumConfig(max_moves=1))
    state.apply(mv[0])


def _create_pool(state):
    from repro.core import PlacementRule, Pool
    from repro.core.crush import place_pg
    pid = 1 + max(state.pools)
    rule = PlacementRule.replicated(2, "host", "hdd")
    pool = Pool(pid, "fresh", 8, rule, stored_bytes=0.4 * TiB)
    acting = {(pid, i): place_pg(state.devices, pool, i, seed=3)
              for i in range(8)}
    sizes = {(pid, i): pool.nominal_shard_size for i in range(8)}
    state.add_pool(pool, acting, sizes)


@pytest.mark.parametrize("mutate", [
    lambda s: s.mark_out(s.devices[1].id),
    _foreign_move,
    _create_pool,
], ids=["device-out", "foreign-movement", "pool-create"])
def test_full_coverage_deltas_absorbed_without_rebuild(mutate):
    """PR 4 closes the absorption gaps: device out, a foreign balancer's
    movement, and pool creation all absorb into the device carry — zero
    dense rebuilds, continuation bit-identical to a cold start."""
    assert _warm_vs_cold(mutate) == 0


def test_device_back_in_absorbed_without_rebuild():
    def mutate(state):
        state.mark_out(state.devices[1].id)
        state.mark_out(state.devices[1].id, out=False)
    assert _warm_vs_cold(mutate) == 0


def test_drain_like_mix_absorbed_without_rebuild():
    """The churn shape the sim engine produces on a DeviceOut/DeviceFail:
    one out-delta followed by a burst of re-placement movements — all
    absorbed in a single gap."""
    def mutate(state):
        out = state.devices[2].id
        state.mark_out(out)
        for (pg, slot) in sorted(state.shards_on[out]):
            for dst in state.devices:
                if state.move_is_legal(pg, slot, dst.id):
                    state.apply(Movement(pg, slot, out, dst.id,
                                         state.shard_sizes[pg]))
                    break
    assert _warm_vs_cold(mutate) == 0


def test_wider_rule_pool_create_absorbed():
    """A created pool whose rule is wider than any existing one grows
    the acting table's slot axis (a recompile, not a rebuild) — still
    absorbed, still bit-identical."""
    def mutate(state):
        from repro.core import PlacementRule, Pool
        from repro.core.crush import place_pg
        rule = PlacementRule.erasure(3, 2, "host", "hdd")    # size 5 > 3
        pid = 1 + max(state.pools)
        pool = Pool(pid, "wide-ec", 12, rule, ec_k=3,
                    stored_bytes=2.0 * TiB)
        acting = {(pid, i): place_pg(state.devices, pool, i, seed=9)
                  for i in range(12)}
        sizes = {(pid, i): pool.nominal_shard_size for i in range(12)}
        state.add_pool(pool, acting, sizes)
    assert _warm_vs_cold(mutate) == 0


def test_unknown_delta_type_falls_back_to_rebuild():
    """The conservative fallback survives for delta types the absorber
    does not know — correctness never depends on absorption."""
    from dataclasses import dataclass

    from repro.core import ClusterDelta

    @dataclass(frozen=True)
    class WeirdDelta(ClusterDelta):
        pass

    def mutate(state):
        state.mutation_epoch += 1
        state._notify(WeirdDelta(state.mutation_epoch))

    assert _warm_vs_cold(mutate) == 1


def test_renumbering_pool_id_falls_back_to_rebuild():
    """A pool id sorting before an existing one would renumber the
    carry's dense pool/pg/shard rows: absorption must refuse and
    rebuild, staying bit-identical."""
    from repro.core import PlacementRule, Pool, build_cluster
    from repro.core.crush import place_pg
    devs = small_test_cluster().devices
    rule = PlacementRule.replicated(3, "host", "hdd")
    state = build_cluster(devs, [
        Pool(0, "a", 32, rule, stored_bytes=120 * TiB),
        Pool(5, "b", 16, rule, stored_bytes=60 * TiB)], seed=1)
    planner = create_planner("equilibrium_batch", chunk=5)
    planner.plan(state, budget=5)
    pool = Pool(3, "mid", 8, rule, stored_bytes=5 * TiB)   # sorts between
    acting = {(3, i): place_pg(devs, pool, i, seed=1) for i in range(8)}
    sizes = {(3, i): pool.nominal_shard_size for i in range(8)}
    state.add_pool(pool, acting, sizes)
    cold, _ = _balance(state.copy(), EquilibriumConfig())
    before = dense_rebuild_count()
    warm = planner.plan(state)
    assert tup(warm.moves) == tup(cold)
    assert dense_rebuild_count() - before == 1


def test_growth_absorbed_into_overshoot_stash():
    """chunk > budget leaves device-planned overshoot in the stash; that
    continuation predates the growth, so the absorber discards it and
    re-derives the carry from the mutated state — no rebuild, and the
    emitted stream still equals a cold start (the stash fix, PR 4)."""
    assert _warm_vs_cold(lambda s: s.grow_pool(0, 2.0 * TiB),
                         chunk=64, first_budget=5) == 0


def test_observe_reports_absorbability():
    state = small_test_cluster()
    planner = create_planner("equilibrium_batch", chunk=4)
    planner.plan(state, budget=4)
    impl = planner._impl
    state.grow_pool(0, 1.0 * TiB)
    assert impl.observe(PoolGrowthDelta(state.mutation_epoch, 0, 1.0 * TiB))
    state.mark_out(state.devices[0].id)
    assert impl.observe(
        DeviceOutDelta(state.mutation_epoch, state.devices[0].id, True))
    # an unstamped delta cannot be ordered into the stream: not absorbable
    assert not impl.observe(PoolGrowthDelta(-1, 0, 1.0 * TiB))


def test_conflicting_epoch_claim_forces_rebuild_not_corruption():
    """A manual observe() whose epoch collides with a different recorded
    delta must poison absorption (rebuild), never replace the real delta
    — replacing it would refresh the carry against the wrong mutation."""
    state = small_test_cluster()
    planner = create_planner("equilibrium_batch", chunk=4)
    planner.plan(state, budget=4)
    state.add_device(Device(id=504, capacity=8 * TiB,
                            device_class="hdd", host="hz"))
    # same epoch, different (false) story about what happened
    assert not planner.observe(
        PoolGrowthDelta(state.mutation_epoch, 0, 1.0 * TiB))
    cold, _ = _balance(state.copy(), EquilibriumConfig())
    before = dense_rebuild_count()
    warm = planner.plan(state)
    assert tup(warm.moves) == tup(cold)
    assert dense_rebuild_count() - before == 1


def test_own_replay_overflowing_pending_cap_does_not_poison_absorption(
        monkeypatch):
    """plan() replays its own moves through state.apply, feeding its own
    MovementDeltas back through the subscription; overflowing PENDING_CAP
    there must not permanently disable absorption — after the end-of-plan
    sync the planner is consistent again and later growth absorbs."""
    from repro.core.equilibrium_batch import BatchPlanner
    monkeypatch.setattr(BatchPlanner, "PENDING_CAP", 3)
    state = small_test_cluster()
    planner = create_planner("equilibrium_batch", chunk=8)
    planner.plan(state, budget=8)            # 8 replayed moves > cap
    state.grow_pool(0, 2.0 * TiB)
    cold, _ = _balance(state.copy(), EquilibriumConfig())
    before = dense_rebuild_count()
    warm = planner.plan(state)
    assert tup(warm.moves) == tup(cold)
    assert dense_rebuild_count() - before == 0


def test_reset_forces_cold_start():
    state = small_test_cluster()
    planner = create_planner("equilibrium_batch", chunk=4)
    planner.plan(state, budget=4)
    before = dense_rebuild_count()
    planner.reset()
    planner.plan(state, budget=4)
    assert dense_rebuild_count() - before == 1


# ---------------------------------------------------------------------------
# scenario-level acceptance: warm start across a live cluster lifetime


def test_steady_growth_rebuilds_at_most_once():
    """The ROADMAP's open item, closed: growth ticks no longer force a
    dense rebuild — the whole steady-growth lifecycle builds the device
    mirror exactly once (the initial build)."""
    before = dense_rebuild_count()
    run_scenario("steady-growth", "equilibrium_batch", seed=0, quick=True)
    assert dense_rebuild_count() - before <= 1


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_scenario_warm_batch_identical_to_cold(name):
    """Byte-identical metrics between the warm-started batch planner and
    the cold-per-tick dense engine across every scenario: the emitted
    move stream (and therefore every physical series) never deviates
    from a cold start, whatever mix of deltas the timeline throws."""
    warm = run_scenario(name, "equilibrium_batch", seed=0, quick=True)
    cold = run_scenario(name, "equilibrium", seed=0, quick=True)
    assert json.dumps(warm["metrics"], sort_keys=True) == \
        json.dumps(cold["metrics"], sort_keys=True)


def test_planners_in_class_raises_when_an_engine_cannot_import(monkeypatch):
    """A registered engine whose module fails to import must fail the
    enumeration, not silently drop out of the equivalence class."""
    from repro.core import planner as planner_mod
    monkeypatch.setitem(planner_mod._LAZY_PLANNERS, "broken_engine",
                        "repro.core.no_such_engine_module")
    with pytest.raises(ImportError):
        planner_mod.planners_in_class("equilibrium")


def test_compile_cache_dir(monkeypatch):
    """The entry points' cache helper defers to JAX_COMPILATION_CACHE_DIR
    and otherwise keeps the cache at the repo's fixed .cache/jax."""
    import jax
    from repro import compile_cache
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: pytest.fail(f"config changed: {a}"))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
    assert compile_cache.enable_compile_cache() == "/elsewhere/jax"
    seen = []
    monkeypatch.setattr(jax.config, "update", lambda *a: seen.append(a))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    assert path.endswith("/.cache/jax")
    assert seen == [("jax_compilation_cache_dir", path)]


def test_engine_accepts_injected_planner():
    """Third-party planners plug into the scenario engine by instance."""

    class Noop:
        name = "custom-noop"

        def plan(self, state, *, budget=None, record_trajectory=False,
                 record_free_space=True):
            return PlanResult([], [], self.name)

        def observe(self, delta):
            return True

        def reset(self):
            pass

    state, events, cfg = SCENARIOS["steady-growth"].build(0, True)
    engine = ScenarioEngine(state, events, cfg, planner=Noop())
    metrics = engine.run()
    assert metrics.planned_moves[-1] == 0
