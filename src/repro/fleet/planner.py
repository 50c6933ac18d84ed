"""Vmapped multi-cluster planner: one device dispatch plans a fleet.

A storage operator runs *fleets* of Ceph clusters, and the per-cluster
planning cost of :class:`~repro.core.equilibrium_batch.BatchPlanner` is
dominated at steady state by dispatch latency, not FLOPs: each cluster's
chunk step is one jit call plus one host sync, serialized per cluster.
:class:`FleetPlanner` amortizes both across the fleet — clusters are
padded to shared shape buckets (:mod:`repro.fleet.pack`) and one
``jax.vmap`` of the *same* ``_plan_chunk_impl`` the single-cluster
engine jits plans every cluster in a bucket per dispatch, with one host
sync per bucket-round instead of one per cluster-chunk.

The vmap is bit-exact per lane: the chunk step's carry updates are
branch-free masked scatters (``apply_move`` with ``ok=False`` is a
bitwise no-op), its ``lax.while_loop`` runs while *any* lane is
unresolved with every resolved lane's carry passed through unchanged,
and the per-cluster ``n_real`` / ``k_eff`` / ``active0`` scalars keep
shape padding out of every criterion.  A fleet plan therefore emits,
per cluster, **exactly** the move sequence a serial
:class:`BatchPlanner` run would (property-tested in
tests/test_fleet.py, including under interleaved delta streams and
heterogeneous shapes).

The latency-SLO knob (``slo_seconds``) bounds a fleet tick's wall time:
the deadline is checked before every bucket dispatch after the first
(the first dispatch is the progress guarantee), and an expired tick
returns each unfinished cluster's moves fetched so far — a *partial but
valid* plan (every fetched move is already applied in the carry and is
replayed through :meth:`ClusterState.apply`, which re-validates it;
planned-but-unfetched work simply stays in the carry for the next
tick).  Each cluster's :class:`~repro.core.planner.PlanResult` reports
the cut through the schema'd ``slo_expired`` / ``plan_freshness_seconds``
/ ``converged`` / ``variance_after`` stats keys.
"""

from __future__ import annotations

import time
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..core.cluster import ClusterDelta, ClusterState
from ..core.equilibrium import EquilibriumConfig
from ..core.equilibrium_batch import BatchPlanner, _fetch, _plan_chunk_impl
from ..core.planner import PlanResult, _finish, register_planner
from ..core.tail import tail_flush, tail_record, tail_stats, tail_terminal
from .. import obs as _obs
from ..obs import registry as _obs_registry
from .pack import FleetPack

__all__ = ["FleetPlanner"]

_UNSET = object()


@partial(jax.jit, static_argnames=("k", "kb", "rb", "m", "backend", "cached",
                                   "bounds", "telemetry"),
         donate_argnums=(0,))
def _plan_fleet_chunk(dyn, const, slack, headroom, min_dvar, n_real, k_eff,
                      active0, *, k, kb, rb, m, backend, cached, bounds,
                      telemetry=False):
    """The fleet chunk step: ``_plan_chunk_impl`` vmapped over a leading
    cluster axis.  Every argument is stacked (scalars become per-lane
    vectors); the static tile geometry is the bucket's.  One compiled
    program per (bucket shape, lane count).

    The stacked carry is donated (like the single-cluster
    ``_plan_chunk``): the bucket always rebinds ``bucket.dyn`` to the
    returned carry, so the previous round's buffers are update-in-place
    fodder rather than copies."""
    impl = partial(_plan_chunk_impl, k=k, kb=kb, rb=rb, m=m, backend=backend,
                   cached=cached, bounds=bounds, telemetry=telemetry)
    # the walk's per-step iteration counts are not fetched
    dyn, done, overflow, tel, moves, _steps = jax.vmap(impl)(
        dyn, const, slack, headroom, min_dvar, n_real, k_eff, active0)
    # per-lane row high-water mark, fused into the same program so the
    # overflow check costs no extra eager op per round
    return dyn, done, overflow, tel, moves, jnp.max(dyn[8], axis=1)


@register_planner("fleet", sim_config_attr="equilibrium",
                  description="vmapped multi-cluster engine: shape-bucketed "
                              "fleets planned by one dispatch per bucket, "
                              "with per-cluster move budgets, streaming "
                              "delta absorption and an optional latency SLO",
                  equivalence="equilibrium")
class FleetPlanner:
    """Plan N independent clusters with one vmapped engine.

    Each cluster is a named lane: :meth:`add_cluster` binds a
    :class:`BatchPlanner` (the per-cluster sync / absorb / reconcile
    machinery is reused verbatim — only the chunk dispatch is batched).
    :meth:`plan_fleet` runs one fleet tick over any subset of clusters
    with per-cluster move budgets; the protocol :meth:`plan` makes a
    fleet of one behave exactly like ``equilibrium_batch`` behind the
    registry (auto-binding the passed state to a lane), so the scenario
    engine can drive it unmodified.

    Fleet lanes force the engine options that are vmap-uniform on CPU:
    ``select_backend="ref"`` (pure jnp — the Pallas interpreter does not
    batch), ``source_block=1`` and ``legality_cache=False`` (the cache's
    payoff geometry is per-accelerator, and its buffers dominate the
    stacked carry).  ``source_bounds`` stays on: certificates are
    per-lane state and vmap cleanly.
    """

    name = "fleet"

    def __init__(self, cfg: EquilibriumConfig | None = None, chunk: int = 64,
                 row_block: int = 8, source_bounds: bool = True,
                 slo_seconds: float | None = None):
        self.cfg = cfg or EquilibriumConfig()
        self.chunk = chunk
        rb = max(1, row_block)
        if rb & (rb - 1):       # bucket widths are pow2 multiples of rb
            rb = 1 << (rb - 1).bit_length()
        self.rb = rb
        self.source_bounds = source_bounds
        self.slo_seconds = slo_seconds
        self._clusters: dict[object, BatchPlanner] = {}
        self._pack = FleetPack(rb)
        self._by_state: dict[int, object] = {}      # id(state) -> key
        # per-cluster pruned-source counts, valid while the lane saw no
        # dispatch, absorb or rebuild since the last device fetch
        self._pruned: dict[object, int] = {}
        # lanes whose stacked carry ran ahead of their planner's tuple:
        # crop is deferred until something actually needs bp._dyn (an
        # absorb/rebuild sync, a bucket move, or detach) — on the hot
        # delta-free path the device lane alone stays authoritative
        self._needs_crop: set = set()

    # -- fleet membership -----------------------------------------------------

    def add_cluster(self, key, state: ClusterState,
                    cfg: EquilibriumConfig | None = None,
                    row_capacity: int | None = None) -> BatchPlanner:
        """Bind one cluster as fleet lane ``key`` (stable across ticks);
        returns its per-cluster engine handle.  ``row_capacity`` pins
        the carry's initial row axis — giving heterogeneous clusters a
        common capacity lands them in one bucket (one compiled program,
        no mid-run re-bucketing) instead of one per natural pow2.

        While the cluster is in the fleet, plan through the fleet
        (:meth:`plan` / :meth:`plan_fleet`), not the returned handle:
        between fleet ticks the stacked lane, not the handle's own
        carry, is the authoritative device state (:meth:`remove_cluster`
        hands the carry back)."""
        if key in self._clusters:
            raise ValueError(f"cluster {key!r} already in the fleet")
        bp = BatchPlanner(state, cfg or self.cfg, chunk=self.chunk,
                          source_block=1, row_block=self.rb,
                          select_backend="ref", legality_cache=False,
                          source_bounds=self.source_bounds,
                          row_capacity=row_capacity)
        self._clusters[key] = bp
        self._by_state[id(state)] = key
        return bp

    def remove_cluster(self, key) -> None:
        bp = self._clusters.pop(key)
        self._by_state.pop(id(bp.state), None)
        if key in self._needs_crop:
            # hand the engine back with its carry current: the caller
            # keeps the BatchPlanner handle add_cluster returned
            self._pack.crop_lane(key, bp)
            self._needs_crop.discard(key)
        self._pruned.pop(key, None)
        self._pack.remove(key)

    @property
    def clusters(self) -> tuple:
        return tuple(self._clusters)

    # -- Planner protocol (the fleet of one) ----------------------------------

    def plan(self, state: ClusterState, *, budget: int | None = None,
             record_trajectory: bool = False,
             record_free_space: bool = True) -> PlanResult:
        key = self._by_state.get(id(state))
        if key is None:
            n = len(self._clusters)
            key = f"cluster{n}"
            while key in self._clusters:
                n += 1
                key = f"cluster{n}"
            self.add_cluster(key, state)
        results = self.plan_fleet({key: budget},
                                  record_trajectory=record_trajectory,
                                  record_free_space=record_free_space)
        return results[key]

    def observe(self, delta: ClusterDelta) -> bool:
        """Single-lane protocol hook.  Deltas from bound states arrive
        through their subscriptions automatically; manual routing in a
        multi-cluster fleet must name the lane (:meth:`observe_cluster`
        / :meth:`FleetService.ingest`) — broadcasting a delta across
        unrelated epoch streams would poison them."""
        if len(self._clusters) == 1:
            (bp,) = self._clusters.values()
            return bp.observe(delta)
        return True

    def observe_cluster(self, key, delta: ClusterDelta) -> bool:
        """Route one streamed delta to lane ``key``; True iff that
        cluster's warm carry can absorb it (False = it will rebuild at
        the next tick)."""
        return self._clusters[key].observe(delta)

    def reset(self) -> None:
        for bp in self._clusters.values():
            bp.reset()
        self._pack = FleetPack(self.rb)
        self._pruned.clear()
        self._needs_crop.clear()

    # -- the fleet tick -------------------------------------------------------

    def plan_fleet(self, budgets: dict | None = None, *,
                   slo_seconds=_UNSET, record_trajectory: bool = False,
                   record_free_space: bool = True) -> dict:
        """One fleet tick: sync every requested cluster, pack, plan all
        of them through vmapped bucket dispatches, reconcile each, and
        return ``{key: PlanResult}``.

        ``budgets`` maps lane key -> move budget (None = that cluster's
        ``cfg.max_moves``); ``budgets=None`` plans every cluster at its
        default.  Clusters not named do not plan this tick and their
        carries are untouched.  ``slo_seconds`` overrides the instance
        default for this tick (None = unbounded).
        """
        slo = self.slo_seconds if slo_seconds is _UNSET else slo_seconds
        if budgets is None:
            budgets = {k: None for k in self._clusters}
        unknown = [k for k in budgets if k not in self._clusters]
        if unknown:
            raise KeyError(f"unknown fleet clusters: {unknown!r}")
        keys = [k for k in self._clusters if k in budgets]
        reg = _obs_registry()
        results: dict = {}
        t_tick = time.perf_counter()
        deadline = None if slo is None else t_tick + float(slo)
        with jax.enable_x64(True), \
                _obs.span("fleet.tick", cat="fleet", counters=True,
                          clusters=len(keys)) as sp:
            # --- sync phase: per-cluster delta absorption / (re)build,
            # sequential host work with per-cluster counter attribution
            sync_stats: dict = {}
            sync_dt: dict = {}
            sync_at: dict = {}
            for key in keys:
                bp = self._clusters[key]
                if key in self._needs_crop and (bp.stale or bp._pending
                                                or bp._invalid):
                    # sync below will absorb into / rebuild from the
                    # planner tuple: refresh it from the lane first
                    self._pack.crop_lane(key, bp)
                    self._needs_crop.discard(key)
                snap = reg.snapshot()
                t0 = time.perf_counter()
                bp.sync()
                sync_dt[key] = time.perf_counter() - t0
                sync_at[key] = time.perf_counter()
                d = reg.deltas_since(snap)
                sync_stats[key] = (int(d.get("batch.rebuilds", 0)),
                                   int(d.get("batch.host_syncs", 0)))
                if d.get("absorb.runs", 0) or d.get("batch.rebuilds", 0):
                    # the carry changed without a dispatch: the cached
                    # pruned-source count no longer describes it
                    self._pruned.pop(key, None)
                bp._terminal_seconds = 0.0

            # --- budgets, stash replay, packing
            budget_of: dict = {}
            raw: dict = {}
            lane_secs = {k: 0.0 for k in keys}
            packed: set = set()
            for key in keys:
                bp = self._clusters[key]
                b = budgets.get(key)
                budget_of[key] = bp.cfg.max_moves if b is None else b
                raw[key] = []
                if bp._dyn is None or budget_of[key] <= 0:
                    continue
                take = min(len(bp._stash), budget_of[key])
                if take:
                    raw[key].extend(bp._stash[:take])
                    del bp._stash[:take]
                    reg.inc("batch.stash_replayed", take)
                if (key in self._needs_crop
                        and self._pack.tokens.get(key) is None):
                    # the lane moved buckets out-of-band: ensure would
                    # re-pack from the stale tuple — refresh it first
                    self._pack.crop_lane(key, bp)
                    self._needs_crop.discard(key)
                self._pack.ensure(key, bp)
                packed.add(key)

            # --- bucket-round dispatch loop
            live = {key for key in packed
                    if len(raw[key]) < budget_of[key]
                    and not self._clusters[key]._done}
            telemetry = _obs.device_counters()
            expired = False
            first_dispatch = True
            rounds = 0
            chunks = 0
            participations = {k: 0 for k in keys}
            groups = None       # rebuilt when lanes move buckets
            while live and not expired:
                rounds += 1
                if groups is None:
                    groups = [(shape, bucket,
                               [(key, i)
                                for key, i in bucket.lanes().items()
                                if key in packed
                                and self._pack.where.get(key) == (shape, i)])
                              for shape, bucket in self._pack.buckets.items()]
                # phase 1 — co-scheduled dispatch: every bucket with live
                # lanes goes out asynchronously before the round's single
                # host sync, so the device works all shapes concurrently
                # instead of idling while the host blocks per bucket.
                # Buckets are disjoint key sets, so no result of one can
                # change another's dispatch; the per-lane streams stay
                # bit-identical to the sequential rounds.
                pending = []
                for shape, bucket, members in groups:
                    active = [(key, i) for key, i in members if key in live]
                    if not active:
                        continue
                    if (not first_dispatch and deadline is not None
                            and time.perf_counter() > deadline):
                        # SLO cut before committing more work; whatever
                        # is already in flight below still gets fetched
                        # (it is applied in the carries either way)
                        expired = True
                        break
                    first_dispatch = False
                    mask = np.zeros(len(bucket), bool)
                    for _key, i in active:
                        mask[i] = True
                    s = bucket.dispatch_scalars()
                    t0 = time.perf_counter()
                    jit0 = _plan_fleet_chunk._cache_size()
                    bucket.dyn, done, overflow, tel, moves, nmax = \
                        _plan_fleet_chunk(
                            bucket.dyn, bucket.const,
                            s[0], s[1], s[2], s[3], s[4],
                            bucket.dispatch_mask(mask),
                            k=shape.k, kb=1, rb=self.rb, m=self.chunk,
                            backend="ref", cached=False,
                            bounds=self.source_bounds, telemetry=telemetry)
                    recompiles = _plan_fleet_chunk._cache_size() - jit0
                    if recompiles:
                        reg.inc("fleet.jit_recompiles", recompiles)
                    pending.append((shape, bucket, active,
                                    (moves, done, overflow, tel, nmax), t0))
                if not pending:
                    continue
                reg.inc("fleet.rounds")
                if len(pending) > 1:
                    reg.inc("fleet.rounds.overlapped")
                # phase 2 — one blocking transfer for the whole round
                # (CI-gated: fleet.round_syncs stays equal to fleet.rounds
                # no matter how many bucket shapes are in play)
                fetched = _fetch([p[3] for p in pending], "chunk")
                reg.inc("fleet.round_syncs")
                for (shape, bucket, active, _handles, t0), \
                        (moves_np, done_np, ovf_np, tel_np, nmax_np) \
                        in zip(pending, fetched):
                    dt = time.perf_counter() - t0
                    chunks += 1
                    reg.inc("fleet.chunks")
                    lane_dt = dt / len(active)
                    if telemetry:
                        rows = [i for _k, i in active]
                        reg.inc("batch.tiles_walked",
                                int(tel_np[rows, 0].sum()))
                        reg.inc("batch.cand_tiles",
                                int(tel_np[rows, 1].sum()))
                    for key, i in active:
                        bp = self._clusters[key]
                        participations[key] += 1
                        lane_secs[key] += lane_dt
                        em = moves_np[i]
                        em = em[em[:, 0] >= 0]
                        per_s = lane_dt / max(len(em), 1)
                        raw[key].extend((*m, per_s)
                                        for m in map(tuple, em.tolist()))
                        lane_done = bool(done_np[i])
                        lane_ovf = bool(ovf_np[i])
                        if len(em) == 0 and lane_done and not lane_ovf:
                            bp._terminal_seconds += lane_dt
                        if len(raw[key]) >= budget_of[key]:
                            over = len(raw[key]) - budget_of[key]
                            if over:
                                # overshoot is already applied in the
                                # carry: hold it for the next tick, same
                                # as the serial engine
                                reg.inc("batch.stash_moves", over)
                                _obs.point("batch.stash", cat="batch",
                                           moves=over)
                                bp._stash = (raw[key][budget_of[key]:]
                                             + bp._stash)
                                del raw[key][budget_of[key]:]
                            if lane_done:
                                bp._done = True
                            live.discard(key)
                        elif lane_done:
                            bp._done = True
                            live.discard(key)
                        if key in live and (
                                lane_ovf or
                                int(nmax_np[i]) + self.chunk > shape.r_cap):
                            # only this lane's slice moves to the next
                            # row-capacity bucket; every other cluster's
                            # stacked carry stays bitwise untouched
                            reg.inc("fleet.rebuckets")
                            _obs.point("fleet.rebucket", cat="fleet",
                                       cluster=str(key),
                                       r_cap=shape.r_cap)
                            self._pack.rebucket(key)
                            groups = None

            slo_cut = set(live) if expired else set()

            # --- pruned-source counts: one batched fetch per bucket
            # (the fleet's replacement for the per-planner sync in
            # BatchPlanner._flush_stats)
            pruned_of = {key: 0 for key in keys}
            if self.source_bounds and packed:
                for shape, bucket in list(self._pack.buckets.items()):
                    lanes = [(key, i) for key, i in bucket.lanes().items()
                             if key in packed
                             and self._pack.where.get(key) == (shape, i)]
                    if not lanes:
                        continue
                    # a lane's count only moves on dispatch / absorb /
                    # rebuild; otherwise the cached fetch stands and the
                    # tick costs no device sync here at all
                    if any(participations[key] > 0 or key not in self._pruned
                           for key, i in lanes):
                        sums = _fetch(jnp.sum(bucket.dyn[13], axis=1),
                                      "pruned")
                        for key, i in lanes:
                            self._pruned[key] = int(sums[i])
                    for key, i in lanes:
                        pruned_of[key] = self._pruned[key]

            # --- planned-on lanes ran ahead of their planner tuples.
            # Don't crop them back eagerly: on the hot delta-free path
            # nothing reads bp._dyn before the next tick re-uses the
            # stacked lane, so the write-back (one fused dispatch per
            # cluster) is deferred until a sync / bucket move / detach
            # actually needs the tuple (see _needs_crop)
            for key in keys:
                if key in packed and participations[key] > 0:
                    self._needs_crop.add(key)

            # --- per-cluster reconcile + schema'd stats
            total_moves = 0
            for key in keys:
                bp = self._clusters[key]
                with _obs.span("planner.plan", cat="planner", counters=True,
                               planner=self.name, cluster=str(key)) as psp:
                    t0 = time.perf_counter()
                    where = self._pack.where.get(key)
                    movements, records = bp._reconcile(
                        raw[key], record_trajectory, record_free_space,
                        dst_width=where[0].n_dev if where else None)
                    stats: dict = {}
                    acc = tail_stats(stats)
                    for _row, _src, _dst, tried, skipped, secs in raw[key]:
                        tail_record(acc, tried, secs, 0.0)
                        acc["bound_hits"] += int(skipped)
                    tail_terminal(acc, bp._terminal_seconds)
                    if self.source_bounds:
                        acc["pruned"] = pruned_of[key]
                    tail_flush(acc)
                    rebuilds, sync_syncs = sync_stats[key]
                    now = time.perf_counter()
                    stats.update({
                        "planning_seconds": (sync_dt[key] + lane_secs[key]
                                             + (now - t0)),
                        "budget": budgets.get(key),
                        "engine": "fleet",
                        "warm": True,
                        "rebuilds": rebuilds,
                        "absorbed_deltas": bp._absorbed_deltas,
                        # sync-phase transfers plus one per bucket-round
                        # participated; the vmapped dispatch itself is
                        # shared, so per-cluster recompiles are 0 by
                        # construction (tick-level recompiles are the
                        # fleet.jit_recompiles counter)
                        "host_syncs": sync_syncs + participations[key],
                        "jit_recompiles": 0,
                        "stash_moves": len(bp._stash),
                        "legality_cache": False,
                        "source_bounds": self.source_bounds,
                        "fleet_clusters": len(keys),
                        "slo_deadline_seconds": (None if slo is None
                                                 else float(slo)),
                        "slo_expired": key in slo_cut,
                        "plan_freshness_seconds": now - sync_at[key],
                        "converged": bool(bp._done or bp._dyn is None),
                        "variance_after":
                            float(bp.state.utilization_variance()),
                    })
                    result = PlanResult(movements, records, self.name,
                                        stats=stats)
                    results[key] = _finish(result, psp)
                total_moves += len(movements)
                _obs.point("fleet.plan", cat="fleet", cluster=str(key),
                           moves=len(movements),
                           wall=results[key].stats["planning_seconds"],
                           freshness=results[key].stats[
                               "plan_freshness_seconds"],
                           slo_expired=key in slo_cut,
                           converged=results[key].stats["converged"])

            reg.inc("fleet.ticks")
            reg.inc("fleet.planned_moves", total_moves)
            if slo is not None:
                reg.inc("fleet.slo_misses" if expired else "fleet.slo_hits")
            reg.set_gauge("fleet.clusters", len(self._clusters))
            sp.set(rounds=rounds, chunks=chunks, moves=total_moves,
                   slo_expired=bool(expired),
                   wall=time.perf_counter() - t_tick)
        return results
