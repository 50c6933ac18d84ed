"""Unified Planner protocol, registry, and typed cluster deltas.

This module is the single front door to every balancer in the repo.  The
reproduction historically grew four divergent entry points — the faithful
§3.1 loop, the dense-NumPy engine, the device-resident batched engine and
the Ceph ``mgr`` baseline — each with its own calling convention,
dispatched by a hardcoded string tuple in the scenario engine.  PR 3
replaces that with three small pieces:

* :class:`Planner` — the protocol every balancer implements::

      plan(state, *, budget=None, ...) -> PlanResult   # plan + apply
      observe(delta) -> bool                           # stay warm?
      reset()                                          # drop warm state

  ``observe`` is the incremental-replanning hook: a planner that keeps
  warm state across calls (``equilibrium_batch``) is told *what changed*
  through typed :class:`~repro.core.cluster.ClusterDelta` objects and
  answers whether it can absorb the change without a cold rebuild.
  Stateless planners trivially return True.  Deltas are emitted
  automatically by every :class:`~repro.core.cluster.ClusterState`
  mutator to subscribers (:meth:`ClusterState.subscribe`), so most
  callers never invoke ``observe`` by hand.

* :class:`PlanResult` — the unified return value (moves, per-move
  records, engine metadata, stats) replacing the ad-hoc
  ``(movements, records)`` / ``(movements, trajectory-dicts)`` tuples.

* :func:`register_planner` / :func:`create_planner` — the registry the
  scenario engine, benchmarks and examples resolve balancer names
  against.  Third-party planners register the same way (see the README
  "Planner API" section)::

      @register_planner("my-balancer", sim_config_attr="equilibrium")
      class MyPlanner: ...

``sim_config_attr`` names the :class:`repro.sim.engine.SimConfig` field
holding the planner's config, so the scenario engine can construct any
registered planner without per-name dispatch branches.

The old module-level entry points (``equilibrium.balance``,
``equilibrium_jax.balance_fast``, ``equilibrium_batch.balance_batch``,
``mgr_balancer.balance``) remain as deprecation shims with identical
outputs; nothing inside ``src/`` may call them (CI-enforced by
``tools/check_deprecated.py``).
"""

from __future__ import annotations

import dataclasses
import inspect
import time
from dataclasses import dataclass, field
from typing import Protocol, runtime_checkable

from .cluster import (ClusterDelta, ClusterState, DeviceAddDelta,
                      DeviceOutDelta, Movement, MovementDelta,
                      PoolCreateDelta, PoolGrowthDelta)
from .equilibrium import EquilibriumConfig, MoveRecord, _balance
from .mgr_balancer import MgrBalancerConfig, _balance as _mgr_balance
from .. import obs as _obs
from ..obs import finalize_stats

__all__ = [
    "ClusterDelta", "MovementDelta", "PoolGrowthDelta", "DeviceAddDelta",
    "DeviceOutDelta", "PoolCreateDelta", "PlanResult", "Planner",
    "PlannerSpec", "register_planner", "create_planner", "get_planner_spec",
    "available_planners", "planners_in_class",
]


# ---------------------------------------------------------------------------
# Unified plan result


@dataclass
class PlanResult:
    """What one :meth:`Planner.plan` call produced.

    ``moves`` were already applied to the planned-against state (planners
    plan against their own projected state, §3.1).  ``records`` is the
    per-move trajectory (empty unless ``record_trajectory=True``) in the
    shared :class:`~repro.core.equilibrium.MoveRecord` shape for every
    planner, including the mgr baseline.  ``stats`` carries engine
    metadata under the single documented schema
    :data:`repro.obs.schema.STATS_SCHEMA`: every registered planner
    emits exactly the same key set (engine-specific signals default to
    their neutral value), so consumers never branch per planner.
    """

    moves: list[Movement]
    records: list[MoveRecord]
    planner: str                     # registry name
    stats: dict = field(default_factory=dict)

    @property
    def variance_trajectory(self) -> list[float]:
        """Utilization variance after each move (needs trajectory)."""
        return [r.variance_after for r in self.records]

    def as_tuple(self) -> tuple[list[Movement], list[MoveRecord]]:
        """The legacy ``(movements, records)`` pair (migration helper)."""
        return self.moves, self.records

    def __len__(self) -> int:
        return len(self.moves)


# ---------------------------------------------------------------------------
# Protocol + registry


@runtime_checkable
class Planner(Protocol):
    """Anything that can plan shard movements against a ClusterState."""

    name: str

    def plan(self, state: ClusterState, *, budget: int | None = None,
             record_trajectory: bool = False,
             record_free_space: bool = True) -> PlanResult:
        """Plan up to ``budget`` moves (planner default when None),
        applying them to ``state``; return the unified result."""
        ...

    def observe(self, delta: ClusterDelta) -> bool:
        """Note one cluster mutation; True iff warm state survives it."""
        ...

    def reset(self) -> None:
        """Drop any warm state; the next plan() cold-starts."""
        ...


@dataclass(frozen=True)
class PlannerSpec:
    name: str
    factory: type | object           # callable returning a Planner
    sim_config_attr: str | None      # SimConfig field holding its config
    description: str = ""
    #: differential-testing equivalence class: planners sharing a tag
    #: must emit bitwise-identical move streams on the same input (the
    #: fuzz harness enumerates a class via :func:`planners_in_class`)
    equivalence: str | None = None


_REGISTRY: dict[str, PlannerSpec] = {}

#: planners whose defining module lives *above* repro.core (importing it
#: here eagerly would be a dependency cycle): resolved on first lookup by
#: importing the named module, whose import-time ``@register_planner``
#: fills the registry slot.
_LAZY_PLANNERS: dict[str, str] = {
    "fleet": "repro.fleet.planner",
    "equilibrium_batch_sharded": "repro.core.shard",
}


def register_planner(name: str, *, sim_config_attr: str | None = None,
                     description: str = "", replace: bool = False,
                     equivalence: str | None = None):
    """Class/factory decorator adding a planner to the registry."""
    def deco(factory):
        if name in _REGISTRY and not replace:
            raise ValueError(f"planner {name!r} already registered")
        _REGISTRY[name] = PlannerSpec(
            name, factory, sim_config_attr,
            description or inspect.getdoc(factory) or "",
            equivalence)
        return factory
    return deco


def get_planner_spec(name: str) -> PlannerSpec:
    if name not in _REGISTRY and name in _LAZY_PLANNERS:
        import importlib
        importlib.import_module(_LAZY_PLANNERS[name])
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown planner {name!r}: expected one of "
                         f"{available_planners()}") from None


def available_planners() -> tuple[str, ...]:
    """Registered planner names (lazy ones included), sorted."""
    return tuple(sorted(_REGISTRY.keys() | _LAZY_PLANNERS.keys()))


def planners_in_class(equivalence: str) -> tuple[str, ...]:
    """Registered planner names tagged with ``equivalence``, sorted.

    Lazy planner modules are imported first so their registrations are
    visible; an import failure propagates, so a broken engine can never
    drop out of a differential comparison unnoticed.
    """
    import importlib
    for name, module in _LAZY_PLANNERS.items():
        if name not in _REGISTRY:
            importlib.import_module(module)
    return tuple(sorted(n for n, spec in _REGISTRY.items()
                        if spec.equivalence == equivalence))


def create_planner(name: str, **kwargs) -> Planner:
    """Instantiate a registered planner.

    Keyword arguments not accepted by the planner's factory are dropped,
    so one call site can configure heterogeneous planners (the scenario
    engine passes ``cfg`` and ``chunk`` to every planner; ``none`` takes
    neither).  Factories accepting ``**kwargs`` receive everything.
    """
    spec = get_planner_spec(name)
    sig = inspect.signature(spec.factory)
    params = sig.parameters.values()
    if not any(p.kind is inspect.Parameter.VAR_KEYWORD for p in params):
        accepted = {p.name for p in params
                    if p.kind in (inspect.Parameter.POSITIONAL_OR_KEYWORD,
                                  inspect.Parameter.KEYWORD_ONLY)}
        kwargs = {k: v for k, v in kwargs.items() if k in accepted}
    return spec.factory(**kwargs)


# ---------------------------------------------------------------------------
# The built-in planners


def _with_budget(cfg, budget: int | None):
    return cfg if budget is None else dataclasses.replace(cfg,
                                                          max_moves=budget)


def _plan_span(name: str):
    """The per-plan telemetry span every built-in planner wraps its
    plan() in: ``counters=True`` attributes the registry increments made
    while planning (tail flushes, batch syncs, absorb runs) to this call
    in the trace — the rows ``tools/tracestat.py`` aggregates."""
    return _obs.span("planner.plan", cat="planner", counters=True,
                     planner=name)


def _finish(result: PlanResult, sp) -> PlanResult:
    """Normalize and publish one plan result: funnel ``stats`` through
    :func:`repro.obs.finalize_stats` (every registered planner emits the
    same documented key set — equivalence-tested in tests/test_obs.py),
    bump the planner throughput counters, annotate the span."""
    finalize_stats(result.stats)
    reg = _obs.registry()
    reg.inc("planner.plans", planner=result.planner)
    reg.inc("planner.moves", len(result.moves), planner=result.planner)
    sp.set(moves=len(result.moves),
           planning_seconds=result.stats["planning_seconds"],
           engine=result.stats["engine"])
    return result


class _StatelessPlanner:
    """Shared base for planners that rebuild from the state every call:
    there is no warm state to invalidate, so every delta is trivially
    absorbed and reset() is a no-op."""

    name = "stateless"

    def observe(self, delta: ClusterDelta) -> bool:
        return True

    def reset(self) -> None:
        pass


@register_planner("equilibrium_faithful", sim_config_attr="equilibrium",
                  description="paper-faithful §3.1 loop (semantic reference)",
                  equivalence="equilibrium")
class FaithfulEquilibriumPlanner(_StatelessPlanner):
    """The paper's §3.1 planning loop, unchanged — the reference every
    vectorized engine is property-tested against."""

    name = "equilibrium_faithful"

    def __init__(self, cfg: EquilibriumConfig | None = None,
                 source_bounds: bool = False):
        self.cfg = cfg or EquilibriumConfig()
        self.source_bounds = source_bounds

    def plan(self, state, *, budget=None, record_trajectory=False,
             record_free_space=True):
        with _plan_span(self.name) as sp:
            t0 = time.perf_counter()
            aux: dict = {}
            moves, records = _balance(state, _with_budget(self.cfg, budget),
                                      record_trajectory=record_trajectory,
                                      record_free_space=record_free_space,
                                      stats_out=aux,
                                      source_bounds=self.source_bounds)
            return _finish(PlanResult(moves, records, self.name, stats={
                "planning_seconds": time.perf_counter() - t0,
                "budget": budget, "engine": "faithful", **aux}), sp)


class _DensePlanner(_StatelessPlanner):
    """Shared plan() for the dense engines in equilibrium_jax."""

    engine = "numpy"

    def __init__(self, cfg: EquilibriumConfig | None = None,
                 source_bounds: bool = False):
        self.cfg = cfg or EquilibriumConfig()
        self.source_bounds = source_bounds

    def plan(self, state, *, budget=None, record_trajectory=False,
             record_free_space=True):
        from .equilibrium_jax import _balance_fast
        with _plan_span(self.name) as sp:
            t0 = time.perf_counter()
            aux: dict = {}
            moves, records = _balance_fast(
                state, _with_budget(self.cfg, budget),
                record_trajectory=record_trajectory,
                record_free_space=record_free_space, engine=self.engine,
                stats_out=aux, source_bounds=self.source_bounds)
            return _finish(PlanResult(moves, records, self.name, stats={
                "planning_seconds": time.perf_counter() - t0,
                "budget": budget, "engine": self.engine, **aux}), sp)


@register_planner("equilibrium", sim_config_attr="equilibrium",
                  description="dense-NumPy Equilibrium (small-cluster "
                              "default, no warm-up cost)",
                  equivalence="equilibrium")
class EquilibriumPlanner(_DensePlanner):
    name = "equilibrium"
    engine = "numpy"


@register_planner("equilibrium_jax_legacy", sim_config_attr="equilibrium",
                  description="first-generation per-source jitted path "
                              "(benchmark baseline)",
                  equivalence="equilibrium")
class LegacyJaxEquilibriumPlanner(_DensePlanner):
    name = "equilibrium_jax_legacy"
    engine = "jax-legacy"


@register_planner("equilibrium_batch", sim_config_attr="equilibrium",
                  description="device-resident chunked engine; warm-starts "
                              "across calls and absorbs every known delta "
                              "type (growth, add, out, movement, pool "
                              "create) without a rebuild",
                  equivalence="equilibrium")
class BatchEquilibriumPlanner:
    """Protocol adapter over :class:`~repro.core.equilibrium_batch
    .BatchPlanner`.

    The underlying engine binds one ClusterState and keeps its device
    carry warm across :meth:`plan` calls; passing a different state
    object rebinds (and cold-starts) transparently.  ``warm=False``
    forces a cold start on every call — the reference behaviour the
    delta-absorption tests compare against.
    """

    name = "equilibrium_batch"
    engine = "batch"                     # PlanResult.stats["engine"]

    def __init__(self, cfg: EquilibriumConfig | None = None, chunk: int = 64,
                 source_block: int = 1, row_block: int | None = None,
                 row_capacity: int | None = None,
                 select_backend: str = "auto", warm: bool = True,
                 legality_cache: bool = False, source_bounds: bool = True,
                 pipeline: bool = True):
        self.cfg = cfg or EquilibriumConfig()
        self.warm = warm
        self._engine_kwargs = dict(chunk=chunk, source_block=source_block,
                                   row_block=row_block,
                                   row_capacity=row_capacity,
                                   select_backend=select_backend,
                                   legality_cache=legality_cache,
                                   source_bounds=source_bounds,
                                   pipeline=pipeline)
        self._impl = None                # BatchPlanner, bound lazily

    def _bind(self, state: ClusterState):
        from .equilibrium_batch import BatchPlanner
        if self._impl is None or self._impl.state is not state:
            self._impl = BatchPlanner(state, self.cfg, **self._engine_kwargs)
        return self._impl

    def plan(self, state, *, budget=None, record_trajectory=False,
             record_free_space=True):
        impl = self._bind(state)
        if not self.warm:
            impl.reset()
        with _plan_span(self.name) as sp:
            t0 = time.perf_counter()
            # per-plan rebuilds / syncs / recompiles / stash / cache
            # counters arrive in aux as registry deltas computed by
            # BatchPlanner._registry_stats — the engine's own write path
            aux: dict = {}
            moves, records = impl.plan(max_moves=budget,
                                       record_trajectory=record_trajectory,
                                       record_free_space=record_free_space,
                                       stats_out=aux)
            return _finish(PlanResult(moves, records, self.name, stats={
                "planning_seconds": time.perf_counter() - t0,
                "budget": budget, "engine": self.engine, "warm": self.warm,
                **aux}), sp)

    def observe(self, delta: ClusterDelta) -> bool:
        if self._impl is None:
            return True                  # nothing warm yet
        return self._impl.observe(delta)

    def reset(self) -> None:
        if self._impl is not None:
            self._impl.reset()


@register_planner("mgr", sim_config_attr="mgr",
                  description="Ceph's built-in size-blind upmap balancer "
                              "(the paper's baseline)")
class MgrPlanner(_StatelessPlanner):
    """The §2.3.1 baseline behind the same protocol.  Its per-move
    trajectory dicts are normalized into :class:`MoveRecord`
    (``sources_tried`` is always 1: the mgr balancer never falls through
    to another source)."""

    name = "mgr"

    def __init__(self, cfg: MgrBalancerConfig | None = None):
        self.cfg = cfg or MgrBalancerConfig()

    def plan(self, state, *, budget=None, record_trajectory=False,
             record_free_space=True):
        with _plan_span(self.name) as sp:
            t0 = time.perf_counter()
            moves, trajectory = _mgr_balance(
                state, _with_budget(self.cfg, budget),
                record_trajectory=record_trajectory)
            dt = time.perf_counter() - t0
            per_move = dt / max(len(moves), 1)
            records = [MoveRecord(movement=mv, variance_after=t["variance"],
                                  free_space_after=t["free_space"],
                                  planning_seconds=per_move, sources_tried=1)
                       for mv, t in zip(moves, trajectory)]
            # mgr never falls through to another source: its whole wall
            # time is selection, and every move has rank 1
            hist = {"1": len(moves)} if moves else {}
            return _finish(PlanResult(moves, records, self.name, stats={
                "planning_seconds": dt, "budget": budget, "engine": "mgr",
                "sources_tried_hist": hist, "selection_seconds": dt,
                "moves_seconds": dt}), sp)


@register_planner("none", description="no-op baseline: never plans a move")
class NonePlanner(_StatelessPlanner):
    name = "none"

    def plan(self, state, *, budget=None, record_trajectory=False,
             record_free_space=True):
        with _plan_span(self.name) as sp:
            return _finish(PlanResult([], [], self.name, stats={
                "planning_seconds": 0.0, "budget": budget,
                "engine": "none"}), sp)
