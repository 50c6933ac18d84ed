"""The system under test, as the benchmark drives it.

The only module of the benchmark that imports the program.  It builds
the program's ``ClusterState`` from the generated deployment through
its public constructor, and plans with
``repro.core.planner.create_planner("equilibrium_batch")`` and its
``plan(state, budget=...)``: the entry point a balancer calls.  Moves
come back as ``(pg_row, slot, src, dst)``, the benchmark's own naming.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

_SRC = str(Path(__file__).resolve().parents[2] / "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

from repro import obs  # noqa: E402
from repro.core.cluster import (ClusterState, Device, Movement,  # noqa: E402
                                PlacementRule, Pool, RuleStep)
from repro.core.equilibrium import EquilibriumConfig  # noqa: E402
from repro.core.planner import create_planner  # noqa: E402

PLANNER = "equilibrium_batch"


def to_cluster_state(dep) -> ClusterState:
    devices = [Device(id=i, capacity=float(dep.cap[i]),
                      device_class=dep.dev_class[i], host=dep.host[i],
                      rack=dep.rack[i]) for i in range(dep.n_dev)]
    pools = [Pool(p.id, p.name, p.pg_count,
                  PlacementRule(tuple(RuleStep(c, n, fd)
                                      for c, n, fd in p.steps)),
                  ec_k=p.ec_k, stored_bytes=p.stored_bytes,
                  is_user_data=p.is_user_data) for p in dep.pools]
    acting = {pg: [o for o in row if o >= 0]
              for pg, row in zip(dep.pgs, dep.acting.tolist())}
    sizes = dict(zip(dep.pgs, dep.size.tolist()))
    return ClusterState(devices, pools, acting, sizes)


def counter(name: str) -> float:
    """A program counter's running total (``repro.obs`` registry)."""
    return obs.registry().get(name)


class Session:
    """One cluster and one planner bound to it."""

    def __init__(self, state: ClusterState, planner, pgs: list,
                 row_of: dict):
        self.state = state
        self.planner = planner
        self._pgs = pgs
        self._row_of = row_of

    def plan(self, budget):
        """Plan and time one call; the moves are on the host when it
        returns.  Returns (seconds, [(pg_row, slot, src, dst)])."""
        t0 = time.perf_counter()
        result = self.planner.plan(self.state, budget=budget)
        dt = time.perf_counter() - t0
        return dt, [(self._row_of[m.pg], m.slot, m.src_osd, m.dst_osd)
                    for m in result.moves]

    def grow(self, pool_id: int, user_bytes: float) -> None:
        self.state.grow_pool(pool_id, user_bytes)

    def set_out(self, osd: int, out: bool) -> None:
        self.state.mark_out(osd, out)

    def apply(self, row: int, slot: int, src: int, dst: int) -> None:
        pg = self._pgs[row]
        self.state.apply(Movement(pg, slot, src, dst,
                                  self.state.shard_sizes[pg]))


class Program:
    """Opens sessions on fresh copies of one generated cluster."""

    def __init__(self, dep, planner_cfg: dict):
        self.base = to_cluster_state(dep)
        self.cfg = EquilibriumConfig(**planner_cfg)
        self.pgs = list(dep.pgs)
        self.row_of = {pg: i for i, pg in enumerate(self.pgs)}

    def session(self) -> Session:
        return Session(self.base.copy(), create_planner(PLANNER, cfg=self.cfg),
                       self.pgs, self.row_of)
