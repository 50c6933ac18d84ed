"""CRUSH-like pseudo-random initial placement.

Ceph's CRUSH maps each PG to devices via straw2 draws down the bucket
hierarchy, weighted by subtree capacity, constrained by the rule's failure
domain and device class (§2.2).  The *exact* hash is irrelevant to balancing
semantics — what matters is that placement is (a) pseudo-random, (b)
capacity-weighted, and (c) constraint-respecting, producing the natural
imbalance the balancers then fix.  We implement a deterministic, seeded
weighted draw with those three properties (DESIGN.md §9.2).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

from .cluster import ClusterState, Device, PGId, Pool, RuleStep


class _DeviceTable:
    """Array views of a device list, per (device class, failure-domain
    level): device ids and capacities in list order, and each device's
    failure-domain token as a small int.  Built once per placement run,
    so a draw filters arrays instead of walking Device objects."""

    def __init__(self, devices: Sequence[Device]):
        self.devices = devices
        self._views: dict = {}

    def view(self, device_class: str | None, level: str):
        key = (device_class, level)
        if key not in self._views:
            devs = [d for d in self.devices
                    if device_class is None or d.device_class == device_class]
            tokens: dict[str, int] = {}
            self._views[key] = (
                np.array([d.id for d in devs], dtype=np.int64),
                np.array([d.capacity for d in devs], dtype=np.float64),
                np.array([tokens.setdefault(d.domain(level), len(tokens))
                          for d in devs], dtype=np.int64),
                len(tokens))
        return self._views[key]


def _select_step(rng: np.random.Generator, table: _DeviceTable,
                 step: RuleStep, taken_osds: set[int]) -> list[int]:
    """Pick ``step.count`` devices for one rule step: capacity-weighted
    draws without replacement, one per failure domain."""
    ids, caps, dom, n_dom = table.view(step.device_class,
                                       step.failure_domain)
    free = ~np.isin(ids, list(taken_osds))
    dom_taken = np.zeros(n_dom, dtype=bool)
    chosen: list[int] = []
    for _ in range(step.count):
        cands = np.flatnonzero(free & ~dom_taken[dom])
        if not cands.size:
            raise RuntimeError(
                f"cannot satisfy rule step {step}: no candidate device left "
                f"(domains taken: {int(dom_taken.sum())})")
        weights = caps[cands]
        weights /= weights.sum()
        j = cands[int(rng.choice(len(cands), p=weights))]
        chosen.append(int(ids[j]))
        taken_osds.add(int(ids[j]))
        free[j] = False
        dom_taken[dom[j]] = True
    return chosen


def place_pg(devices: Sequence[Device], pool: Pool, pg_index: int,
             seed: int = 0) -> list[int]:
    """Place all shards of one PG (deterministic in (seed, pool, pg))."""
    return _place(_DeviceTable(devices), pool, pg_index, seed)


def _place(table: _DeviceTable, pool: Pool, pg_index: int,
           seed: int) -> list[int]:
    rng = np.random.default_rng((seed, pool.id, pg_index))
    taken_osds: set[int] = set()
    acting: list[int] = []
    for step in pool.rule.steps:
        # Failure-domain separation applies within a rule step; Ceph hybrid
        # rules (e.g. 1×ssd + 2×hdd) allow ssd and hdd shards to share a
        # host, matching per-step `take` semantics.
        acting += _select_step(rng, table, step, taken_osds)
    return acting


def build_cluster(devices: Sequence[Device], pools: Sequence[Pool],
                  seed: int = 0, size_jitter: float = 0.05) -> ClusterState:
    """Create a cluster state with CRUSH-style initial placement.

    ``size_jitter`` models the paper's "PG shard sizes in a pool are almost
    equal": per-PG payloads get a small multiplicative jitter around the
    pool's nominal shard size.
    """
    acting: dict[PGId, list[int]] = {}
    shard_sizes: dict[PGId, float] = {}
    rng = np.random.default_rng((seed, 0xC0FFEE))
    table = _DeviceTable(devices)
    for pool in pools:
        nominal = pool.nominal_shard_size
        for pg in range(pool.pg_count):
            pgid: PGId = (pool.id, pg)
            acting[pgid] = _place(table, pool, pg, seed)
            jitter = float(rng.normal(1.0, size_jitter)) if nominal > 0 else 0.0
            shard_sizes[pgid] = max(nominal * max(jitter, 0.1), 0.0)
    state = ClusterState(devices, pools, acting, shard_sizes)
    state.check_valid()
    return state
