"""The benchmark's own copy of the paper-cluster generator.

A frozen copy of the repository's paper-cluster builders and their
vectorised CRUSH-like placement, kept here so that a change to the
program cannot move the yardstick: the same configuration file and seed
give the same devices, pools, acting sets and shard sizes for as long as
the benchmark stands (the placement fingerprints of clusters B and E are
pinned in ``tests/test_chipbench_clustergen.py``).

The output is plain data (:class:`Deployment`).  The system under test
gets it through ``ClusterState``'s public constructor
(``program.to_cluster_state``); the reference planner reads it directly.

``--seed`` relabels the OSD ids with a seeded permutation: every seed
plans the same devices, sizes and acting sets, in another id order.
"""

from __future__ import annotations

import dataclasses
import hashlib
from dataclasses import dataclass

import numpy as np

TiB = 1024.0 ** 4
PiB = 1024.0 * TiB
UNITS = {"TiB": TiB, "PiB": PiB}


@dataclass(frozen=True)
class Pool:
    id: int
    name: str
    pg_count: int
    steps: tuple            # ((device_class | None, count, failure_domain),)
    ec_k: int
    stored_bytes: float
    is_user_data: bool

    @property
    def size(self) -> int:
        return sum(c for _, c, _ in self.steps)

    @property
    def shard_growth_factor(self) -> float:
        per_pg = 1.0 / self.pg_count
        return per_pg if self.ec_k == 0 else per_pg / self.ec_k

    @property
    def nominal_shard_size(self) -> float:
        return self.stored_bytes * self.shard_growth_factor


@dataclass
class Deployment:
    """One generated cluster.  Device index ``i`` has OSD id ``i``.

    ``acting[p]`` lists the OSDs of PG ``pgs[p]`` slot by slot, padded
    with -1 past the pool's rule size; ``size[p]`` is its shard size.
    PGs are in pool order, then PG index order."""

    cap: np.ndarray                 # (n_dev,) float64 bytes
    dev_class: list
    host: list
    rack: list
    pools: list                     # [Pool], ascending id
    pgs: list                       # [(pool_id, pg_index)]
    acting: np.ndarray              # (n_pg, max rule size) int64
    size: np.ndarray                # (n_pg,) float64

    @property
    def n_dev(self) -> int:
        return self.cap.shape[0]


def _amount(spec) -> float:
    return spec["value"] * UNITS[spec["unit"]]


# -- devices (frozen copy of the generator's _make_devices) -----------------


def _make_devices(groups, osds_per_host, hosts_per_rack, het, seed):
    rng = np.random.default_rng((seed, 0xD0D0))
    cap, cls, host, rack = [], [], [], []
    for g in groups:
        count, total, dclass = g["count"], _amount(g["total"]), g["class"]
        per_host = min(osds_per_host, max(1, count // 6))
        mean = total / count
        sizes = np.where(rng.random(count) < 0.5, mean * (1 - het),
                         mean * (1 + het))
        sizes *= total / sizes.sum()
        for j in range(count):
            h = j // per_host
            cap.append(float(sizes[j]))
            cls.append(dclass)
            host.append(f"{dclass}-host{h:04d}")
            rack.append(f"{dclass}-rack{h // hosts_per_rack:03d}")
    return np.array(cap, dtype=np.float64), cls, host, rack


# -- pools (frozen copy of _pool_set) ----------------------------------------


def _rule(cfg, name):
    return tuple((s["class"], s["count"], s["failure_domain"])
                 for s in cfg["rules"][name])


def _pool_set(cfg, seed):
    rng = np.random.default_rng((seed, 0xB00B5))
    pools = []
    pid = 0
    used_pgs = 0
    for big in cfg["big_pools"]:
        pools.append(Pool(pid, f"user{pid}", big["pg_count"],
                          _rule(cfg, big["rule"]), big["ec_k"],
                          _amount(big["stored"]), True))
        used_pgs += big["pg_count"]
        pid += 1
    remaining = cfg["total_pgs"] - used_pgs
    n_small, n_meta = cfg["small_user_pools"], cfg["meta_pools"]
    n_rest = n_small + n_meta
    if n_rest > 0:
        weights = rng.pareto(1.5, size=n_rest) + 1.0
        weights /= weights.sum()
        counts = np.maximum(1, np.round(weights * remaining)).astype(int)
        while counts.sum() > remaining:
            counts[int(np.argmax(counts))] -= 1
        while counts.sum() < remaining:
            counts[int(np.argmin(counts))] += 1
        small_bytes = _amount(cfg["small_stored"])
        meta_bytes = _amount(cfg["meta_stored"])
        for i in range(n_small):
            stored = small_bytes * float(rng.uniform(0.3, 1.7))
            pools.append(Pool(pid, f"user{pid}", int(counts[i]),
                              _rule(cfg, cfg["small_rule"]), 0, stored,
                              True))
            pid += 1
        for i in range(n_small, n_rest):
            stored = meta_bytes * float(rng.uniform(0.3, 1.7))
            pools.append(Pool(pid, f"meta{pid}", int(counts[i]),
                              _rule(cfg, cfg["meta_rule"]), 0, stored,
                              False))
            pid += 1
    return pools


# -- placement (frozen copy of crush.build_cluster / _place) ------------------


def _domain(level, i, host, rack):
    if level == "osd":
        return f"osd.{i}"
    if level == "host":
        return host[i]
    if level == "rack":
        return rack[i]
    if level == "datacenter":
        return "dc0"
    raise ValueError(f"unknown failure-domain level: {level!r}")


class _DeviceTable:
    def __init__(self, cap, cls, host, rack):
        self.cap, self.cls, self.host, self.rack = cap, cls, host, rack
        self._views: dict = {}

    def view(self, device_class, level):
        key = (device_class, level)
        if key not in self._views:
            idx = [i for i in range(len(self.cap))
                   if device_class is None or self.cls[i] == device_class]
            tokens: dict = {}
            self._views[key] = (
                np.array(idx, dtype=np.int64),
                np.array([self.cap[i] for i in idx], dtype=np.float64),
                np.array([tokens.setdefault(
                    _domain(level, i, self.host, self.rack), len(tokens))
                    for i in idx], dtype=np.int64),
                len(tokens))
        return self._views[key]


def _select_step(rng, table, step, taken):
    device_class, count, level = step
    ids, caps, dom, n_dom = table.view(device_class, level)
    free = ~np.isin(ids, list(taken))
    dom_taken = np.zeros(n_dom, dtype=bool)
    chosen = []
    for _ in range(count):
        cands = np.flatnonzero(free & ~dom_taken[dom])
        if not cands.size:
            raise RuntimeError(f"cannot satisfy rule step {step}")
        weights = caps[cands]
        weights /= weights.sum()
        j = cands[int(rng.choice(len(cands), p=weights))]
        chosen.append(int(ids[j]))
        taken.add(int(ids[j]))
        free[j] = False
        dom_taken[dom[j]] = True
    return chosen


def _place_all(table, pools, seed, jitter):
    rng = np.random.default_rng((seed, 0xC0FFEE))
    width = max(p.size for p in pools)
    pgs, rows, sizes = [], [], []
    for pool in pools:
        nominal = pool.nominal_shard_size
        for pg in range(pool.pg_count):
            prng = np.random.default_rng((seed, pool.id, pg))
            taken: set = set()
            acting = []
            for step in pool.steps:
                acting += _select_step(prng, table, step, taken)
            j = float(rng.normal(1.0, jitter)) if nominal > 0 else 0.0
            pgs.append((pool.id, pg))
            rows.append(acting + [-1] * (width - len(acting)))
            sizes.append(max(nominal * max(j, 0.1), 0.0))
    return pgs, np.array(rows, dtype=np.int64), np.array(sizes, np.float64)


def used_bytes(acting: np.ndarray, size: np.ndarray, n_dev: int
               ) -> np.ndarray:
    """Per-device bytes, accumulated PG by PG and slot by slot: the order
    in which the program's ``ClusterState`` sums them, so both hold the
    same float64 values bit for bit."""
    used = np.zeros(n_dev, dtype=size.dtype)
    mask = acting >= 0
    np.add.at(used, acting[mask],
              np.broadcast_to(size[:, None], acting.shape)[mask])
    return used


def paper_cluster(cfg: dict) -> Deployment:
    """The configuration's cluster at its own generator seed."""
    seed = cfg["generator_seed"]
    cap, cls, host, rack = _make_devices(
        cfg["device_groups"], cfg["osds_per_host"], cfg["hosts_per_rack"],
        cfg["capacity_tiers"], seed)
    pools = _pool_set(cfg, seed)
    table = _DeviceTable(cap, cls, host, rack)
    jitter = cfg["shard_size_jitter"]
    pgs, acting, size = _place_all(table, pools, seed, jitter)
    util = used_bytes(acting, size, len(cap)) / cap
    limit = cfg["max_initial_util"]
    if float(util.max()) > limit:
        # a real cluster cannot be filled past its full ratio: scale the
        # payload down and place again, as the generator does
        scale = limit / float(util.max())
        pools = [dataclasses.replace(p, stored_bytes=p.stored_bytes * scale)
                 for p in pools]
        pgs, acting, size = _place_all(table, pools, seed, jitter)
    return Deployment(cap, cls, host, rack, pools, pgs, acting, size)


def relabel(dep: Deployment, seed: int) -> Deployment:
    """The same cluster with OSD ids permuted by ``seed``."""
    n = dep.n_dev
    new_id = np.random.default_rng((seed, 0x5EED)).permutation(n)
    old_of = np.argsort(new_id)
    acting = np.where(dep.acting >= 0, new_id[np.maximum(dep.acting, 0)], -1)
    return Deployment(dep.cap[old_of].copy(),
                      [dep.dev_class[i] for i in old_of],
                      [dep.host[i] for i in old_of],
                      [dep.rack[i] for i in old_of],
                      list(dep.pools), list(dep.pgs), acting,
                      dep.size.copy())


def generate(cfg: dict, seed: int) -> Deployment:
    return relabel(paper_cluster(cfg), seed)


def fingerprint(dep: Deployment) -> str:
    """sha256 over every device, pool, acting set and shard size."""
    h = hashlib.sha256()
    for i in range(dep.n_dev):
        h.update(f"d{i},{float(dep.cap[i])!r},{dep.dev_class[i]},"
                 f"{dep.host[i]},{dep.rack[i]};".encode())
    for p in dep.pools:
        h.update(f"p{p.id},{p.pg_count},{p.ec_k},{p.stored_bytes!r},"
                 f"{p.steps};".encode())
    for (pool, pg), row, s in zip(dep.pgs, dep.acting.tolist(),
                                  dep.size.tolist()):
        h.update(f"g{pool},{pg},{[o for o in row if o >= 0]},{s!r};"
                 .encode())
    return h.hexdigest()
