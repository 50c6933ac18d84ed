"""Plain reference of the Equilibrium planner (paper §3.1), in NumPy.

Imports nothing of the program.  It plans on :class:`RefState`, a plain
array model of the cluster built from the benchmark's generated data,
and follows the paper's loop move by move:

1. sources: devices by relative utilization, fullest first (stable), the
   ``k`` fullest tried in turn;
2. shards on the source: largest first, ties by PG then slot;
3. destinations: emptiest first (stable), only devices before the source
   in that order; the first that keeps the PG legal under its rule
   (class, one shard per failure domain, not out, fits), moves both
   endpoints' pool shard counts toward their ideal and strictly lowers
   the utilization variance is taken;
4. the move is applied and the loop repeats until no source yields one.

Where the faithful loop scans one candidate at a time, this one tests a
block of shards against every destination with array operations: the
first passing (shard, destination) pair in the loop's order is the same.
The variance test uses running moments of utilization, started afresh
for every plan call, with the expression order of the paper's code and
squares as products.

``dtype`` sets the precision of every byte count, utilization and
moment.  The configuration states float64; float32 is the control that
the comparison has to reject.
"""

from __future__ import annotations

import numpy as np

from benchmarks.chip.clustergen import Deployment, used_bytes

LEVELS = ("osd", "host", "rack", "datacenter")


def _domains(dep: Deployment) -> np.ndarray:
    """(levels, n_dev) failure-domain token ids."""
    out = np.empty((len(LEVELS), dep.n_dev), dtype=np.int64)
    for li, level in enumerate(LEVELS):
        toks: dict = {}
        for i in range(dep.n_dev):
            tok = {"osd": i, "host": dep.host[i], "rack": dep.rack[i],
                   "datacenter": "dc0"}[level]
            out[li, i] = toks.setdefault(tok, len(toks))
    return out


class RefState:
    """The cluster as arrays: placement, shard sizes, bytes per device,
    per-pool shard counts, out flags.  Mutated only through
    :meth:`apply`, :meth:`grow` and :meth:`set_out`."""

    def __init__(self, dep: Deployment, dtype=np.float64):
        self.dtype = np.dtype(dtype)
        n = dep.n_dev
        self.n_dev = n
        self.cap = dep.cap.astype(self.dtype)
        classes = sorted(set(dep.dev_class))
        self.class_name = np.array(dep.dev_class)
        self.dev_class = np.array([classes.index(c) for c in dep.dev_class])
        self.dom = _domains(dep)
        self.dev_in = np.ones(n, dtype=bool)
        self.pools = {p.id: p for p in dep.pools}
        self.acting = dep.acting.copy()
        self.size = dep.size.astype(self.dtype)
        self.used = used_bytes(dep.acting, dep.size.astype(self.dtype), n) \
            .astype(self.dtype)
        self.pg_pool = np.array([pool for pool, _ in dep.pgs])
        self.rows_of_pool = {pid: np.flatnonzero(self.pg_pool == pid)
                             for pid in self.pools}
        w = self.acting.shape[1]
        n_pg = self.acting.shape[0]
        # per (pg, slot): rule step's class id (-1 any), level, first
        # slot and slot count
        self.slot_cls = np.full((n_pg, w), -1, np.int64)
        self.slot_lvl = np.zeros((n_pg, w), np.int64)
        self.slot_base = np.zeros((n_pg, w), np.int64)
        self.slot_cnt = np.zeros((n_pg, w), np.int64)
        for pid, rows in self.rows_of_pool.items():
            base = 0
            for cls, count, level in self.pools[pid].steps:
                sl = slice(base, base + count)
                self.slot_cls[rows, sl] = (-1 if cls is None
                                           else classes.index(cls)
                                           if cls in classes else -2)
                self.slot_lvl[rows, sl] = LEVELS.index(level)
                self.slot_base[rows, sl] = base
                self.slot_cnt[rows, sl] = count
                base += count
        self.pool_counts = np.zeros((max(self.pools) + 1, n), np.int64)
        mask = self.acting >= 0
        np.add.at(self.pool_counts,
                  (np.broadcast_to(self.pg_pool[:, None], mask.shape)[mask],
                   self.acting[mask]), 1)

    def copy(self) -> "RefState":
        new = object.__new__(RefState)
        new.__dict__.update(self.__dict__)
        for name in ("used", "acting", "size", "pool_counts", "dev_in"):
            setattr(new, name, getattr(self, name).copy())
        new.pools = dict(self.pools)
        return new

    # -- mutation ------------------------------------------------------------

    def apply(self, row: int, slot: int, src: int, dst: int) -> None:
        if self.acting[row, slot] != src:
            raise ValueError(f"stale move: slot {slot} of PG row {row} is "
                             f"on {self.acting[row, slot]}, not {src}")
        size = self.size[row]
        self.acting[row, slot] = dst
        self.used[src] -= size
        self.used[dst] += size
        pool = self.pg_pool[row]
        self.pool_counts[pool, src] -= 1
        self.pool_counts[pool, dst] += 1

    def grow(self, pool_id: int, user_bytes: float) -> None:
        """Every shard of the pool grows by its share of ``user_bytes``."""
        pool = self.pools[pool_id]
        delta = user_bytes * pool.shard_growth_factor
        if delta == 0.0:
            return
        rows = self.rows_of_pool[pool_id]
        self.size[rows] += self.dtype.type(delta)
        act = self.acting[rows]
        np.add.at(self.used, act[act >= 0], self.dtype.type(delta))

    def set_out(self, osd: int, out: bool) -> None:
        self.dev_in[osd] = not out

    # -- legality ------------------------------------------------------------

    def legal_mask(self, rows, slots, headroom: float = 0.0,
                   cols=None) -> np.ndarray:
        """(len(rows), len(cols)) bool: may shard (row, slot) move to
        each device of ``cols`` (all devices when None) under its rule?
        Class, not out, not already holding a shard of the PG, no peer
        of its rule step in the same failure domain, and room for the
        shard."""
        rows = np.asarray(rows)
        slots = np.asarray(slots)
        cols = np.arange(self.n_dev) if cols is None else np.asarray(cols)
        nb = rows.shape[0]
        cls = self.slot_cls[rows, slots][:, None]
        dev_class = self.dev_class[cols][None, :]
        ok = ((cls == -1) | (dev_class == cls)) & self.dev_in[cols][None, :]
        acting = self.acting[rows]                            # (nb, w)
        ok &= ~(cols[None, None, :] == acting[:, :, None]).any(axis=1)
        lvl = self.slot_lvl[rows, slots]
        dom = self.dom[lvl]                                   # (nb, n_dev)
        w = acting.shape[1]
        j = np.arange(w)[None, :]
        base = self.slot_base[rows, slots][:, None]
        cnt = self.slot_cnt[rows, slots][:, None]
        peer = (j >= base) & (j < base + cnt) & (j != slots[:, None]) \
            & (acting >= 0)
        peer_dom = np.where(peer, np.take_along_axis(
            dom, np.maximum(acting, 0), axis=1), -1)          # (nb, w)
        clash = (dom[:, cols][:, None, :] == peer_dom[:, :, None]).any(axis=1)
        ok &= ~clash
        limit = self.cap[cols] * self.dtype.type(1.0 - headroom)
        ok &= (self.used[cols][None, :] + self.size[rows][:, None]) \
            <= limit[None, :]
        return ok

    def is_legal(self, row: int, slot: int, dst: int,
                 headroom: float = 0.0) -> bool:
        return bool(self.legal_mask([row], [slot], headroom)[0, dst])

    def shards_on(self, osd: int) -> tuple[np.ndarray, np.ndarray]:
        """(rows, slots) held by ``osd``, in (PG, slot) order."""
        rows, slots = np.nonzero(self.acting == osd)
        return rows, slots

    def ideal(self) -> np.ndarray:
        """(pools, n_dev) ideal shard counts: each rule step's shards
        apportioned by capacity over its in devices of its class."""
        out = np.zeros(self.pool_counts.shape, dtype=self.dtype)
        for pid, pool in self.pools.items():
            ideal = np.zeros(self.n_dev, dtype=self.dtype)
            for cls, count, _level in pool.steps:
                mask = self.dev_in.copy()
                if cls is not None:
                    mask &= self.class_name == cls
                cap = np.where(mask, self.cap, self.dtype.type(0.0))
                total = cap.sum()
                if total <= 0:
                    continue
                ideal += pool.pg_count * count * cap / total
            out[pid] = ideal
        return out


# -- the planner ----------------------------------------------------------------


def _count_ok(counts, ideal, slack, delta):
    """§3.1 ideal-count criterion: ``delta`` = +1.0 for the destination
    (gains a shard), -1.0 for the source (loses one)."""
    return abs(counts + delta - ideal) <= abs(counts - ideal) + slack


def plan(st: RefState, budget: int | None = None, k: int = 25,
         count_slack: float = 0.0, headroom: float = 0.0,
         min_variance_delta: float = 0.0) -> list[tuple[int, int, int, int]]:
    """Plan up to ``budget`` moves (all, to convergence, when None),
    applying each to ``st``.  Returns [(pg_row, slot, src, dst)]."""
    t = st.dtype.type
    n = st.n_dev
    k = min(k, n)
    ideal = st.ideal()
    slack = t(count_slack)
    min_dvar = t(min_variance_delta)
    util = st.used / st.cap
    u_sum = util.sum()
    u_sq = (util * util).sum()
    moves: list[tuple[int, int, int, int]] = []
    while budget is None or len(moves) < budget:
        util = st.used / st.cap
        found = None
        for src in np.argsort(-util, kind="stable")[:k]:
            src = int(src)
            found = _best_from_source(st, src, util, ideal, slack, headroom,
                                      min_dvar, u_sum, u_sq)
            if found is not None:
                break
        if found is None:
            break
        row, slot, dst = found
        st.apply(row, slot, src, dst)
        for i in (src, dst):                 # source first
            u_new = st.used[i] / st.cap[i]
            u_sum += u_new - util[i]
            u_sq += u_new * u_new - util[i] * util[i]
        moves.append((row, slot, src, dst))
    return moves


def _best_from_source(st, src, util, ideal, slack, headroom, min_dvar,
                      u_sum, u_sq):
    t = st.dtype.type
    rows, slots = st.shards_on(src)
    sizes = st.size[rows]
    # the source criterion depends on the shard's pool alone: shards
    # failing it cannot move, whatever the destination
    pools = st.pg_pool[rows]
    keep = (sizes > 0.0) & _count_ok(
        st.pool_counts[pools, src].astype(st.dtype), ideal[pools, src],
        slack, t(-1.0))
    rows, slots, sizes = rows[keep], slots[keep], sizes[keep]
    if rows.size == 0:
        return None
    order = np.lexsort((slots, rows, -sizes))
    rows, slots, sizes = rows[order], slots[order], sizes[order]
    # destinations: only devices before the source in the emptiest-first
    # order, kept in that order so the first passing column is the one
    cols = np.argsort(util, kind="stable")
    before = (util[cols] < util[src]) \
        | ((util[cols] == util[src]) & (cols < src))
    cols = cols[before]
    if cols.size == 0:
        return None
    n = t(st.n_dev)
    cap_s, used_s, util_s = st.cap[src], st.used[src], util[src]
    used_d, cap_d, util_d = st.used[cols], st.cap[cols], util[cols]
    old_var = u_sq / n - (u_sum / n) * (u_sum / n)
    start, block = 0, 8
    while start < rows.size:
        r = rows[start:start + block]
        s = slots[start:start + block]
        z = sizes[start:start + block][:, None]
        pools = st.pg_pool[r]
        ok = st.legal_mask(r, s, headroom, cols)
        ok &= _count_ok(st.pool_counts[pools][:, cols].astype(st.dtype),
                        ideal[pools][:, cols], slack, t(1.0))
        v_s = (used_s - z) / cap_s
        v_d = (used_d + z) / cap_d
        dsum = (v_s - util_s) + (v_d - util_d)
        dsq = (v_s * v_s - util_s * util_s) + (v_d * v_d - util_d * util_d)
        new_var = (u_sq + dsq) / n - ((u_sum + dsum) / n) \
            * ((u_sum + dsum) / n)
        ok &= (new_var - old_var) < -min_dvar
        hit = np.flatnonzero(ok.any(axis=1))
        if hit.size:
            b = int(hit[0])
            return int(r[b]), int(s[b]), int(cols[np.argmax(ok[b])])
        start += block
        block *= 4
    return None
