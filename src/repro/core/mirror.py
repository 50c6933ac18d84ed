"""Host mirror of a :class:`ClusterState` shared by the array engines.

Both the dense-NumPy engine (:class:`repro.core.equilibrium_jax.DenseState`)
and the device-resident batch engine
(:class:`repro.core.equilibrium_batch.BatchPlanner`) plan on a flat table
of shards: one row per ``(pg, slot)``, rows in sorted-PG, slot-major
order, each row carrying its PG, pool, size, device and the geometry of
the rule step that governs its slot.  :class:`ShardMirror` builds that
table, the per-device legality inputs and the per-pool accounting from
whole arrays (one Python pass over the acting sets and shard sizes, the
rest NumPy), so every engine starts from the same bits.  The batch
engine plans on exactly this; ``DenseState`` adds the membership,
domain-occupancy and per-device row sets only its own loop reads.
"""

from __future__ import annotations

from itertools import chain

import numpy as np

from . import legality
from .cluster import ClusterState
from .legality import LegalityState


class ShardMirror:
    """Flat array mirror of a :class:`ClusterState`.

    Shard rows are ``(pg, slot)`` pairs in sorted-PG, slot-major order:
    row ``r`` is ``(pgs[sh_pg[r]], sh_slot[r])``; ``sh_*`` are per-row
    int64 attributes (``sh_size`` float64).  The mirror holds no membership or occupancy structures,
    so the dense engine refuses to plan on it (``require_fresh``);
    :class:`~repro.core.equilibrium_jax.DenseState` is the complete one.
    """

    def __init__(self, state: ClusterState):
        self.state = state
        # freshness contract: the mirror is bit-faithful to the state at
        # exactly this mutation epoch (see require_fresh)
        self.epoch = state.mutation_epoch
        self.mirror_complete = False

        # per-device legality inputs (capacities, class ids, domain ids,
        # in-mask) come from the shared LegalityState; out devices are
        # never legal destinations (mirrors move_is_legal's out_osds
        # check, independent of the ideal-count criterion which stops
        # excluding at count_slack >= 1)
        self.legality = leg = LegalityState.from_cluster(state)
        self.n_dev = state.n_devices
        self.cap = leg.cap
        self.used = state.used()
        self.class_id = leg.class_id
        self.dev_class = leg.dev_class
        self.dev_in = leg.dev_in
        self.levels = leg.levels
        self.dev_domain_arr = leg.dev_domain_arr
        self.n_domains = leg.n_domains

        # pools
        pool_ids = sorted(state.pools)
        self.pool_index = {p: i for i, p in enumerate(pool_ids)}
        self.n_pools = len(pool_ids)
        self.ideal = np.stack([state.ideal_shard_count(state.pools[p])
                               for p in pool_ids])          # (n_pools, n_dev)
        self.pool_counts = np.stack([state.pool_counts[p] for p in pool_ids]
                                    ).astype(np.float64)     # (n_pools, n_dev)

        # flat shard table: per-PG columns, repeated over each PG's slots
        pgs = sorted(state.acting)
        self.pgs = pgs
        self.pg_index = {pg: i for i, pg in enumerate(pgs)}
        n_pg = len(pgs)
        pg_pool = np.searchsorted(
            np.array(pool_ids, np.int64),
            np.fromiter((pg[0] for pg in pgs), np.int64, n_pg))
        width = np.array([state.pools[p].size for p in pool_ids],
                         np.int64)[pg_pool]
        n_sh = int(width.sum())
        first = np.cumsum(width) - width                    # PG's first row
        self.sh_pg = np.repeat(np.arange(n_pg, dtype=np.int64), width)
        self.sh_pool = pg_pool[self.sh_pg]
        self.sh_slot = np.arange(n_sh, dtype=np.int64) - first[self.sh_pg]
        self.sh_size = self.row_sizes(state)
        # placement at build time: DenseState.apply_row keeps it current,
        # the batch engine keeps the live placement in its device carry
        self.sh_dev = self.row_devices(state)

        # per-row rule-step attributes, gathered from a (pool, slot)
        # table of the shared slot-geometry walk (legality.rule_slot_steps
        # — also the pool-create absorption's source, so absorbed rows
        # cannot drift from built ones); pools without PGs stay zero
        lvl_id = {l: i for i, l in enumerate(self.levels)}
        n_slots = max((p.size for p in state.pools.values()), default=0)
        geo = np.zeros((5, self.n_pools, n_slots), np.int64)
        for pi in np.unique(pg_pool).tolist():
            rule = state.pools[pool_ids[pi]].rule
            for slot, (si, base, scnt, domain, dev_class) in enumerate(
                    legality.rule_slot_steps(rule)):
                geo[:, pi, slot] = (
                    lvl_id[domain],
                    self.class_id[dev_class] if dev_class is not None
                    else -1, si, base, scnt)
        rows = (self.sh_pool, self.sh_slot)
        self.sh_level = geo[0][rows]
        self.sh_class = geo[1][rows]        # -1 = any
        self.sh_step = geo[2][rows]         # step idx in pool rule
        self.sh_sbase = geo[3][rows]        # step's first slot
        self.sh_scnt = geo[4][rows]         # step's slot count

        # incremental variance bookkeeping
        self.util = self.used / self.cap
        self.util_sum = float(self.util.sum())
        self.util_sumsq = float((self.util ** 2).sum())

    # -- re-reads of the bound state over this mirror's rows ----------------

    def row_sizes(self, state: ClusterState) -> np.ndarray:
        """Per-row shard sizes (float64) read from ``state``."""
        size = np.fromiter((state.shard_sizes[pg] for pg in self.pgs),
                           np.float64, len(self.pgs))
        return size[self.sh_pg]

    def row_devices(self, state: ClusterState) -> np.ndarray:
        """Per-row device index (int64) read from ``state.acting``: the
        rows' OSD ids in one pass, mapped through an id → index table."""
        osds = np.fromiter(
            chain.from_iterable(state.acting[pg] for pg in self.pgs),
            np.int64, len(self.sh_pg))
        ids = np.fromiter((d.id for d in state.devices), np.int64,
                          state.n_devices)
        idx_of = np.full(int(ids.max(initial=-1)) + 1, -1, np.int64)
        idx_of[ids] = np.arange(state.n_devices)
        return idx_of[osds]

    def acting_table(self, sh_dev: np.ndarray) -> np.ndarray:
        """The compact ``(n_pg, max pool size)`` int32 acting table of
        the rows' device indices ``sh_dev``, padded with -1."""
        n_slots = max(p.size for p in self.state.pools.values())
        acting = np.full((len(self.pgs), n_slots), -1, np.int32)
        acting[self.sh_pg, self.sh_slot] = sh_dev
        return acting

    # -- freshness ----------------------------------------------------------

    @property
    def stale(self) -> bool:
        """True when the bound state mutated past the mirrored epoch (or
        the mirror is not structurally complete)."""
        return (not self.mirror_complete
                or self.epoch != self.state.mutation_epoch)

    def mark_synced(self) -> None:
        """Re-stamp the mirror as faithful to the state's current epoch —
        legal only right after the mirror and the state absorbed the same
        mutation (``apply_row`` + ``ClusterState.apply`` of one move)."""
        self.epoch = self.state.mutation_epoch

    def require_fresh(self, state: ClusterState) -> None:
        """Refuse a dense-engine warm start on a stale, partial or
        foreign mirror.

        The dense engine's planning math reads the *full* mirror
        (membership, domain occupancy, per-device row sets); planning on
        arrays that missed a mutation silently emits illegal or
        non-faithful moves, so a mismatched epoch is an error, never a
        fallback.
        """
        if state is not self.state:
            raise ValueError("DenseState warm start bound to a different "
                             "ClusterState than it mirrors")
        if not self.mirror_complete:
            raise RuntimeError(
                "mirror is structurally incomplete (the batch engine's "
                "ShardMirror holds no membership, occupancy or per-device "
                "row sets); build a DenseState before warm starting the "
                "dense engine")
        if self.epoch != self.state.mutation_epoch:
            raise RuntimeError(
                f"DenseState mirror is stale (mirrored epoch {self.epoch}, "
                f"state epoch {self.state.mutation_epoch}); rebuild it or "
                "absorb the missed mutations before warm starting")
