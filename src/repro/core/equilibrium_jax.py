"""Vectorized Equilibrium planner (engine 2 of 3, DESIGN.md §2).

The repo ships a three-engine architecture, all emitting *bit-identical
move sequences* (property-tested in tests/test_equilibrium_jax.py and
tests/test_equilibrium_batch.py):

1. **faithful** (:mod:`repro.core.equilibrium`) — the paper's §3.1 loop,
   O(shards_on_source × devices) Python ``move_is_legal`` calls per move;
   the semantic reference.  The paper reports up to 1 s/move on cluster B
   (810 HDD + 185 SSD OSDs, 8731 PGs) and argues planning time is
   amortized by transfer time; the other engines remove the limitation.
2. **dense-numpy** (this module) — one balancing step is reformulated as
   dense masked array work over a ``(shards_on_source, devices)`` grid;
   no warm-up cost, the small-cluster default.
3. **device-resident batched** (:mod:`repro.core.equilibrium_batch`) —
   the ``use_jax=True`` production path: all planning state lives in
   device arrays, one jitted chunked scan evaluates all ``k`` fullest
   sources at once and applies moves functionally on-device, syncing
   with the host once per chunk instead of per source.

The per-step math shared by engines 2 and 3:

* legality  = class-match ∧ ¬PG-member ∧ failure-domain-free ∧ capacity-fit
* criteria  = ideal-count (source scalar, destination vector)
              ∧ exact O(1) variance delta < 0
* selection = largest shard with any valid destination; emptiest valid
              destination — identical tie-breaking to the faithful planner.

All incremental state (membership matrix, per-(pg,step) domain occupancy
gathered per device, per-pool shard counts) is maintained across moves, so
one move costs a few vector ops instead of ~10⁵ Python calls.

``_pick_jax`` / ``engine="jax-legacy"`` preserves the first-generation JAX
path — one jit call and one blocking host sync per source per move — as
the measured baseline for benchmarks/bench_planner.py's throughput
trajectory; new callers should use the batched engine.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from .cluster import ClusterState, Movement
from .equilibrium import EquilibriumConfig, MoveRecord
from . import legality
from .mirror import ShardMirror


# ---------------------------------------------------------------------------
# Dense registry of cluster state
#
# All id-numbering and criterion math comes from repro.core.legality — the
# single source both a full DenseState build and the batch engine's delta
# absorption (BatchPlanner._absorb) share, so a warm carry cannot diverge
# bitwise from a rebuilt one.


class DenseState(ShardMirror):
    """Flat array mirror of a :class:`ClusterState`, maintained incrementally.

    Shards are rows of the shared flat table (:class:`ShardMirror`); PG
    membership and per-(pg,step) domain occupancy are dense matrices so
    legality of *all* destinations for *all* source shards is a handful
    of vectorized ops.
    """

    def __init__(self, state: ClusterState):
        super().__init__(state)
        # freshness contract: ``apply_row`` callers re-stamp via
        # ``mark_synced`` after applying the movement to the state; any
        # other mutation makes ``require_fresh`` refuse warm reuse
        self.mirror_complete = True
        self.dev_domain = {lvl: self.dev_domain_arr[li]
                           for li, lvl in enumerate(self.levels)}
        self.shard_key = list(zip([self.pgs[i] for i in self.sh_pg.tolist()],
                                  self.sh_slot.tolist()))   # row -> (pg, slot)
        n_pg, n_dev = len(self.pgs), self.n_dev

        # membership (n_pg, n_dev) and per-(pg,step,level) domain occupancy
        self.member = np.zeros((n_pg, n_dev), dtype=bool)
        self.member[self.sh_pg, self.sh_dev] = True
        max_steps = max(len(state.pools[p].rule.steps) for p in state.pools)
        self.max_steps = max_steps
        self.occ = {lvl: np.zeros((n_pg, max_steps, self.n_domains[lvl]),
                                  dtype=np.int16) for lvl in self.levels}
        for li, lvl in enumerate(self.levels):
            at = self.sh_level == li
            np.add.at(self.occ[lvl],
                      (self.sh_pg[at], self.sh_step[at],
                       self.dev_domain[lvl][self.sh_dev[at]]), 1)

        # Per-device domain-occupancy view: occ_dev[pg, step, d] = shards of
        # (pg, step) already in the failure domain containing device d, at
        # the step's own level.  One gather per candidate block replaces the
        # per-row Python peer-occupancy rebuild; maintained incrementally in
        # apply_row.  Each (pg, step) has exactly one failure-domain level
        # (the rule step's), so a single dense array suffices.
        self.occ_dev = np.zeros((n_pg, max_steps, n_dev), dtype=np.int16)
        pg_pool = np.array([pg[0] for pg in self.pgs])
        for p in sorted(state.pools):
            idx = np.flatnonzero(pg_pool == p)
            for si, rstep in enumerate(state.pools[p].rule.steps):
                lvl = rstep.failure_domain
                self.occ_dev[idx, si] = \
                    self.occ[lvl][idx, si][:, self.dev_domain[lvl]]

        # per-device shard rows (python sets; updated incrementally)
        self.rows_on_dev: list[set[int]] = [set() for _ in range(n_dev)]
        for r, d in enumerate(self.sh_dev.tolist()):
            self.rows_on_dev[d].add(r)

    # -- mutation -----------------------------------------------------------

    def apply_row(self, row: int, dst_idx: int) -> Movement:
        pg, slot = self.shard_key[row]
        src_idx = int(self.sh_dev[row])
        size = float(self.sh_size[row])
        pgi = self.sh_pg[row]
        lvl = self.levels[self.sh_level[row]]
        stp = self.sh_step[row]

        self.member[pgi, src_idx] = False
        self.member[pgi, dst_idx] = True
        dom = self.dev_domain[lvl]
        self.occ[lvl][pgi, stp, dom[src_idx]] -= 1
        self.occ[lvl][pgi, stp, dom[dst_idx]] += 1
        self.occ_dev[pgi, stp] += ((dom == dom[dst_idx]).astype(np.int16)
                                   - (dom == dom[src_idx]).astype(np.int16))
        self.pool_counts[self.sh_pool[row], src_idx] -= 1
        self.pool_counts[self.sh_pool[row], dst_idx] += 1
        self.rows_on_dev[src_idx].discard(row)
        self.rows_on_dev[dst_idx].add(row)
        self.sh_dev[row] = dst_idx
        self.used[src_idx] -= size
        self.used[dst_idx] += size
        for i in (src_idx, dst_idx):
            u_new = self.used[i] / self.cap[i]
            self.util_sum += u_new - self.util[i]
            self.util_sumsq += u_new ** 2 - self.util[i] ** 2
            self.util[i] = u_new

        src_osd = self.state.devices[src_idx].id
        dst_osd = self.state.devices[dst_idx].id
        return Movement(pg, slot, src_osd, dst_osd, size)

    # -- candidate evaluation -------------------------------------------------

    def source_rows(self, src_idx: int) -> np.ndarray:
        """Shard rows on a device, largest-first with the faithful planner's
        tie-break ((-size, pg, slot) — rows are built in (pg, slot) order,
        so a stable sort on -size matches)."""
        rows = np.fromiter(self.rows_on_dev[src_idx], dtype=np.int64,
                           count=len(self.rows_on_dev[src_idx]))
        rows.sort()                              # (pg, slot) order
        order = np.argsort(-self.sh_size[rows], kind="stable")
        rows = rows[order]
        return rows[self.sh_size[rows] > 0.0]

    def peer_occupancy(self, rows: np.ndarray,
                       src_idx: int) -> tuple[np.ndarray, np.ndarray]:
        """(R, n_dev) peer occupancy per destination with each shard's own
        source domain already subtracted, plus the raw per-device domain
        occupancy.  No Python per-row work — two gathers on occ_dev /
        dev_domain_arr (levels differ per row, so both are indexed by the
        row's own level)."""
        occ = self.occ_dev[self.sh_pg[rows], self.sh_step[rows]]   # (R, n)
        lvl_rows = self.sh_level[rows]
        dom_rows = self.dev_domain_arr[lvl_rows]                   # (R, n)
        own = self.dev_domain_arr[lvl_rows, src_idx]               # (R,)
        peer = occ - (dom_rows == own[:, None]).astype(np.int16)
        return peer, occ

    def candidate_matrix(self, rows: np.ndarray, src_idx: int,
                         cfg: EquilibriumConfig) -> np.ndarray:
        """(len(rows), n_dev) pairs passing every criterion *except* the
        variance test — the PR-6 prune predicate's mask: a source whose
        candidate matrix is all-false holds a no-candidate certificate
        (the variance criterion alone can never create a legal move)."""
        n = self.n_dev
        sizes = self.sh_size[rows][:, None]                   # (R,1)

        # class match
        cls = self.sh_class[rows][:, None]                    # (R,1)
        cls_ok = legality.class_ok(cls, self.dev_class[None, :])

        # not already a member of the PG
        not_member = ~self.member[self.sh_pg[rows]]           # (R,n)

        # failure-domain free (excluding the shard's own slot): pure array
        # indexing against the incrementally-maintained occ_dev view
        peer, _ = self.peer_occupancy(rows, src_idx)
        dom_ok = peer <= 0

        # capacity fit
        cap_ok = legality.capacity_ok(
            self.used[None, :], legality.capacity_limit(self.cap[None, :],
                                                        cfg.headroom), sizes)

        # ideal-count criterion
        pool_rows = self.sh_pool[rows]
        cnt = self.pool_counts[pool_rows]                     # (R,n)
        ideal = self.ideal[pool_rows]                         # (R,n)
        src_cnt = cnt[np.arange(len(rows)), src_idx]
        src_ideal = ideal[np.arange(len(rows)), src_idx]
        src_ok = legality.src_count_ok(src_cnt, src_ideal, cfg.count_slack)
        dst_ok = legality.dst_count_ok(cnt, ideal, cfg.count_slack)

        # the faithful loop scans destinations emptiest-first and stops at
        # the source's own rank (see legality.before_source)
        u = self.util
        before_src = legality.before_source(u, u[src_idx], np.arange(n),
                                            src_idx)

        cand = (cls_ok & not_member & dom_ok & cap_ok & dst_ok
                & src_ok[:, None] & self.dev_in[None, :]
                & before_src[None, :])
        cand[:, src_idx] = False
        return cand

    def variance_mask(self, rows: np.ndarray, src_idx: int,
                      cfg: EquilibriumConfig) -> np.ndarray:
        """(len(rows), n_dev) exact variance delta < -min_variance_delta
        (strict improvement)."""
        sizes = self.sh_size[rows][:, None]                   # (R,1)
        u = self.util
        return legality.variance_improves(
            self.used[src_idx], self.used[None, :], self.cap[src_idx],
            self.cap[None, :], u[src_idx], u[None, :], sizes,
            self.util_sum, self.util_sumsq, float(self.n_dev),
            cfg.min_variance_delta)

    def valid_matrix(self, rows: np.ndarray, src_idx: int,
                     cfg: EquilibriumConfig) -> np.ndarray:
        """(len(rows), n_dev) boolean matrix of acceptable moves
        (candidate ∧ variance — boolean AND, so splitting the masks for
        the bounds path cannot change a bit)."""
        return (self.candidate_matrix(rows, src_idx, cfg)
                & self.variance_mask(rows, src_idx, cfg))

    def pick(self, rows: np.ndarray, valid: np.ndarray) -> tuple[int, int] | None:
        """First row (largest shard) with a valid destination; destination =
        min utilization (ties → lowest device index, matching np.argsort
        stable order of the faithful planner)."""
        any_valid = valid.any(axis=1)
        if not any_valid.any():
            return None
        i = int(np.argmax(any_valid))
        util = np.where(valid[i], self.util, np.inf)
        d = int(np.argmin(util))
        return int(rows[i]), d


# ---------------------------------------------------------------------------
# JAX kernel for the hot selection math


@partial(jax.jit, static_argnames=("n_dev",))
def _jax_select(sizes, cls, member, peer_occ, own_dom_eq, cnt, ideal,
                src_cnt, src_ideal, used, cap, util, util_sum, util_sumsq,
                dev_class, src_idx, count_slack, headroom,
                min_variance_delta, n_dev):
    """Jitted (R, n_dev) legality+criteria evaluation and selection.

    Returns (row_local_idx, dest_idx, found) — indices into the padded
    row block.  Padded rows carry size<=0 and are masked out.
    """
    R = sizes.shape[0]
    sizes_c = sizes[:, None]
    cls_ok = legality.class_ok(cls[:, None], dev_class[None, :])
    not_member = ~member
    dom_ok = (peer_occ - own_dom_eq[None, :].astype(peer_occ.dtype)) <= 0
    cap_ok = legality.capacity_ok(
        used[None, :], legality.capacity_limit(cap[None, :], headroom),
        sizes_c)
    src_ok = legality.src_count_ok(src_cnt, src_ideal, count_slack)
    dst_ok = legality.dst_count_ok(cnt, ideal, count_slack)

    n_f = jnp.asarray(n_dev, sizes.dtype)
    var_ok = legality.variance_improves(
        used[src_idx], used[None, :], cap[src_idx], cap[None, :],
        util[src_idx], util[None, :], sizes_c, util_sum, util_sumsq,
        n_f, min_variance_delta)

    valid = (cls_ok & not_member & dom_ok & cap_ok & dst_ok & var_ok
             & src_ok[:, None] & (sizes_c > 0))
    valid = valid.at[:, src_idx].set(False)

    any_valid = valid.any(axis=1)
    found = any_valid.any()
    i = jnp.argmax(any_valid)
    masked_util = jnp.where(valid[i], util, jnp.inf)
    d = jnp.argmin(masked_util)
    return i, d, found


# ---------------------------------------------------------------------------
# Planner entry point


def _balance_fast(state: ClusterState, cfg: EquilibriumConfig | None = None,
                  record_trajectory: bool = False, use_jax: bool = False,
                  pad_rows: int = 256, record_free_space: bool = True,
                  engine: str | None = None, stats_out: dict | None = None,
                  source_bounds: bool = False,
                  dense: "DenseState | None" = None):
    """Drop-in replacement for :func:`repro.core.equilibrium.balance` with
    identical outputs (move-for-move) and 1–3 orders of magnitude less
    planning time on paper-scale clusters.  Library-internal engine entry;
    the public API is ``repro.core.planner.create_planner("equilibrium")``.

    ``engine`` selects among the three implementations (all bit-identical):

    * ``"numpy"`` — the dense-NumPy path below; no warm-up cost, the
      small-cluster default (``use_jax=False``).
    * ``"batch"`` — the device-resident chunked-scan engine
      (:func:`repro.core.equilibrium_batch.balance_batch`); the
      ``use_jax=True`` path, O(1) host syncs per chunk of moves.
    * ``"jax-legacy"`` — the first-generation per-source jitted kernel
      (one dispatch + one blocking sync per source per move), retained as
      the measured baseline for benchmarks/bench_planner.py.
    """
    cfg = cfg or EquilibriumConfig()
    if engine is None:
        engine = "batch" if use_jax else "numpy"
    if engine not in ("numpy", "batch", "jax-legacy"):
        raise ValueError(f"unknown engine {engine!r}: "
                         "expected 'numpy', 'batch' or 'jax-legacy'")
    if engine == "batch":
        from .equilibrium_batch import _balance_batch
        return _balance_batch(state, cfg,
                              record_trajectory=record_trajectory,
                              record_free_space=record_free_space,
                              stats_out=stats_out,
                              source_bounds=source_bounds)
    use_legacy_jax = engine == "jax-legacy"
    if source_bounds and use_legacy_jax:
        raise ValueError("source_bounds is not supported by the jax-legacy "
                         "engine: its kernel does not expose the candidate "
                         "mask the prune predicate needs")

    from .tail import (SourceBounds, tail_flush, tail_record, tail_stats,
                       tail_terminal)
    # warm start (``dense`` kept from a prior call): accepted only when
    # the mirror provably matches the state — a stale mirror raises
    # instead of silently planning on arrays that missed a mutation
    if dense is None:
        dense = DenseState(state)
    else:
        dense.require_fresh(state)
    bounds = SourceBounds() if source_bounds else None
    movements: list[Movement] = []
    records: list[MoveRecord] = []
    acc = tail_stats(stats_out)

    while len(movements) < cfg.max_moves:
        t0 = time.perf_counter()
        src_order = legality.fullest_first(dense.util)[: cfg.k]
        picked = None
        tried = 0
        if bounds is not None:
            bounds.begin_scan()
        for src_idx in src_order:
            tried += 1
            src_idx = int(src_idx)
            if bounds is not None and bounds.skip(src_idx):
                continue
            rows = dense.source_rows(src_idx)
            if rows.size == 0:
                if bounds is not None:
                    bounds.prune(src_idx, 0.0)   # no pairs at all
                continue
            if use_legacy_jax:
                picked = _pick_jax(dense, rows, src_idx, cfg, pad_rows)
            elif bounds is not None:
                cand = dense.candidate_matrix(rows, src_idx, cfg)
                if not cand.any():
                    # no candidate pair: certificate (rows[0] = largest)
                    bounds.prune(src_idx, float(dense.sh_size[rows[0]]))
                    continue
                picked = dense.pick(rows,
                                    cand & dense.variance_mask(rows, src_idx,
                                                               cfg))
            else:
                valid = dense.valid_matrix(rows, src_idx, cfg)
                picked = dense.pick(rows, valid)
            if picked is not None:
                break
        dt = time.perf_counter() - t0
        if picked is None:
            if bounds is not None:
                bounds.end_terminal_scan()
            tail_terminal(acc, dt)
            break
        row, dst_idx = picked
        t1 = time.perf_counter()
        if bounds is not None:
            pool_i = int(dense.sh_pool[row])
            s_pre = int(dense.sh_dev[row])
            pgi = int(dense.sh_pg[row])
            c_old = float(dense.pool_counts[pool_i, s_pre])
            i_src = float(dense.ideal[pool_i, s_pre])
            flip = bool(legality.count_flip_enables(
                legality.dst_count_ok(c_old, i_src, cfg.count_slack),
                legality.dst_count_ok(c_old - 1.0, i_src, cfg.count_slack)))
            util_before = float(dense.util[s_pre])
            used_before = float(dense.used[s_pre])
        mv = dense.apply_row(row, dst_idx)
        state.apply(mv)
        dense.mark_synced()      # mirror and state absorbed the same move
        if bounds is not None:
            holders = np.flatnonzero(dense.member[pgi]).tolist() + [s_pre]
            counts = dense.pool_counts[pool_i]
            bounds.invalidate(
                s_pre, dst_idx, holders, util_before,
                float(dense.util[s_pre]), dense.util, used_before,
                float(legality.capacity_limit(dense.cap[s_pre],
                                              cfg.headroom)),
                flip, lambda s: counts[s] > 0)
        tail_record(acc, tried, dt, time.perf_counter() - t1)
        movements.append(mv)
        if record_trajectory:
            records.append(MoveRecord(
                movement=mv,
                variance_after=state.utilization_variance(),
                free_space_after=(state.total_pool_free_space()
                                  if record_free_space else float("nan")),
                planning_seconds=dt,
                sources_tried=tried,
            ))
    if bounds is not None:
        acc["bound_hits"] = bounds.bound_hits
        acc["pruned"] = bounds.pruned_count
        bounds.flush_counters()
    if stats_out is not None:
        stats_out["source_bounds"] = bool(source_bounds)
    tail_flush(acc)
    return movements, records


def balance_fast(state: ClusterState, cfg: EquilibriumConfig | None = None,
                 record_trajectory: bool = False, use_jax: bool = False,
                 pad_rows: int = 256, record_free_space: bool = True,
                 engine: str | None = None):
    """Deprecated: use ``create_planner("equilibrium")`` (numpy engine),
    ``create_planner("equilibrium_batch")`` (``use_jax=True``) or
    ``create_planner("equilibrium_jax_legacy")`` from
    :mod:`repro.core.planner` — same move sequences, unified PlanResult."""
    from ._compat import warn_deprecated
    warn_deprecated("repro.core.equilibrium_jax.balance_fast",
                    'create_planner("equilibrium")')
    return _balance_fast(state, cfg, record_trajectory=record_trajectory,
                         use_jax=use_jax, pad_rows=pad_rows,
                         record_free_space=record_free_space, engine=engine)


def _pick_jax(dense: DenseState, rows: np.ndarray, src_idx: int,
              cfg: EquilibriumConfig, pad_rows: int) -> tuple[int, int] | None:
    n = dense.n_dev
    R = len(rows)
    P = pad_rows * max(1, -(-R // pad_rows))      # round up to pad multiple
    def padded(a, fill=0):
        out = np.full((P,) + a.shape[1:], fill, dtype=a.dtype)
        out[:R] = a
        return out

    sizes = padded(dense.sh_size[rows].astype(np.float64), -1.0)
    cls = padded(dense.sh_class[rows], 0)
    # out devices and destinations at/after the source's utilization rank
    # are folded into the membership mask (each excludes a destination),
    # keeping the jitted kernel's signature stable
    before_src = legality.before_source(dense.util, dense.util[src_idx],
                                        np.arange(n), src_idx)
    member = padded(dense.member[dense.sh_pg[rows]]
                    | ~dense.dev_in[None, :] | ~before_src[None, :], True)
    # peer occupancy with the shard's own source domain already subtracted
    # (levels differ per row, so folding it here is simpler than in-kernel).
    peer = padded(dense.peer_occupancy(rows, src_idx)[0])
    own_dom_eq = np.zeros(n, dtype=bool)          # folded into peer above

    pool_rows = dense.sh_pool[rows]
    cnt = padded(dense.pool_counts[pool_rows])
    ideal = padded(dense.ideal[pool_rows])
    src_cnt = padded(dense.pool_counts[pool_rows, src_idx])
    src_ideal = padded(dense.ideal[pool_rows, src_idx])

    # bit-identity with the numpy/faithful engines requires the criteria
    # math in float64 — without x64, jnp.asarray silently downcasts every
    # float64 input to float32 and near-threshold count/variance tests can
    # flip (caught by the lifecycle fuzzer under non-default count_slack)
    with jax.enable_x64(True):
        i, d, found = _jax_select(
            jnp.asarray(sizes), jnp.asarray(cls), jnp.asarray(member),
            jnp.asarray(peer), jnp.asarray(own_dom_eq),
            jnp.asarray(cnt), jnp.asarray(ideal),
            jnp.asarray(src_cnt), jnp.asarray(src_ideal),
            jnp.asarray(dense.used), jnp.asarray(dense.cap),
            jnp.asarray(dense.util), dense.util_sum, dense.util_sumsq,
            jnp.asarray(dense.dev_class), src_idx, cfg.count_slack,
            cfg.headroom, cfg.min_variance_delta, n)
    if not bool(found):
        return None
    i = int(i)
    if i >= R:
        return None
    return int(rows[i]), int(d)
