"""Device-resident batched Equilibrium planner (engine 3 of 3).

The dense-NumPy planner (:mod:`repro.core.equilibrium_jax`) already
vectorized the per-source legality math, but its outer loop stayed on the
host: one selection per source per move, a Python peer-occupancy rebuild,
and — on the first-generation JAX path — one jit dispatch plus one
blocking ``bool(found)`` device sync per source.  This module moves the
*entire* planning loop onto the device:

* **All planning state lives in device arrays**, chosen so the per-move
  functional update never rewrites a large buffer (XLA CPU copies a
  scatter-updated loop carry wholesale, so the dense ``(n_pg, n_dev)``
  membership / domain-occupancy matrices of the NumPy engine are replaced
  by their compact ground truth): the ``(n_pg, max_pool_size)`` acting
  table, per-pool shard counts and their destination-count criterion, the
  per-device shard row-sets as a padded ``(n_dev, row_capacity)`` table
  in the faithful candidate order (size-descending, row-ascending), and
  the utilization order itself (a maintained stable argsort) — each
  updated incrementally by O(n) shift/scatter work per move.  Membership
  and failure-domain legality are recomputed per candidate tile from the
  acting table (≤ pool-size vectorized compares per destination), the way
  CRUSH evaluates placements from the map rather than from materialized
  occupancy.
* **One jitted step batches the k fullest sources.**  Legality +
  criteria are evaluated as a ``(source_block, row_block, n_dev)`` masked
  tensor; a ``lax.while_loop`` walks the (source, row) frontier and stops
  as soon as the *faithful* winner is decided: a source may only win once
  every fuller source is resolved (found or exhausted), i.e. the loop
  runs until ``min(found sources) < min(unresolved sources)``.  With
  ``source_block=cfg.k`` and ``row_block ≥ max rows/device`` this is the
  full ``(k, R_max, n_dev)`` tensor in one iteration.  The default tile
  is one source × :data:`ROW_BLOCK` rows: where the fullest source's
  first rows yield the move (cluster E) one step decides it, and where
  many rows and sources come up empty first (clusters B and D) the walk
  takes several steps, each evaluated whole — the same move sequence
  for every tile, property-tested across tile shapes.
* **The inner masked-argmax/argmin reduction is a kernel** —
  :func:`repro.kernels.ops.masked_select` (Pallas, compiled on a TPU
  over an exact int32 order key; the pure-jnp reference on CPU),
  returning per candidate row whether any destination is legal and the
  emptiest legal destination.
* **Moves apply functionally on-device.**  A ``lax.scan`` emits up to
  ``chunk`` moves per host round-trip; each applied move updates the
  carry with masked scatters (masked, not branched — ``lax.cond`` around
  the carry would also defeat buffer reuse).  The host syncs **once per
  chunk** (a single ``device_get`` of the emitted move block — O(1/chunk)
  syncs per move, regression-tested via :func:`host_sync_count`), instead
  of ~k times per move.
* **ClusterState reconciles once at the end**: the emitted move list is
  replayed through :meth:`ClusterState.apply` (which re-validates every
  source assignment), exactly like :func:`repro.core.simulate.simulate`
  replays movement logs.

All float math runs in float64 (``jax.enable_x64(True)``) with the
same expressions and evaluation order as the NumPy engine, so the move
sequences are **bit-identical** to the faithful §3.1 planner — property-
tested across multi-pool / multi-class / hybrid-rule clusters in
tests/test_equilibrium_batch.py.  Row tables are padded to
``row_capacity ≥ max shards/device + chunk`` so a chunk can never
overflow; if a destination's row list nears capacity the host re-pads and
resumes (exercised by the padding-boundary tests).
"""

from __future__ import annotations

import time
import weakref
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from . import legality
from .cluster import (ClusterDelta, ClusterState, DeviceAddDelta,
                      DeviceOutDelta, Movement, MovementDelta,
                      PoolCreateDelta, PoolGrowthDelta)
from .equilibrium import EquilibriumConfig, MoveRecord
from .legality import LegalityState
from .mirror import ShardMirror
from .tail import tail_flush, tail_record, tail_stats, tail_terminal
from .. import obs as _obs
from ..kernels.select_move import compact_parked, order_key
from ..obs import registry as _obs_registry


def host_sync_count() -> int:
    """Total device→host transfers issued by this engine — a monotonic
    read of the ``batch.host_syncs`` registry counter (test hook; tests
    assert on before/after deltas)."""
    return int(_obs_registry().get("batch.host_syncs"))


def dense_rebuild_count() -> int:
    """Total from-scratch dense-state builds (``batch.rebuilds`` registry
    counter; test hook for the warm-start path: consecutive plans on an
    unchanged cluster must not rebuild)."""
    return int(_obs_registry().get("batch.rebuilds"))


def _fetch(tree, what: str):
    """The only device→host transfer point in this module: one call per
    planning chunk (plus one per re-pad), never per move or per source.
    ``what`` names the reason on the ``batch.fetch`` span: ``chunk``,
    ``repad``, ``pruned`` or ``absorb``."""
    _obs_registry().inc("batch.host_syncs")
    with _obs.span("batch.fetch", cat="batch", what=what):
        return jax.device_get(tree)


def _select_rows(valid2d, util, backend: str, key=None):
    """Dispatch the masked-select reduction: per candidate row, any-legal
    flag and emptiest legal destination (ties → lowest device index).
    The Pallas backends reduce ``key``, the exact int32 order key of
    ``util`` (:func:`repro.kernels.select_move.order_key`); "pallas-tpu"
    compiles the kernel with Mosaic and "pallas" interprets it (a host
    without a TPU only — :class:`BatchPlanner` refuses it on a TPU)."""
    if backend == "ref":
        from ..kernels.ref import masked_select_ref
        return masked_select_ref(valid2d, util)
    from ..kernels.ops import masked_select
    return masked_select(valid2d, key, interpret=(backend == "pallas"))


def _shift_remove(arr, pos, pad):
    """Drop ``arr[pos]``, shift the tail left, pad the freed last slot."""
    n = arr.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    out = jnp.where(idx >= pos, jnp.roll(arr, -1), arr)
    return out.at[n - 1].set(pad)


def _shift_insert(arr, pos, value):
    """Insert ``value`` at ``pos``, shifting the tail right (last drops)."""
    n = arr.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    return jnp.where(idx < pos, arr,
                     jnp.where(idx == pos, value, jnp.roll(arr, 1)))


# ---------------------------------------------------------------------------
# The jitted chunk: select + apply up to `m` moves entirely on-device


def _plan_chunk_impl(dyn, const, slack, headroom, min_dvar, n_real, k_eff,
                     active0, *, k, kb, rb, m, backend, cached, bounds,
                     telemetry=False):
    """Run up to ``m`` planning steps on-device.

    dyn   = (used, util, util_sum, util_sumsq, acting, pool_counts,
             dst_ok, rows_on, nrows, order,
             cache_dev, cache_ok, cache_clean, pruned) — mutated
             functionally
    const = (cap, dev_class, dev_in, dev_domain, sh_size, sh_pg, sh_pool,
             sh_class, sh_level, sh_slot, sh_sbase, sh_scnt, ideal)

    ``cache_*`` is the cross-move incremental legality cache (enabled by
    the static ``cached`` flag): per top-k source rank, the tile's full
    *candidate* mask — every criterion except the variance test: class
    match ∧ ¬PG-member ∧ failure-domain free ∧ capacity fit ∧ both count
    criteria ∧ the emptiest-first cutoff — tagged with the device it was
    computed for (``cache_dev``) and per-row-block validity bits
    (``cache_clean``).  ``apply_move`` repairs the cache instead of
    discarding it: the two touched devices' tiles and the row-blocks
    holding a shard of the moved PG are invalidated, and — because a
    move's dynamic inputs only change at its two endpoints — the
    endpoints' *destination columns* of every other cached tile are
    recomputed in place, so a clean tile stays bitwise the fresh
    evaluation.  Only the variance test (whose ``util_sum``/``util_sumsq``
    inputs change globally every move) is recomputed per walk, and only
    for tiles that hold a candidate at all.  Rank-keyed entries whose
    device changed (the maintained order shifted) simply miss and
    recompute; correctness never depends on a hit.

    ``pruned`` is the persistent source-bound state (enabled by the
    static ``bounds`` flag): a device is pruned when a full scan saw no
    candidate pair on it — the one verdict the variance criterion alone
    can never revisit, so the certificate stays valid until a move
    perturbs a device past it (the legality-core ``bound_*`` triggers in
    ``apply_move``).  Each step starts from the pruned-compacted source
    queue (:func:`repro.kernels.select_move.compact_sources`) so the
    convergence tail skips fruitless sources without touching their
    legality tiles.

    Returns (dyn', done, overflow, tel, moves (m, 5) int32, steps (m,)
    int32) where each move row is (shard_row, src_idx, dst_idx,
    sources_tried, bound_skips) or -1 sentinels; ``sources_tried`` counts
    ranks in the *full* fullest-first order (identical with and without
    ``bounds``) and ``bound_skips`` of those ranks were skipped by live
    certificates.  ``steps`` counts the walk's iterations at each scan
    step, the fruitless final walk and an overflowing move's walk
    included (0 where the step is inactive) — always on, one add per
    iteration.

    ``tel`` is the device-side telemetry vector (int32[4]: legality
    tiles walked, i.e. the sum of ``steps``, tiles holding a candidate,
    legality-cache hits, legality-cache misses), populated only under the static ``telemetry``
    flag — the disabled variant compiles the counter updates away
    entirely, so tracing can never perturb the move sequence (it only
    ever reads).  The host fetches it with the same per-chunk sync that
    returns the moves.

    ``n_real`` / ``k_eff`` / ``active0`` are *traced* per-cluster scalars
    that make the step ``vmap``-safe across a fleet of clusters padded to
    a common static shape (:mod:`repro.fleet`): ``n_real`` (float64) is
    the cluster's true device count — the ``n`` of the variance
    acceptance, which must not see shape padding; ``k_eff`` (int32 ≤ the
    static ``k``) is the cluster's true source-queue depth — ranks past
    it are parked exactly like pruned sources, so pad devices can never
    win, prune, or extend the walk; ``active0`` (bool) seeds the chunk's
    ``done`` flag, the early-exit mask for already-converged lanes (an
    inactive lane's while_loop body never runs and its carry is returned
    untouched).  The single-cluster wrapper :func:`_plan_chunk` passes
    ``n_real = n_dev``, ``k_eff = k``, ``active0 = True``, which makes
    every guard the constant it was before this factoring — the
    sequences stay bit-identical (property-tested).
    """
    (cap, dev_class, dev_in, dev_domain, sh_size, sh_pg, sh_pool,
     sh_class, sh_level, sh_slot, sh_sbase, sh_scnt, ideal) = const
    n_dev = cap.shape[0]
    n_slots = dyn[4].shape[1]
    r_cap = dyn[7].shape[1]
    n_blocks = r_cap // rb              # _round_cap keeps r_cap % rb == 0
    n_f = n_real                        # true device count, not the padded
    #                                     shape — the variance criterion's n
    n_sb = -(-k // kb)
    k_pad = n_sb * kb
    dev_iota = jnp.arange(n_dev, dtype=jnp.int32)
    cap_lim = legality.capacity_limit(cap, headroom)  # loop-invariant

    def select_one(dyn, active, tel):
        """One §3.1 planning step: walk (source-block, row-block) tiles of
        the batched legality tensor until the faithful winner is decided."""
        used, util, us, usq, acting, pool_counts, dst_ok, \
            rows_on, nrows, order, c_dev, c_ok, c_clean, pruned = dyn
        order_k = order[:k]         # maintained == argsort(-util, stable)
        # the Pallas select reduces an exact int32 key of util, derived
        # from the maintained order once per step
        key = None if backend == "ref" else order_key(util, order)
        if bounds:
            # persistent priority queue: unpruned sources first (faithful
            # fullest-first order preserved), pruned sources parked at
            # the back.  Parked entries contribute no rows and can
            # neither win nor re-prune (the n_avail guards below), so the
            # walk starts at the first plausible source.  Ranks past
            # k_eff (fleet shape padding) park through the same
            # partition: they sort behind every real rank and the
            # n_avail count excludes them.
            rank_k = jnp.arange(k, dtype=jnp.int32)
            parked = pruned[order_k] | (rank_k >= k_eff)
            src_order, n_avail = compact_parked(order_k, parked)
        else:
            src_order, n_avail = order_k, k_eff
        if k_pad > k:   # pad to a source-block multiple; masked from wins
            src_order = jnp.pad(src_order, (0, k_pad - k))
        rows_k = rows_on[src_order]         # (k_pad, r_cap), faithful order
        n_rows_k = jnp.where(jnp.arange(k_pad) < n_avail,
                             nrows[src_order], 0)

        def eval_static(sb, c):
            """(kb, rb, n_dev) static legality for tile (sb, c): class
            match ∧ ¬member ∧ failure-domain free — everything derived
            from the acting table and device registry only, i.e. the
            cacheable half."""
            blk = lax.dynamic_slice(rows_k, (sb * kb, c * rb), (kb, rb))
            r = jnp.clip(blk, 0)
            pg = sh_pg[r]
            lvl = sh_level[r]
            slot = sh_slot[r]
            sbase = sh_sbase[r]
            scnt = sh_scnt[r]
            # device domain ids at each row's failure-domain level
            dom = jnp.broadcast_to(dev_domain[0][None, None, :],
                                   (kb, rb, n_dev))
            for l in range(1, dev_domain.shape[0]):
                dom = jnp.where((lvl == l)[..., None], dev_domain[l], dom)
            # membership + per-step domain separation straight from the
            # acting table: ≤ n_slots vectorized compares per destination
            # (padded slots are -1 and never match)
            acting_t = acting[pg]                                # (kb, rb, S)
            bad = jnp.zeros((kb, rb, n_dev), bool)
            for j in range(n_slots):
                a_j = acting_t[..., j]                           # (kb, rb)
                in_step = (j >= sbase) & (j < sbase + scnt) & (j != slot)
                peer_dom = dev_domain[lvl, jnp.clip(a_j, 0)]
                bad |= a_j[..., None] == dev_iota                # member
                bad |= in_step[..., None] & (dom == peer_dom[..., None])
            cls = sh_class[r]
            return legality.class_ok(cls[..., None],
                                     dev_class[None, None, :]) & ~bad

        def eval_cand(sb, c):
            """(kb, rb, n_dev) *candidate* mask for tile (sb, c): every
            criterion except the variance test — the vocabulary of the
            no-candidate prune predicate, and (under ``cached``) the tile
            payload the cross-move cache stores and column-repairs."""
            blk = lax.dynamic_slice(rows_k, (sb * kb, c * rb), (kb, rb))
            src_b = lax.dynamic_slice_in_dim(src_order, sb * kb, kb)
            r = jnp.clip(blk, 0)
            size = jnp.where(blk >= 0, sh_size[r], 0.0)          # (kb, rb)
            real = size > 0.0
            pool = sh_pool[r]
            cap_ok = legality.capacity_ok(used[None, None, :], cap_lim,
                                          size[..., None])
            crit = dst_ok[pool]                                  # (kb, rb, n)
            cnt_s = pool_counts[pool, src_b[:, None]]            # (kb, rb)
            idl_s = ideal[pool, src_b[:, None]]
            src_ok = legality.src_count_ok(cnt_s, idl_s, slack)
            u_s = util[src_b][:, None, None]
            not_self = dev_iota[None, None, :] != src_b[:, None, None]
            # faithful destination cutoff (legality.before_source)
            before_src = legality.before_source(
                util[None, None, :], u_s, dev_iota[None, None, :],
                src_b[:, None, None])
            return (eval_static(sb, c) & cap_ok & crit
                    & (real & src_ok)[..., None]
                    & not_self & dev_in[None, None, :] & before_src)

        def eval_var(sb, c):
            """(kb, rb, n_dev) exact variance-delta acceptance for tile
            (sb, c) — the one criterion whose inputs (the maintained
            ``util_sum``/``util_sumsq`` moments) change globally every
            move, so it is never cached and only evaluated for tiles
            that hold a candidate at all."""
            blk = lax.dynamic_slice(rows_k, (sb * kb, c * rb), (kb, rb))
            src_b = lax.dynamic_slice_in_dim(src_order, sb * kb, kb)
            r = jnp.clip(blk, 0)
            size = jnp.where(blk >= 0, sh_size[r], 0.0)          # (kb, rb)
            u_s = util[src_b][:, None, None]
            return legality.variance_improves(
                used[src_b][:, None, None], used[None, None, :],
                cap[src_b][:, None, None], cap[None, None, :],
                u_s, util[None, None, :], size[..., None],
                us, usq, n_f, min_dvar)

        def body(carry):
            (sb, c, found_row, found_dst, win_j, win_row, win_dst, done,
             c_dev, c_ok, c_clean, marg, pruned, tel, steps) = carry
            src_b = lax.dynamic_slice_in_dim(src_order, sb * kb, kb)
            if cached:
                zero = jnp.int32(0)
                tags = lax.dynamic_slice_in_dim(c_dev, sb * kb, kb)
                clean_b = lax.dynamic_slice(c_clean, (sb * kb, c),
                                            (kb, 1))[:, 0]
                hit = jnp.all((tags == src_b) & clean_b)
                # only the expensive evaluation is conditional — the
                # large cache buffers stay *outside* the cond (a
                # conditional that returns them would copy the whole
                # buffer every iteration); on a hit the same block is
                # harmlessly rewritten in place.  A clean cached tile is
                # bitwise the fresh candidate mask: apply_move repairs
                # the endpoints' destination columns in place.
                cand = lax.cond(
                    hit,
                    lambda: lax.dynamic_slice(
                        c_ok, (sb * kb, c * rb, zero), (kb, rb, n_dev)),
                    lambda: eval_cand(sb, c))
                c_ok = lax.dynamic_update_slice(
                    c_ok, cand, (sb * kb, c * rb, zero))
                # a tag change invalidates the slot's other blocks (a
                # no-op when the tags already matched)
                keep = tags == src_b
                rowc = lax.dynamic_slice(c_clean, (sb * kb, zero),
                                         (kb, n_blocks))
                rowc = jnp.where(keep[:, None], rowc, False)
                rowc = lax.dynamic_update_slice(
                    rowc, jnp.ones((kb, 1), bool), (zero, c))
                c_clean = lax.dynamic_update_slice(c_clean, rowc,
                                                   (sb * kb, zero))
                c_dev = lax.dynamic_update_slice(c_dev, src_b, (sb * kb,))
                if telemetry:
                    tel = tel.at[2].add(hit.astype(jnp.int32))
                    tel = tel.at[3].add((~hit).astype(jnp.int32))
            else:
                cand = eval_cand(sb, c)
            any_rows = jnp.any(cand, axis=(1, 2))            # (kb,)
            steps = steps + 1
            if telemetry:
                tel = tel.at[1].add(jnp.any(any_rows).astype(jnp.int32))
            # the variance test + masked-select reduction only run when
            # the tile holds a candidate at all; the convergence-tail
            # walk is dominated by tiles that do not.  A dead tile's
            # select would return (all-False, all-0) — exactly the
            # short-circuit value, so the sequence is unchanged.
            anyv, dst = lax.cond(
                jnp.any(any_rows),
                lambda t: _select_rows(
                    (t & eval_var(sb, c)).reshape(kb * rb, n_dev),
                    util, backend, key),
                lambda t: (jnp.zeros((kb * rb,), bool),
                           jnp.zeros((kb * rb,), jnp.int32)),
                cand)
            anyv = anyv.reshape(kb, rb)
            dst = dst.reshape(kb, rb)
            first_i = jnp.argmax(anyv, axis=1)
            has = jnp.take_along_axis(anyv, first_i[:, None], 1)[:, 0]
            tile_dst = jnp.take_along_axis(dst, first_i[:, None], 1)[:, 0]
            idxb = jnp.arange(kb, dtype=jnp.int32)
            in_avail = sb * kb + idxb < n_avail
            has &= in_avail                 # pad / parked sources alias
            newly = has & (found_row < 0)   # real devices; may never win
            found_row = jnp.where(newly, (c * rb + first_i).astype(jnp.int32),
                                  found_row)
            found_dst = jnp.where(newly, tile_dst.astype(jnp.int32),
                                  found_dst)
            # a source wins once every fuller source in its block resolved
            # (blocks are walked in source order, so earlier blocks already
            # resolved empty); decided/exhausted drive the frontier
            n_rows_b = lax.dynamic_slice_in_dim(n_rows_k, sb * kb, kb)
            found = found_row >= 0
            unres = ~found & (n_rows_b > (c + 1) * rb)
            min_found = jnp.min(jnp.where(found, idxb, kb))
            min_unres = jnp.min(jnp.where(unres, idxb, kb))
            decided = min_found < min_unres
            exhausted = (min_found == kb) & (min_unres == kb)
            jb = jnp.clip(min_found, 0, kb - 1)
            win_j = jnp.where(decided, sb * kb + jb, win_j)
            win_row = jnp.where(decided, found_row[jb], win_row)
            win_dst = jnp.where(decided, found_dst[jb], win_dst)
            if bounds:
                # certificate: a fully-walked fruitless source whose scan
                # saw no candidate pair anywhere — the one verdict the
                # variance criterion alone can never change.  ``marg``
                # accumulates any-candidate per block slot; sources still
                # mid-walk (unres) or winning are never pruned.
                marg = marg | any_rows
                scanned = (decided | exhausted) & ~found & ~unres
                prunable = scanned & ~marg & in_avail
                tgt = jnp.where(prunable, src_b, n_dev)  # OOB writes drop
                pruned = pruned.at[tgt].set(True, mode="drop")
            next_sb = jnp.where(exhausted, sb + 1, sb)
            next_c = jnp.where(exhausted, 0, c + 1)
            done = decided | (exhausted & ((sb + 1) * kb >= n_avail))
            reset = jnp.full((kb,), -1, jnp.int32)
            found_row = jnp.where(exhausted, reset, found_row)
            found_dst = jnp.where(exhausted, 0, found_dst)
            marg = jnp.where(exhausted, False, marg)
            return (next_sb, next_c, found_row, found_dst,
                    win_j, win_row, win_dst, done, c_dev, c_ok, c_clean,
                    marg, pruned, tel, steps)

        def cond(carry):
            return active & ~carry[7]

        init = (jnp.int32(0), jnp.int32(0), jnp.full((kb,), -1, jnp.int32),
                jnp.zeros((kb,), jnp.int32), jnp.int32(-1), jnp.int32(-1),
                jnp.int32(0), jnp.bool_(False), c_dev, c_ok, c_clean,
                jnp.zeros((kb,), bool), pruned, tel, jnp.int32(0))
        out = lax.while_loop(cond, body, init)
        win_j, win_row, win_dst = out[4], out[5], out[6]
        dyn = dyn[:10] + (out[8], out[9], out[10], out[12])
        tel, steps = out[13], out[14]
        if telemetry:                   # tiles walked = the walk's steps
            tel = tel.at[0].add(steps)
        found = win_j >= 0
        jw = jnp.clip(win_j, 0, k_pad - 1)
        win_dev = src_order[jw]
        if bounds:
            # faithful rank of the winner in the *full* fullest-first
            # order: the sources_tried histogram stays identical with and
            # without the bounds, and the surplus (rank − compacted
            # position) counts the scans live certificates skipped.
            rank = jnp.argmax(order_k == win_dev).astype(jnp.int32)
        else:
            rank = win_j
        return (found,
                rows_k[jw, jnp.clip(win_row, 0, r_cap - 1)],
                win_dev,
                win_dst,
                rank + 1,
                rank - jw,
                dyn,
                tel,
                steps)

    def reorder(order, util, src, dst):
        """Re-sort ``src`` and ``dst`` within the maintained stable
        argsort(-util) order after their utilizations changed.  Both are
        removed before either is re-inserted — inserting one while the
        other still sits at a stale rank would miscount its position by
        one whenever the two straddle the insertion point.  Insertion
        ranks are counted from the (-util, index) key, exactly the stable
        sort's comparator."""
        o = _shift_remove(order, jnp.argmax(order == src).astype(jnp.int32),
                          jnp.int32(-1))
        o = _shift_remove(o, jnp.argmax(o == dst).astype(jnp.int32),
                          jnp.int32(-1))
        u_s, u_d = util[src], util[dst]
        before_src = ((util > u_s) | ((util == u_s) & (dev_iota < src))) \
            & (dev_iota != dst)
        o = _shift_insert(o, jnp.sum(before_src).astype(jnp.int32), src)
        before_dst = (util > u_d) | ((util == u_d) & (dev_iota < dst))
        return _shift_insert(o, jnp.sum(before_dst).astype(jnp.int32), dst)

    def apply_move(dyn, ok, row, src, dst):
        """Functional mirror of DenseState.apply_row (same update order,
        bit-identical float accumulation).  ``ok=False`` makes every
        update a no-op *without branching*, so XLA keeps the scan carry
        buffers in place; no update touches more than O(n) elements."""
        used, util, us, usq, acting, pool_counts, dst_ok, \
            rows_on, nrows, order, c_dev, c_ok, c_clean, pruned = dyn
        okf = ok.astype(jnp.float64)
        oki = ok.astype(jnp.int32)
        row = jnp.where(ok, row, 0)
        size = sh_size[row]
        pgi = sh_pg[row]
        pool = sh_pool[row]
        slot = sh_slot[row]
        both = jnp.stack([src, dst])
        if bounds:
            # pre-update snapshots for the source-side certificate
            # triggers (legality.bound_*): only the move's source can
            # enable a blocked pair — the destination only gains bytes,
            # shards and membership, all disabling.
            util_src_before = util[src]
            used_src_before = used[src]
            dok_src_before = dst_ok[pool, src]
        acting = acting.at[pgi, slot].set(jnp.where(ok, dst,
                                                    acting[pgi, slot]))
        pool_counts = pool_counts.at[pool, both].add(
            jnp.stack([-okf, okf]))
        # the destination-count criterion only changes where the counts
        # changed: recompute those two entries
        c2 = pool_counts[pool, both]
        i2 = ideal[pool, both]
        ok2 = legality.dst_count_ok(c2, i2, slack)
        dst_ok = dst_ok.at[pool, both].set(jnp.where(ok, ok2,
                                                     dst_ok[pool, both]))
        # sorted row lists: shift-remove from src, shift-insert into dst
        # (keeps the (size desc, row asc) faithful candidate order)
        src_list = rows_on[src]
        pos_s = jnp.argmax(src_list == row).astype(jnp.int32)
        removed = _shift_remove(src_list, pos_s, jnp.int32(-1))
        dst_list = rows_on[dst]
        dsz = jnp.where(dst_list >= 0, sh_size[jnp.clip(dst_list, 0)],
                        -jnp.inf)
        before = (dst_list >= 0) & ((dsz > size)
                                    | ((dsz == size) & (dst_list < row)))
        pos_d = jnp.sum(before).astype(jnp.int32)
        inserted = _shift_insert(dst_list, pos_d, row)
        rows_on = rows_on.at[both].set(
            jnp.stack([jnp.where(ok, removed, src_list),
                       jnp.where(ok, inserted, dst_list)]))
        nrows = nrows.at[both].add(jnp.stack([-oki, oki]))
        used = used.at[both].add(jnp.stack([-size * okf, size * okf]))
        for i in (src, dst):                  # source first, like apply_row
            u_new = used[i] / cap[i]          # no-op when ok=False: the
            us = us + (u_new - util[i])       # recomputed ratio is bit-
            usq = usq + (u_new ** 2 - util[i] ** 2)   # identical, deltas
            util = util.at[i].set(u_new)      # are exactly 0.0
        order = jnp.where(ok, reorder(order, util, src, dst), order)
        if bounds:
            # surgical certificate invalidation — the same legality-core
            # trigger set SourceBounds.invalidate applies host-side:
            # touch (endpoints), holder (post-move acting set of the
            # moved PG plus the old source), emptiest-order crossing,
            # count flip, capacity binding.
            acting_pg = acting[pgi]                          # (n_slots,)
            holder = jnp.any(acting_pg[None, :] == dev_iota[:, None],
                             axis=1)
            touch = (dev_iota == src) | (dev_iota == dst) | holder
            crossed = legality.bound_crossed(util_src_before, util[src],
                                             util, src, dev_iota)
            flip = legality.count_flip_enables(dok_src_before,
                                               dst_ok[pool, src])
            holds_pool = pool_counts[pool] > 0.0
            largest = rows_on[:, 0]
            maxsz = jnp.where(largest >= 0,
                              sh_size[jnp.clip(largest, 0)], 0.0)
            bind = legality.bound_capacity_binding(used_src_before,
                                                   cap_lim[src], maxsz)
            inval = touch | crossed | (flip & holds_pool) | bind
            pruned = jnp.where(ok, pruned & ~inval, pruned)
        if cached:
            # cache repair, part 1: the move perturbs the two touched
            # devices' tiles and the row-blocks holding a shard of the
            # moved PG (its acting set changed) — invalidate exactly
            # those; everything else stays warm across moves
            touched = (c_dev == src) | (c_dev == dst)      # (k_pad,)
            rows_c = rows_on[jnp.clip(c_dev, 0)]           # (k_pad, r_cap)
            has_pg = (rows_c >= 0) & (sh_pg[jnp.clip(rows_c, 0)] == pgi)
            has_pg_b = has_pg.reshape(k_pad, n_blocks, rb).any(axis=2)
            dirty = touched[:, None] | has_pg_b            # (k_pad, blocks)
            c_clean = jnp.where(ok, c_clean & ~dirty, c_clean)
            # cache repair, part 2 — exact column repair: of a cached
            # candidate tile's dynamic inputs, only those at the two
            # endpoints changed (used/util/dst_ok at src/dst), so
            # recomputing the endpoints' destination columns for every
            # cache slot keeps clean tiles bitwise the fresh evaluation
            tags_c = jnp.clip(c_dev, 0)                    # (k_pad,)
            rc = jnp.clip(rows_c, 0)
            lvlc = sh_level[rc]
            slotc = sh_slot[rc]
            sbasec = sh_sbase[rc]
            scntc = sh_scnt[rc]
            sizec = jnp.where(rows_c >= 0, sh_size[rc], 0.0)
            poolc = sh_pool[rc]
            dom_d = dev_domain[lvlc[:, :, None], both[None, None, :]]
            acting_c = acting[sh_pg[rc]]                   # (k_pad, r_cap, S)
            badc = jnp.zeros(dom_d.shape, bool)
            for j in range(n_slots):
                a_j = acting_c[..., j]
                in_step = (j >= sbasec) & (j < sbasec + scntc) & (j != slotc)
                peer = dev_domain[lvlc, jnp.clip(a_j, 0)]
                badc |= a_j[..., None] == both[None, None, :]
                badc |= in_step[..., None] & (dom_d == peer[..., None])
            staticc = legality.class_ok(
                sh_class[rc][..., None],
                dev_class[both][None, None, :]) & ~badc
            cap_okc = legality.capacity_ok(used[both][None, None, :],
                                           cap_lim[both][None, None, :],
                                           sizec[..., None])
            critc = dst_ok[poolc[:, :, None], both[None, None, :]]
            cnt_sc = pool_counts[poolc, tags_c[:, None]]
            idl_sc = ideal[poolc, tags_c[:, None]]
            src_okc = legality.src_count_ok(cnt_sc, idl_sc, slack)
            u_sc = util[tags_c][:, None, None]
            not_selfc = both[None, None, :] != tags_c[:, None, None]
            beforec = legality.before_source(
                util[both][None, None, :], u_sc, both[None, None, :],
                tags_c[:, None, None])
            colsc = (staticc & cap_okc & critc
                     & ((sizec > 0.0) & src_okc)[..., None]
                     & not_selfc & dev_in[both][None, None, :] & beforec)
            c_ok = c_ok.at[:, :, both].set(
                jnp.where(ok, colsc, c_ok[:, :, both]))
        return (used, util, us, usq, acting, pool_counts, dst_ok,
                rows_on, nrows, order, c_dev, c_ok, c_clean, pruned)

    def step(carry, _):
        dyn, done, overflow, tel = carry
        active = ~(done | overflow)
        found, row, src, dst, tried, skipped, dyn, tel, steps = \
            select_one(dyn, active, tel)
        # a full destination row-list would drop a shard: stop the chunk
        # and let the host re-pad (never hit when row_capacity >= max
        # rows/device + chunk, the packing invariant)
        ovf = found & (dyn[8][dst] >= r_cap)
        ok = active & found & ~ovf
        dyn = apply_move(dyn, ok, row, src, dst)
        emit = jnp.where(ok, jnp.stack([row, src, dst, tried, skipped]),
                         jnp.full((5,), -1, jnp.int32))
        done = done | (active & ~found)
        overflow = overflow | ovf
        return (dyn, done, overflow, tel), (emit, steps)

    carry0 = (dyn, ~active0, jnp.bool_(False),
              jnp.zeros((4,), jnp.int32))
    (dyn, done, overflow, tel), (moves, steps) = lax.scan(
        step, carry0, None, length=m)
    return dyn, done, overflow, tel, moves, steps


#: The chunk carry is donated to the jit call (``donate_argnums``): the
#: previous chunk's output buffers are reused in place instead of copied
#: per dispatch.  Structural (not a knob) — exported so benchmarks can
#: record the variant honestly in derived fields.
DONATED_CARRY = True

#: Rows of a legality tile, the rows of each source one step of the walk
#: evaluates, where the caller gives no ``row_block``.  The walk's device
#: time is its step count times a step's cost; 32 rows take a third of
#: the 8-row tile's steps a move on the paper's clusters B and D and
#: about as many on E, where the fullest source's first rows yield the
#: move.  The choice assumes a 32-row step costs about what an 8-row
#: one does on the chip (PERF.md §6).
ROW_BLOCK = 32


@partial(jax.jit, static_argnames=("k", "kb", "rb", "m", "backend", "cached",
                                   "bounds", "telemetry"),
         donate_argnums=(0,))
def _plan_chunk(dyn, const, slack, headroom, min_dvar, *,
                k, kb, rb, m, backend, cached, bounds, telemetry=False):
    """Single-cluster jitted entry over :func:`_plan_chunk_impl` — the
    degenerate fleet of one: no shape padding (``n_real = n_dev``,
    ``k_eff = k``) and an always-active lane.  Kept as the planner's
    call target so the fleet factoring cannot perturb the single-cluster
    sequence (the extra scalars fold to the constants they replaced).

    The ``dyn`` carry is donated — every element of the output carry
    matches a donated input buffer in shape and dtype, so XLA updates the
    carry in place and the per-chunk buffer copies disappear.  Callers
    must treat the passed-in carry as consumed (the planner always
    rebinds ``self._dyn`` to the returned one).  The trailing
    ``max(nrows)`` output replaces the host's post-hoc fetch of the whole
    ``nrows`` vector for the re-pad check, keeping the per-chunk sync
    payload O(chunk) — and free of references into the donated carry."""
    n_dev = const[0].shape[0]
    dyn, done, overflow, tel, moves, steps = _plan_chunk_impl(
        dyn, const, slack, headroom, min_dvar,
        jnp.asarray(float(n_dev), jnp.float64), jnp.int32(k),
        jnp.bool_(True), k=k, kb=kb, rb=rb, m=m, backend=backend,
        cached=cached, bounds=bounds, telemetry=telemetry)
    return dyn, done, overflow, tel, moves, steps, jnp.max(dyn[8])


# ---------------------------------------------------------------------------
# Host driver


def _pack_rows(sh_dev: np.ndarray, sh_size: np.ndarray, n_dev: int,
               r_cap: int) -> tuple[np.ndarray, np.ndarray]:
    """Per-device row tables padded to (n_dev, r_cap), each in the
    faithful candidate order: size descending, row (= (pg, slot))
    ascending; and the per-device row counts."""
    order = np.lexsort((np.arange(len(sh_dev)), -sh_size, sh_dev))
    nrows = np.bincount(sh_dev, minlength=n_dev)
    dev = sh_dev[order]
    pos = np.arange(len(order)) - (np.cumsum(nrows) - nrows)[dev]
    rows = np.full((n_dev, r_cap), -1, np.int32)
    rows[dev, pos] = order
    return rows, nrows.astype(np.int32)


class BatchPlanner:
    """Warm-startable handle on the device-resident engine.

    :func:`balance_batch` rebuilt the full dense mirror — ShardMirror, the
    packed row tables, the acting table, every device array — on *every*
    call, even when nothing changed since the last plan.  The scenario
    engine (:mod:`repro.sim.engine`) calls the planner every
    ``RebalanceTick``, usually with a small per-tick move budget, so the
    rebuild would dominate: this class keeps the device carry (``dyn``)
    alive between calls and resumes planning from it whenever the bound
    :class:`ClusterState` has not been mutated by anyone else.

    Staleness is detected through ``state.mutation_epoch``: the planner
    records the epoch after replaying its own emitted moves; an external
    mutation makes the epochs disagree.  The planner subscribes to the
    bound state's :class:`~repro.core.cluster.ClusterDelta` stream
    (:meth:`ClusterState.subscribe`), so at the next :meth:`plan` it knows
    *what* changed, not just that something did:

    * **Every known delta type absorbs into the device carry**
      (:meth:`observe` / ``_absorb``, full coverage since PR 4):
      :class:`PoolGrowthDelta` and :class:`DeviceOutDelta` are pure host
      refreshes (sizes / utils / ideals / in-mask / orders recomputed
      with the shared legality core), :class:`DeviceAddDelta` extends the
      ``n_dev`` axis with padded rows, :class:`MovementDelta` (a foreign
      balancer's move) and :class:`PoolCreateDelta` re-read the mutated
      assignment append-only.  A non-empty overshoot stash no longer
      blocks absorption — the stashed continuation (planned pre-delta,
      never applied to the state) is discarded and re-derived.  The
      refreshed carry is bitwise equal to a freshly built one, so warm
      continuations stay bit-identical to cold starts (regression-tested
      via :func:`dense_rebuild_count`).
    * The conservative full-rebuild fallback remains for unknown delta
      types, a missed/conflicting delta stream, and id-renumbering
      topology changes (a device class or pool id sorting before existing
      ones) — correctness never depends on absorption.

    Because the §3.1 sequence is deterministic, a warm continuation emits
    exactly the moves a cold-start planner would (property-tested in
    tests/test_equilibrium_batch.py and tests/test_planner_api.py),
    including moves the device planned past a call's budget — those are
    stashed (they are already applied in the device carry) and emitted
    first by the next call.
    """

    #: pending-delta backlog above which we stop tracking and just rebuild
    PENDING_CAP = 8192

    def __init__(self, state: ClusterState,
                 cfg: EquilibriumConfig | None = None, chunk: int = 64,
                 source_block: int = 1, row_block: int | None = None,
                 row_capacity: int | None = None,
                 select_backend: str = "auto",
                 legality_cache: bool = False,
                 source_bounds: bool = True,
                 pipeline: bool = True):
        self.state = state
        self.cfg = cfg or EquilibriumConfig()
        self.chunk = chunk
        self.row_capacity = row_capacity
        self.legality_cache = legality_cache
        self.source_bounds = source_bounds
        # pipelined dispatch: overlap chunk i+1's device work with chunk
        # i's host-side processing (pure scheduling — the dispatch gate in
        # _chunk_loop keeps the emitted sequence bit-identical)
        self.pipeline = pipeline
        on_tpu = jax.default_backend() == "tpu"
        if select_backend == "auto":
            select_backend = "pallas-tpu" if on_tpu else "ref"
        if select_backend not in ("ref", "pallas", "pallas-tpu"):
            raise ValueError(f"unknown select_backend {select_backend!r}: "
                             "expected 'auto', 'ref', 'pallas' or "
                             "'pallas-tpu'")
        if select_backend == "pallas" and on_tpu:
            raise ValueError("select_backend='pallas' interprets the kernel "
                             "and is for hosts without a TPU; use "
                             "'pallas-tpu' (or 'auto') on a TPU")
        self.select_backend = select_backend
        self._k = min(self.cfg.k, max(state.n_devices, 1))
        self._kb = min(max(1, source_block), self._k)
        self._rb = ROW_BLOCK if row_block is None else max(1, row_block)
        self._dense = None
        self._dyn = None
        self._epoch = -1                # state.mutation_epoch at last sync
        self._done = False
        self._terminal_seconds = 0.0    # wall time of empty final chunks
        # moves the device already planned+applied in the carry but the
        # host has not yet emitted: (row, src, dst, tried, skipped,
        # seconds)
        self._stash: list[tuple[int, int, int, int, int, float]] = []
        # deltas observed since the last sync, keyed by epoch; _invalid is
        # set when the stream is unusable (overflow, unstamped delta)
        self._pending: dict[int, ClusterDelta] = {}
        self._invalid = False
        self._absorbed_deltas = 0       # lifetime count (stats/tests)
        # subscribe weakly: the state must not keep a dead planner alive
        ref = weakref.ref(self)

        def _deliver(delta, _ref=ref):
            planner = _ref()
            if planner is None:
                return False            # prune this subscription
            planner._record_delta(delta)
            return True

        state.subscribe(_deliver)

    # -- dense-state lifecycle ----------------------------------------------

    def _round_cap(self, n: int) -> int:
        return max(self._rb, -(-int(n) // self._rb) * self._rb)

    def _fresh_cache(self, n_dev: int):
        """All-invalid legality-cache arrays (cache_dev, cache_ok,
        cache_clean) for the current (k, kb, r_cap) geometry; every slot
        tags device -1, so the first walk of any tile recomputes it."""
        if not self.legality_cache:
            return (jnp.full((1,), -1, jnp.int32),
                    jnp.zeros((1, 1, 1), bool), jnp.zeros((1, 1), bool))
        k_pad = -(-self._k // self._kb) * self._kb
        n_blocks = self._r_cap // self._rb
        return (jnp.full((k_pad,), -1, jnp.int32),
                jnp.zeros((k_pad, self._r_cap, n_dev), bool),
                jnp.zeros((k_pad, n_blocks), bool))

    def _build(self) -> None:
        """Full rebuild of the device mirror from ``self.state``."""
        _obs_registry().inc("batch.rebuilds")
        with _obs.span("batch.build", cat="batch",
                       n_devices=self.state.n_devices,
                       pending=len(self._pending), invalid=self._invalid):
            self._build_mirror()

    def _build_mirror(self) -> None:
        state, cfg = self.state, self.cfg
        self._stash = []
        self._done = False
        self._pending.clear()
        self._invalid = False
        self._dense = None
        self._dyn = None
        self._k = min(cfg.k, max(state.n_devices, 1))
        self._kb = min(self._kb, self._k)
        if not state.acting or not state.pools or state.n_devices < 2:
            self._epoch = state.mutation_epoch
            return
        dense = ShardMirror(state)
        if not dense.sh_pg.size:
            self._epoch = state.mutation_epoch
            return
        self._dense = dense

        # every table in its device dtype on the host, then one upload
        max_rows = int(np.bincount(dense.sh_dev).max())
        self._r_cap = self._round_cap(
            max(self.row_capacity, max_rows)
            if self.row_capacity is not None else max_rows + self.chunk)
        rows_np, nrows_np = _pack_rows(dense.sh_dev, dense.sh_size,
                                       dense.n_dev, self._r_cap)
        i32 = np.int32
        const = (
            dense.cap, dense.dev_class.astype(i32), dense.dev_in,
            dense.dev_domain_arr.astype(i32), dense.sh_size,
            dense.sh_pg.astype(i32), dense.sh_pool.astype(i32),
            dense.sh_class.astype(i32), dense.sh_level.astype(i32),
            dense.sh_slot.astype(i32), dense.sh_sbase.astype(i32),
            dense.sh_scnt.astype(i32), dense.ideal,
        )
        dyn = (
            dense.used, dense.util, np.float64(dense.util_sum),
            np.float64(dense.util_sumsq),
            dense.acting_table(dense.sh_dev), dense.pool_counts,
            legality.dst_count_ok(dense.pool_counts, dense.ideal,
                                  cfg.count_slack),
            rows_np, nrows_np,
            legality.fullest_first(dense.util).astype(i32),
        )
        scalars = (np.float64(cfg.count_slack), np.float64(cfg.headroom),
                   np.float64(cfg.min_variance_delta))
        (self._const, dyn, (self._slack, self._headroom, self._min_dvar),
         pruned) = jax.device_put(
            (const, dyn, scalars, np.zeros(dense.n_dev, bool)))
        # pruned: no bounds yet
        self._dyn = dyn + self._fresh_cache(dense.n_dev) + (pruned,)
        self._epoch = state.mutation_epoch

    @property
    def stale(self) -> bool:
        return self._epoch != self.state.mutation_epoch

    # -- delta observation (the incremental-replanning surface) --------------

    def _record_delta(self, delta: ClusterDelta) -> None:
        if len(self._pending) >= self.PENDING_CAP:
            self._invalid = True
            self._pending.clear()
            return
        existing = self._pending.get(delta.epoch)
        if existing is None:
            self._pending[delta.epoch] = delta
        elif existing != delta:
            # two different claims about one epoch: the stream is
            # untrustworthy — rebuild rather than absorb the wrong one
            self._invalid = True

    def _drop_synced_pending(self) -> None:
        """Forget deltas at or below the synced epoch (they are already
        reflected in the carry — typically our own replayed movements)."""
        self._pending = {e: d for e, d in self._pending.items()
                         if e > self._epoch}

    def _pending_run(self) -> list[ClusterDelta] | None:
        """The contiguous delta run covering (synced epoch, state epoch],
        or None if any mutation went unobserved."""
        run = []
        for epoch in range(self._epoch + 1, self.state.mutation_epoch + 1):
            delta = self._pending.get(epoch)
            if delta is None:
                return None
            run.append(delta)
        return run

    def _class_ids_stable(self) -> bool:
        """Device classes are dense sorted ids in the carry; a new class
        that sorts before an existing one would renumber ``sh_class``."""
        new_id, _ = legality.device_class_ids(self.state.devices)
        return all(new_id.get(c) == i
                   for c, i in self._dense.class_id.items())

    def _absorbable(self, run: list[ClusterDelta] | None) -> bool:
        """Every known delta type is absorbable (full coverage, PR 4):
        pool growth and device out/in are pure host refreshes, device
        adds extend the device axis (unless a new class renumbers the
        dense class ids), foreign movements and pool creates are
        append/update-only re-reads of the mutated state.  A non-empty
        overshoot stash no longer poisons absorption — the stashed
        continuation is discarded and re-derived from the refreshed
        carry.  The conservative rebuild fallback remains for unknown
        delta types, a broken delta stream, and renumbering topology
        changes."""
        if run is None or self._invalid or self._dyn is None:
            return False
        dense = self._dense
        if dense is None:
            return False
        max_pool = max(dense.pool_index, default=-1)
        for delta in run:
            if isinstance(delta, (PoolGrowthDelta, DeviceOutDelta,
                                  MovementDelta)):
                continue
            if isinstance(delta, DeviceAddDelta):
                if not self._class_ids_stable():
                    return False
                continue
            if isinstance(delta, PoolCreateDelta):
                # pools are dense sorted ids in the carry: the new pool
                # (and its PGs / shard rows) must sort after everything
                # already mirrored, and its rule's device classes must
                # already have dense ids
                pool = self.state.pools.get(delta.pool_id)
                if pool is None or delta.pool_id <= max_pool:
                    return False
                if not all(s.device_class is None
                           or s.device_class in dense.class_id
                           for s in pool.rule.steps):
                    return False
                max_pool = delta.pool_id
                continue
            return False        # unknown delta type: conservative fallback
        return True

    def observe(self, delta: ClusterDelta) -> bool:
        """Record one cluster delta; True iff the planner can stay warm.

        Deltas from the bound state arrive automatically through the
        subscription, so calling this is only needed for deltas produced
        elsewhere (it deduplicates by epoch).  Returning False means the
        next :meth:`plan` will rebuild the dense mirror; True means the
        pending deltas will be absorbed into the device carry.
        """
        if getattr(delta, "epoch", -1) < 0:
            self._invalid = True        # unstamped: cannot be ordered
        else:
            self._record_delta(delta)
        if self._epoch < 0 or not self.stale:
            return True                 # nothing warm to invalidate (yet)
        return self._absorbable(self._pending_run())

    def reset(self) -> None:
        """Drop all warm state; the next :meth:`plan` cold-starts."""
        self._epoch = -1
        self._dyn = None
        self._dense = None
        self._stash = []
        self._done = False
        self._pending.clear()
        self._invalid = False

    def _extend_pools(self, created: list[int]) -> None:
        """Append freshly created pools' PGs and shard rows to the host
        mirror's tables, in the exact (sorted pg, slot-major) order a
        cold ShardMirror build walks, so an absorbed carry stays bitwise
        equal to a rebuilt one (guarded by ``_absorbable``: the new pool
        ids sort after everything already mirrored)."""
        state, dense = self.state, self._dense
        lvl_id = {l: i for i, l in enumerate(dense.levels)}
        for pid in sorted(created):
            pool = state.pools[pid]
            dense.pool_index[pid] = len(dense.pool_index)
            dense.n_pools = len(dense.pool_index)
            # per-slot rule geometry from the same shared walk
            # ShardMirror.__init__ uses (legality.rule_slot_steps)
            geometry = legality.rule_slot_steps(pool.rule)
            new = {"pg": [], "pool": [], "level": [], "class": [],
                   "step": [], "slot": [], "sbase": [], "scnt": []}
            for pg in sorted(state.pgs_of_pool[pid]):
                dense.pg_index[pg] = len(dense.pg_index)
                dense.pgs.append(pg)
                for slot in range(pool.size):
                    si, base, scnt, domain, dev_class = geometry[slot]
                    new["pg"].append(dense.pg_index[pg])
                    new["pool"].append(dense.pool_index[pid])
                    new["level"].append(lvl_id[domain])
                    new["class"].append(dense.class_id[dev_class]
                                        if dev_class is not None else -1)
                    new["step"].append(si)
                    new["slot"].append(slot)
                    new["sbase"].append(base)
                    new["scnt"].append(scnt)
            for key, attr in (("pg", "sh_pg"), ("pool", "sh_pool"),
                              ("level", "sh_level"), ("class", "sh_class"),
                              ("step", "sh_step"), ("slot", "sh_slot"),
                              ("sbase", "sh_sbase"), ("scnt", "sh_scnt")):
                setattr(dense, attr,
                        np.concatenate([getattr(dense, attr), new[key]]
                                       ).astype(np.int64))

    def _absorb(self) -> bool:
        """Apply the pending delta run directly to the device carry.

        Full coverage (PR 4): pool growth, device add, device out/in,
        foreign movements and pool creates all absorb; only unknown
        delta types, a broken stream, or id-renumbering topology changes
        rebuild.  Every refreshed array is recomputed with the *same
        host-side expressions* :meth:`_build` uses — the shared legality
        core for ids / criteria / orders, ``state.used()`` /
        ``ideal_shard_count`` for accounting, ``_pack_rows`` for the
        ``(size desc, row asc)`` candidate order — so the absorbed carry
        is bitwise equal to a freshly built one and the continued move
        sequence stays bit-identical to a cold start.

        A non-empty overshoot stash is simply discarded: its moves were
        planned against the pre-delta state and exist *only* in the
        carry (never applied to ``self.state``), so re-deriving the
        structural arrays from the mutated state is the undo.
        """
        run = self._pending_run()
        if not self._absorbable(run):
            return False
        with _obs.span("batch.absorb", cat="batch",
                       deltas=len(run)) as sp:
            structural, kept_bounds = self._absorb_run(run)
            sp.set(structural=structural, kept_bounds=kept_bounds)
        return True

    def _absorb_run(self, run: list[ClusterDelta]) -> tuple[bool, bool]:
        """The body of :meth:`_absorb`; returns whether the run was
        structural and whether the source bounds were kept."""
        state, cfg, dense = self.state, self.cfg, self._dense
        added = [d.device for d in run if isinstance(d, DeviceAddDelta)]
        created = [d.pool_id for d in run if isinstance(d, PoolCreateDelta)]
        grew = any(isinstance(d, PoolGrowthDelta) for d in run)
        # shard assignment / acting-table changes require re-reading the
        # structural arrays from the mutated state; pure growth / add /
        # out runs keep the device-side tables (the hot per-tick path)
        structural = (bool(created) or bool(self._stash)
                      or any(isinstance(d, MovementDelta) for d in run))
        # PR 6: source-bound certificates survive absorption only across
        # a pure foreign-movement run planned with no discarded stash —
        # discarding stashed moves un-applies them from the carry, which
        # would leave certificates claiming facts about a state that
        # never existed.  Every other delta type perturbs certificate
        # inputs wholesale (sizes, ideals, the device axis), so the
        # certificates restart cold there.
        keep_bounds = (self.source_bounds and not self._stash
                       and bool(run)
                       and all(isinstance(d, MovementDelta) for d in run))
        if keep_bounds:
            used_old, util_old, dst_ok_old, pruned_old = (
                np.asarray(a) for a in _fetch(
                    (self._dyn[0], self._dyn[1], self._dyn[6],
                     self._dyn[13]), "absorb"))
        self._stash = []

        # structural extensions first (append-only, per _absorbable)
        if created:
            self._extend_pools(created)
        # per-device legality inputs through the shared LegalityState —
        # the same construction ShardMirror.__init__ uses (append-only
        # device order keeps every existing id, verified by
        # _class_ids_stable; out flips land in dev_in).  Only adds and
        # out-flips can change the device axis, so pure growth /
        # movement runs keep the existing registry and device buffers
        outs = any(isinstance(d, DeviceOutDelta) for d in run)
        if added or outs:
            dense.legality = leg = LegalityState.from_cluster(state)
            dense.class_id = leg.class_id
            dense.dev_class = leg.dev_class
            dense.dev_domain_arr = leg.dev_domain_arr
            dense.n_domains = leg.n_domains
            dense.dev_in = leg.dev_in
            dense.cap = leg.cap
            dev_const = (
                jnp.asarray(dense.cap),
                jnp.asarray(dense.dev_class, jnp.int32),
                jnp.asarray(dense.dev_in),
                jnp.asarray(dense.dev_domain_arr, jnp.int32),
            )
        else:
            dev_const = self._const[:4]
        n_dev = dense.n_dev = state.n_devices
        if added:
            self._k = min(cfg.k, max(n_dev, 1))
            self._kb = min(self._kb, self._k)

        # host-side rebuild-equivalent views of the mutated cluster
        cap = dense.cap
        used = state.used()
        util = used / cap
        pool_ids = sorted(state.pools)
        ideal = np.stack([state.ideal_shard_count(state.pools[p])
                          for p in pool_ids])
        pool_counts = np.stack([state.pool_counts[p] for p in pool_ids]
                               ).astype(np.float64)
        dst_ok = legality.dst_count_ok(pool_counts, ideal, cfg.count_slack)
        sh_size = dense.row_sizes(state)

        if structural:
            # canonical row tables straight from the mutated state, with
            # the same helpers _build uses; foreign movements and the
            # discarded stash both collapse to "re-read the assignment",
            # growth re-sorts implicitly
            sh_dev = dense.row_devices(state)
            max_rows = int(np.bincount(sh_dev, minlength=n_dev).max())
            if max_rows + self.chunk > self._r_cap:
                self._r_cap = self._round_cap(max_rows + self.chunk)
            rows_np, nrows_np = _pack_rows(sh_dev, sh_size, n_dev,
                                           self._r_cap)
            acting = jnp.asarray(dense.acting_table(sh_dev))
            shard_const = (
                jnp.asarray(sh_size.astype(np.float64)),
                jnp.asarray(dense.sh_pg, jnp.int32),
                jnp.asarray(dense.sh_pool, jnp.int32),
                jnp.asarray(dense.sh_class, jnp.int32),
                jnp.asarray(dense.sh_level, jnp.int32),
                jnp.asarray(dense.sh_slot, jnp.int32),
                jnp.asarray(dense.sh_sbase, jnp.int32),
                jnp.asarray(dense.sh_scnt, jnp.int32),
            )
        else:
            # assignment untouched: keep the device-side acting table and
            # per-shard geometry buffers; row tables come back from the
            # device (one sync), extended for adds and re-sorted for
            # growth — the cheap per-tick path
            acting = self._dyn[4]
            rows_np, nrows_np = (np.array(a) for a in
                                 _fetch((self._dyn[7], self._dyn[8]),
                                        "absorb"))
            if added:
                pad_rows = np.full((len(added), rows_np.shape[1]), -1,
                                   np.int32)
                rows_np = np.concatenate([rows_np, pad_rows])
                nrows_np = np.concatenate(
                    [nrows_np, np.zeros(len(added), np.int32)])
            if grew:
                for d in range(n_dev):
                    nd = int(nrows_np[d])
                    order = sorted(rows_np[d, :nd].tolist(),
                                   key=lambda r: (-sh_size[r], r))
                    rows_np[d, :nd] = order
            shard_const = ((jnp.asarray(sh_size.astype(np.float64))
                            if grew else self._const[4]),) \
                + self._const[5:12]

        # surviving source-bound certificates: clear the endpoints and
        # every current holder of each moved PG, then run the same
        # legality-core triggers apply_move uses as a net carry-old vs
        # state-new sweep — the criteria are memoryless, so the net
        # compare per device is exact for the remaining (untouched)
        # certificate holders
        pruned_np = np.zeros(n_dev, bool)
        if keep_bounds and pruned_old.any():
            pruned_np = pruned_old.copy()
            for d in run:
                mv = d.movement
                s_i, d_i = state.idx(mv.src_osd), state.idx(mv.dst_osd)
                pruned_np[s_i] = pruned_np[d_i] = False
                for o in state.acting[mv.pg]:
                    pruned_np[state.idx(o)] = False
            if pruned_np.any():
                iota = np.arange(n_dev)
                crossed = legality.bound_crossed(
                    util_old[:, None], util[:, None], util[None, :],
                    iota[:, None], iota[None, :])
                kill = crossed.any(axis=0)
                flips = dst_ok & ~dst_ok_old
                kill |= (flips.any(axis=1)[:, None]
                         & (pool_counts > 0.0)).any(axis=0)
                largest = rows_np[:, 0]
                maxsz = np.where(largest >= 0,
                                 sh_size[np.maximum(largest, 0)], 0.0)
                lim = legality.capacity_limit(cap, cfg.headroom)
                dropped = used < used_old
                kill |= (dropped[:, None]
                         & legality.bound_capacity_binding(
                             used_old[:, None], lim[:, None],
                             maxsz[None, :])).any(axis=0)
                pruned_np &= ~kill

        dense.used = used
        dense.util = util
        dense.sh_size = sh_size          # Movement sizes read from here
        dense.ideal = ideal
        dense.pool_counts = pool_counts

        self._const = dev_const + shard_const + (jnp.asarray(ideal),)
        self._dyn = (
            jnp.asarray(used), jnp.asarray(util),
            jnp.asarray(float(util.sum()), jnp.float64),
            jnp.asarray(float((util ** 2).sum()), jnp.float64),
            acting, jnp.asarray(pool_counts),
            jnp.asarray(dst_ok), jnp.asarray(rows_np),
            jnp.asarray(nrows_np),
            jnp.asarray(legality.fullest_first(util).astype(np.int32)),
        ) + self._fresh_cache(n_dev) + (jnp.asarray(pruned_np),)
        self._done = False
        self._absorbed_deltas += len(run)
        reg = _obs_registry()
        reg.inc("absorb.runs")
        for d in run:
            reg.inc("absorb.deltas", type=type(d).__name__)
        self._epoch = state.mutation_epoch
        self._drop_synced_pending()
        return bool(structural), bool(keep_bounds)

    # -- planning ------------------------------------------------------------

    def sync(self) -> None:
        """Bring the device carry up to date with the bound state:
        cold-build on first use, absorb an absorbable pending delta run
        into the warm carry, full rebuild as the fallback.  Callers must
        hold ``jax.enable_x64(True)`` (as :meth:`plan` and the fleet
        planner's tick both do)."""
        if self._epoch < 0:
            self._build()
        elif self.stale and not self._absorb():
            self._build()

    def _flush_stats(self, raw_moves, stats_out: dict, snap: dict, *,
                     pruned: int | None = None) -> None:
        """Populate ``stats_out`` for one plan call: the convergence-tail
        instrumentation (same schema as the host-loop engines via
        ``tail_flush``; selection and apply are fused on-device, so the
        whole chunk-amortized move time is attributed to selection) plus
        this engine's registry-counter deltas.  ``pruned`` lets a caller
        that already fetched the pruned-source count (the fleet planner
        batches that fetch across clusters) skip the per-planner sync."""
        acc = tail_stats(stats_out)
        for _row, _src, _dst, tried, skipped, secs in raw_moves:
            tail_record(acc, tried, secs, 0.0)
            acc["bound_hits"] += int(skipped)
        tail_terminal(acc, self._terminal_seconds)
        if pruned is not None:
            acc["pruned"] = int(pruned)
        elif self.source_bounds and self._dyn is not None:
            acc["pruned"] = int(_fetch(jnp.sum(self._dyn[13]), "pruned"))
        tail_flush(acc)
        stats_out["legality_cache"] = self.legality_cache
        stats_out["source_bounds"] = self.source_bounds
        stats_out["pipeline"] = self.pipeline
        self._registry_stats(snap, stats_out)

    def _record_move_classes(self, raw_moves,
                             dst_width: int | None = None) -> None:
        """Per-plan account of the moved rows' rule-step classes and of
        the destination axis the chunk program evaluated for them, from
        array operations over the moves' rows (one registry call per
        class, never one per move):

        * ``batch.moves_by_class{cls=<class>}``: moves by the rule-step
          class of the moved row (``any`` for a step with no class);
        * ``batch.dst_cols``: summed over the moves, the width of the
          destination axis of the row's legality tile in
          :func:`_plan_chunk` — ``eval_static`` and ``eval_cand`` evaluate
          every column of the dispatched carry's device axis for every
          row of a walked tile: ``n_dev``, pads of a sharded carry
          included, or ``dst_width`` where the moves were planned over
          another carry (a fleet lane's bucket);
        * ``batch.dst_cols_live``: summed over the moves, how many of
          those columns :func:`legality.class_ok` admits for the row's
          class — the devices of that class, or every column for a step
          with no class (pad devices hold class -2, which no step names).

        A chunk program that narrows its destination axis has to change
        both definitions with it."""
        dense = self._dense
        dst_width = dst_width or int(self._const[0].shape[0])
        rows = np.fromiter((m[0] for m in raw_moves), np.int64,
                           len(raw_moves))
        cls, counts = np.unique(dense.sh_class[rows], return_counts=True)
        dev_axis = np.full(dst_width, -2, np.int64)
        dev_axis[:dense.n_dev] = dense.dev_class
        name_of = {i: c for c, i in dense.class_id.items()}
        reg = _obs_registry()
        live = 0
        for c, n in zip(cls.tolist(), counts.tolist()):
            reg.inc("batch.moves_by_class", n, cls=name_of.get(c, "any"))
            live += n * int(legality.class_ok(c, dev_axis).sum())
        reg.inc("batch.dst_cols", dst_width * len(rows))
        reg.inc("batch.dst_cols_live", live)

    def _reconcile(self, raw_moves, record_trajectory: bool,
                   record_free_space: bool, dst_width: int | None = None
                   ) -> tuple[list[Movement], list["MoveRecord"]]:
        """Replay the emitted move log through :meth:`ClusterState.apply`
        (which re-validates every source assignment), exactly like
        :func:`repro.core.simulate.simulate` replays movement logs, then
        mark the carry synced to the resulting epoch.  ``dst_width``:
        see :meth:`_record_move_classes`."""
        dense, state = self._dense, self.state
        movements: list[Movement] = []
        records: list[MoveRecord] = []
        with _obs.span("batch.reconcile", cat="batch",
                       moves=len(raw_moves)):
            if raw_moves:
                self._record_move_classes(raw_moves, dst_width)
            for row, src, dst, tried, _skipped, secs in raw_moves:
                pg, slot = dense.pgs[dense.sh_pg[row]], int(dense.sh_slot[row])
                mv = Movement(pg, slot, state.devices[src].id,
                              state.devices[dst].id,
                              float(dense.sh_size[row]))
                state.apply(mv)          # re-validates source assignment
                movements.append(mv)
                if record_trajectory:
                    records.append(MoveRecord(
                        movement=mv,
                        variance_after=state.utilization_variance(),
                        free_space_after=(state.total_pool_free_space()
                                          if record_free_space
                                          else float("nan")),
                        planning_seconds=secs,
                        sources_tried=tried,
                    ))
        self._epoch = state.mutation_epoch
        self._drop_synced_pending()     # our own replayed movements
        # fully synced to the state: any backlog concern (e.g. our own
        # replay overflowing PENDING_CAP on a large plan) is moot —
        # staleness detection is the epoch compare, not this
        self._invalid = False
        return movements, records

    def _registry_stats(self, snap: dict, stats_out: dict) -> None:
        """Per-plan engine signals for ``PlanResult.stats``: deltas of
        this engine's registry counters since plan entry (so the same
        monotonic spine that feeds the trace footer also populates the
        per-call stats — one write path, two read frequencies).
        ``absorbed_deltas`` stays the planner-lifetime count it has
        always been."""
        d = _obs_registry().deltas_since(snap)
        stats_out["rebuilds"] = int(d.get("batch.rebuilds", 0))
        stats_out["host_syncs"] = int(d.get("batch.host_syncs", 0))
        stats_out["jit_recompiles"] = int(d.get("batch.jit_recompiles", 0))
        stats_out["stash_moves"] = int(d.get("batch.stash_moves", 0))
        stats_out["cache_hits"] = int(d.get("batch.cache_hits", 0))
        stats_out["cache_misses"] = int(d.get("batch.cache_misses", 0))
        stats_out["absorbed_deltas"] = self._absorbed_deltas

    def _dispatch(self, telemetry: bool):
        """:meth:`_dispatch_chunk` under a ``batch.dispatch`` span."""
        with _obs.span("batch.dispatch", cat="batch"):
            return self._dispatch_chunk(telemetry)

    def _dispatch_chunk(self, telemetry: bool):
        """Async-dispatch one chunk against the current carry (donating
        the previous carry buffers to the jit) and rebind ``self._dyn``
        to the returned one; the small per-chunk results come back as
        *unfetched* handles so the caller chooses when to block.  The
        sharded engine overrides this with the mesh dispatch."""
        jit0 = _plan_chunk._cache_size()
        self._dyn, done, overflow, tel, moves, steps, nmax = _plan_chunk(
            self._dyn, self._const, self._slack, self._headroom,
            self._min_dvar, k=self._k, kb=self._kb, rb=self._rb,
            m=self.chunk, backend=self.select_backend,
            cached=self.legality_cache, bounds=self.source_bounds,
            telemetry=telemetry)
        recompiles = _plan_chunk._cache_size() - jit0
        if recompiles:
            _obs_registry().inc("batch.jit_recompiles", recompiles)
        return (moves, steps, done, overflow, tel, nmax), recompiles

    def _record_chunk_tel(self, reg, tel_np) -> None:
        """Fold one fetched device-telemetry vector into the registry
        (the sharded engine overrides this to keep per-shard counters)."""
        reg.inc("batch.tiles_walked", int(tel_np[0]))
        reg.inc("batch.cand_tiles", int(tel_np[1]))
        if self.legality_cache:
            reg.inc("batch.cache_hits", int(tel_np[2]))
            reg.inc("batch.cache_misses", int(tel_np[3]))

    def _chunk_loop(self, budget: int
                    ) -> list[tuple[int, int, int, int, int, float]]:
        """Run chunks until ``budget`` raw moves are on hand (stashing any
        overshoot), the device reports convergence, or a re-pad is needed.
        ``self._terminal_seconds`` collects the wall time of chunks that
        emit no moves (the terminal every-source-fruitless scan).  The
        walk's iterations come back with each chunk's moves and are
        added to ``batch.walk_steps`` once per call.

        With ``pipeline`` on (the default), chunk *i+1* is async-dispatched
        as soon as chunk *i*'s fetched scalars prove another full chunk is
        needed (not done, not overflowing, budget and row capacity both
        leave room) — so the device computes chunk *i+1* while the host
        drains chunk *i*'s moves.  The gate means a pipelined dispatch is
        never wasted or semantically new: it is exactly the dispatch the
        next loop iteration would have issued, moved before the host-side
        processing.  The emitted sequence is untouched (property-tested)."""
        self._terminal_seconds = 0.0
        raw: list[tuple[int, int, int, int, int, float]] = []
        take = min(len(self._stash), budget)
        raw.extend(self._stash[:take])
        del self._stash[:take]
        reg = _obs_registry()
        if take:
            reg.inc("batch.stash_replayed", take)
        # static jit flag: the telemetry carry compiles in only under a
        # tracer started with device_counters=True (toggling it costs one
        # recompile, counted like any other); the default variant is the
        # exact pre-obs computation, traced or not, keeping plan
        # bit-identity trivially
        telemetry = _obs.device_counters()
        pending = None      # (handles, recompiles) of chunk i+1
        walk_steps = 0
        while len(raw) < budget and not self._done:
            with _obs.span("batch.chunk", cat="batch") as sp:
                t0 = time.perf_counter()
                if pending is None:
                    handles, recompiles = self._dispatch(telemetry)
                    overlapped = False
                else:
                    handles, recompiles = pending
                    pending = None
                    overlapped = True
                moves_np, steps_np, done, overflow, tel_np, nmax = _fetch(
                    handles, "chunk")
                dt = time.perf_counter() - t0
                done, overflow, nmax = bool(done), bool(overflow), int(nmax)
                walk_steps += int(steps_np.sum())
                emitted = moves_np[moves_np[:, 0] >= 0]
                if (self.pipeline and not done and not overflow
                        and len(raw) + len(emitted) < budget
                        and nmax + self.chunk <= self._r_cap):
                    # every break / re-pad condition below is excluded, so
                    # the next loop iteration will run a full chunk: issue
                    # its dispatch now and let the device overlap it with
                    # the host-side processing of this one
                    pending = self._dispatch(telemetry)
                    reg.inc("batch.chunks_overlapped")
                if telemetry:
                    self._record_chunk_tel(reg, tel_np)
                if self.legality_cache:
                    # a clean cache survives every applied move only
                    # because apply_move column-repairs it in place —
                    # one repair per emitted move (host-side knowledge,
                    # needs no device counter)
                    reg.inc("batch.cache_repairs", len(emitted))
                sp.set(emitted=len(emitted), done=done, overflow=overflow,
                       recompiles=recompiles, overlapped=overlapped)
            if len(emitted) == 0 and done and not overflow:
                self._terminal_seconds += dt    # the fruitless final scan
                                                # (not an overflow re-pad)
            per_s = dt / max(len(emitted), 1)
            new = [(*m, per_s) for m in map(tuple, emitted.tolist())]
            raw.extend(new)
            if len(raw) >= budget:
                # device ran past the budget: the overshoot is already
                # applied in the carry — hold it for the next call so the
                # emitted stream stays the cold-start sequence
                over = len(raw) - budget
                if over:
                    reg.inc("batch.stash_moves", over)
                    _obs.point("batch.stash", cat="batch", moves=over)
                self._stash = raw[budget:] + self._stash
                del raw[budget:]
                if done:
                    self._done = True
                break
            if done:
                self._done = True
                break
            if overflow or nmax + self.chunk > self._r_cap:
                # re-pad the per-device row table and resume (one extra
                # sync; triggers one recompile for the new row_capacity);
                # the legality cache is shape-bound to r_cap, so it
                # restarts cold — the source bounds are not (their
                # certificates say nothing about row geometry) and
                # survive the re-pad.  The pipeline gate above excludes
                # both re-pad triggers, so no dispatched chunk is in
                # flight against the stale geometry.  Sized from the
                # carry's own width, which for the sharded engine is the
                # mesh-padded device axis, not ``state.n_devices``.
                reg.inc("batch.repads")
                _obs.point("batch.repad", cat="batch",
                           r_cap=self._r_cap)
                rows_np, nrows_np = _fetch((self._dyn[7], self._dyn[8]),
                                           "repad")
                n_carry = rows_np.shape[0]
                self._r_cap = self._round_cap(int(nrows_np.max())
                                              + self.chunk)
                packed = np.full((n_carry, self._r_cap), -1, np.int32)
                for d in range(n_carry):
                    nd = int(nrows_np[d])
                    packed[d, :nd] = rows_np[d, :nd]
                self._dyn = self._dyn[:7] + (jnp.asarray(packed),) \
                    + self._dyn[8:10] + self._fresh_cache(n_carry) \
                    + (self._dyn[13],)
        if walk_steps:
            reg.inc("batch.walk_steps", walk_steps)
        return raw

    def plan(self, max_moves: int | None = None,
             record_trajectory: bool = False,
             record_free_space: bool = True,
             stats_out: dict | None = None):
        """Plan up to ``max_moves`` (default ``cfg.max_moves``) further
        moves, applying them to the bound state; returns (movements,
        records) exactly like :func:`repro.core.equilibrium.balance`.

        Reuses the device carry from the previous call when the state is
        unchanged; absorbs any absorbable pending delta run into it, and
        rebuilds (one counted rebuild) only as the fallback.  When
        ``stats_out`` is given it receives the convergence-tail
        instrumentation: a ``sources_tried`` histogram and the share of
        planning wall time spent on moves with ``sources_tried > 1``
        (chunk-amortized, since selection and apply are fused on-device).
        """
        budget = self.cfg.max_moves if max_moves is None else max_moves
        snap = (_obs_registry().snapshot() if stats_out is not None
                else None)
        with jax.enable_x64(True):
            self.sync()
            if self._dyn is None or budget <= 0:
                if stats_out is not None:
                    tail_flush(tail_stats(stats_out))
                    stats_out["legality_cache"] = self.legality_cache
                    stats_out["source_bounds"] = self.source_bounds
                    stats_out["pipeline"] = self.pipeline
                    self._registry_stats(snap, stats_out)
                return [], []
            raw_moves = self._chunk_loop(budget)
            if stats_out is not None:
                self._flush_stats(raw_moves, stats_out, snap)
            return self._reconcile(raw_moves, record_trajectory,
                                   record_free_space)


def _balance_batch(state: ClusterState, cfg: EquilibriumConfig | None = None,
                   record_trajectory: bool = False,
                   record_free_space: bool = True, chunk: int = 64,
                   source_block: int = 1, row_block: int | None = None,
                   row_capacity: int | None = None,
                   select_backend: str = "auto",
                   legality_cache: bool = False,
                   source_bounds: bool = True,
                   stats_out: dict | None = None):
    """Device-resident drop-in for the faithful §3.1 planner:
    identical move sequences, one host sync per ``chunk`` moves.
    Library-internal engine entry; the public API is
    ``repro.core.planner.create_planner("equilibrium_batch")``.

    ``source_block`` × ``row_block`` is the tile of the batched
    ``(k, R_max, n_dev)`` legality tensor evaluated per inner iteration
    (``source_block=cfg.k`` + ``row_block >= R_max`` evaluates the whole
    tensor at once); without ``row_block`` each step evaluates
    :data:`ROW_BLOCK` rows of one source.  ``row_capacity`` pads the
    per-device row table (default: max shards/device + ``chunk``, the
    no-overflow invariant).  ``select_backend``: "auto" (the compiled
    Pallas kernel on a TPU, the jnp reference elsewhere), "ref",
    "pallas" (the kernel interpreted; hosts without a TPU only), or
    "pallas-tpu" (the kernel compiled with Mosaic).  ``legality_cache``
    opts into the cross-move candidate-mask cache (full tile masks kept
    in the carry, two columns repaired per move): off by default because
    at the CPU tile sizes the per-move repair costs more than the fresh
    candidate evaluation it saves — it exists for accelerator geometries, and stays
    property-tested bit-identical either way.  ``source_bounds`` (on by
    default) keeps per-source no-candidate certificates plus the pruned
    stable partition of the source walk; both opt-outs are benchmarked
    in benchmarks/bench_planner.py tail rows.

    Trajectory records amortize each chunk's wall-time over its emitted
    moves, so the first chunk's ``planning_seconds`` include the one-time
    jit compile (and a re-pad's recompile); steady-state timing wants a
    warmed engine — see benchmarks/bench_planner.py.

    One-shot wrapper over :class:`BatchPlanner`; hold a planner instance
    instead to plan incrementally across cluster ticks without rebuilding
    the dense state (the scenario engine's warm-start path).
    """
    cfg = cfg or EquilibriumConfig()
    planner = BatchPlanner(state, cfg, chunk=chunk, source_block=source_block,
                           row_block=row_block, row_capacity=row_capacity,
                           select_backend=select_backend,
                           legality_cache=legality_cache,
                           source_bounds=source_bounds)
    return planner.plan(record_trajectory=record_trajectory,
                        record_free_space=record_free_space,
                        stats_out=stats_out)


def balance_batch(state: ClusterState, cfg: EquilibriumConfig | None = None,
                  record_trajectory: bool = False,
                  record_free_space: bool = True, chunk: int = 64,
                  source_block: int = 1, row_block: int | None = None,
                  row_capacity: int | None = None,
                  select_backend: str = "auto",
                  legality_cache: bool = False,
                  source_bounds: bool = True):
    """Deprecated: use ``create_planner("equilibrium_batch")`` from
    :mod:`repro.core.planner`, or hold a :class:`BatchPlanner` directly
    for warm-started incremental planning."""
    from ._compat import warn_deprecated
    warn_deprecated("repro.core.equilibrium_batch.balance_batch",
                    'create_planner("equilibrium_batch")')
    return _balance_batch(state, cfg, record_trajectory=record_trajectory,
                          record_free_space=record_free_space, chunk=chunk,
                          source_block=source_block, row_block=row_block,
                          row_capacity=row_capacity,
                          select_backend=select_backend,
                          legality_cache=legality_cache,
                          source_bounds=source_bounds)
