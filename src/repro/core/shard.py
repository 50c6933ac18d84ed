"""Sharded batch engine: the legality tiles ``shard_map``-ped over an
``n_dev``-partitioned device mesh.

The batch engine's working set is dominated by the destination axis: the
``(source_block, row_block, n_dev)`` legality/variance tiles, the
``(n_pools, n_dev)`` destination-count criterion, the ``(n_dev, r_cap)``
row tables and the per-source certificate vector all scale with the
device count, which is exactly the axis that grows 10k–100k-OSD
clusters past one accelerator's memory.  This module splits that axis
into contiguous ascending blocks, one per mesh shard, and runs the
*same* chunk step (:func:`_shard_chunk_impl` mirrors
``equilibrium_batch._plan_chunk_impl`` expression for expression) under
:func:`jax.shard_map`:

* **sharded**: the device axis (``PartitionSpec("dev")``) of the row
  tables ``rows_on``/``nrows``, the ``dst_ok`` / ``pool_counts`` /
  ``ideal`` criterion columns and the ``pruned`` certificate vector —
  plus every destination-axis slice of the legality tiles, which are
  never materialized globally; the *row axis* of the eight per-row
  shard-registry arrays (``sh_size`` … ``sh_scnt``), block-sharded by
  global row id; and the *pg axis* of the acting table.  Together these
  are everything in the carry that scales with cluster size;
* **replicated** (``P()``): the O(n_dev) bookkeeping vectors
  (``used``/``util``/``order`` and the device registry constants) and
  the scalar moments — each shard updates them with bitwise-identical
  expressions from replicated inputs, so they stay replicated without
  ``check_vma`` (which ``shard_map`` cannot verify through the
  collectives here anyway).

Cross-shard communication happens in exactly three places, and the
*combine math* for all three lives in the legality core
(:mod:`repro.core.legality`, "Cross-shard reductions"), next to the
serial expressions it must agree with:

1. owner gathers — a block-sharded value at a global index (a device's
   carry entry, a row's registry record, a pg's acting set, a pool
   count at a source device) is reconstructed with a one-owner ``psum``
   (``legality.shard_gather_contrib`` / ``shard_gather_finish``; the
   sum has exactly one non-neutral term, so floats survive exactly);
2. the certificate predicate — per-tile any-candidate is the psum-OR of
   the local bits (``legality.shard_any``), so a source is pruned only
   when *no shard anywhere* holds a candidate;
3. the winner rule — each shard's local masked select (first-occurrence
   argmin, i.e. the lexicographic (util, index) minimum within its
   block) is ``all_gather``-ed and folded with
   ``legality.shard_winner_better``, which reproduces the serial
   emptiest-first winner bit-for-bit (ties fall to the lower global
   index because blocks are contiguous and ascending).

The device axis is padded to a multiple of the mesh size with the fleet
pack's neutral device (capacity 1, util 0, out, classless, no rows):
pads sort behind every real device in the maintained fullest-first
order, can never be candidates (``dev_in`` is False) and contribute
zeros to every reduction, so the padded serial sequence — and therefore
the sharded one — is bit-identical to the natural-width sequence
(property-tested in tests/test_shard.py at mesh sizes 1/2/4, uneven
padding included).

On CPU the mesh is forced with
``XLA_FLAGS=--xla_force_host_platform_device_count=N``; the win to
measure there is per-device peak memory (~1/N on the sharded arrays —
see :func:`chunk_memory_stats` and the ``peak_bytes_per_device`` bench
fields), the compute win arrives with real accelerator meshes.
"""

from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from . import legality
from .cluster import ClusterState
from .equilibrium import EquilibriumConfig
from .equilibrium_batch import (BatchPlanner, _select_rows, _shift_insert,
                                _shift_remove, _plan_chunk)
from .planner import BatchEquilibriumPlanner, register_planner
from ..kernels.select_move import compact_parked
from ..obs import registry as _obs_registry

__all__ = ["ShardedBatchPlanner", "chunk_memory_stats"]


def _shard_chunk_impl(dyn, const, slack, headroom, min_dvar, n_real, *,
                      k, kb, rb, m, backend, bounds, telemetry, axis,
                      n_shards):
    """Per-shard body of the sharded chunk: ``_plan_chunk_impl`` with the
    destination axis local to the shard and the three cross-shard
    reductions spliced in.  Everything that is not a destination-axis
    slice or an owner scatter is computed redundantly (and identically)
    on every shard — that redundancy is what keeps the replicated carry
    elements replicated under ``check_vma=False``.

    ``dyn``/``const`` have the ``_plan_chunk_impl`` layout; the sharded
    elements arrive as this shard's local block — the device axis of
    ``dst_ok``/``pool_counts``/``ideal``/``rows_on``/``nrows``/
    ``pruned``, the row axis of the eight registry arrays, the pg axis
    of ``acting``.  The legality cache is unsupported
    (``ShardedBatchPlanner`` refuses it at construction), so the cache
    slots carry the engine's (1,)-shaped placeholders.

    ``tel`` widens to per-shard rows: ``[tiles_walked, global cand
    tiles, *local* cand tiles, local winner count]`` — the first two are
    replicated (every shard walks the tiles in lockstep), the last two
    are this shard's share of the load, the skew signal
    ``tools/tracestat.py --shards`` tabulates.
    ``steps``, the walk's iterations per scan step, is replicated like
    the serial engine's.
    """
    (cap, dev_class, dev_in, dev_domain, sh_size, sh_pg, sh_pool,
     sh_class, sh_level, sh_slot, sh_sbase, sh_scnt, ideal) = const
    n_dev = cap.shape[0]                # mesh-padded global device count
    n_local = dyn[7].shape[0]           # this shard's device-block width
    rows_local = sh_size.shape[0]       # this shard's row-registry block
    n_pg_local = dyn[4].shape[0]        # this shard's acting-table block
    n_slots = dyn[4].shape[1]
    r_cap = dyn[7].shape[1]
    n_f = n_real                        # true device count (the variance n)
    n_sb = -(-k // kb)
    k_pad = n_sb * kb
    dev_iota = jnp.arange(n_dev, dtype=jnp.int32)
    shard = lax.axis_index(axis)
    base = shard * n_local
    rbase = shard * rows_local
    pgbase = shard * n_pg_local
    giota = base + jnp.arange(n_local, dtype=jnp.int32)
    cap_lim = legality.capacity_limit(cap, headroom)  # loop-invariant

    def dslice(a):
        """This shard's destination-axis block of a replicated per-device
        vector (the tiles' destination axis is never materialized
        globally)."""
        return lax.dynamic_slice_in_dim(a, base, n_local)

    i32 = jnp.int32

    def gather_at(values_local, idx, owns, blk_base, neutral=0):
        """Owner gather: a block-sharded array's values at global indices
        ``idx`` via the legality core's one-owner psum (``blk_base`` is
        this shard's offset on the sharded axis)."""
        safe = jnp.where(owns, idx - blk_base, 0)
        picked = values_local[safe]
        if picked.ndim > owns.ndim:
            owns = owns.reshape(owns.shape + (1,) * (picked.ndim
                                                     - owns.ndim))
        contrib = legality.shard_gather_contrib(picked, owns.astype(i32),
                                                neutral)
        return legality.shard_gather_finish(lax.psum(contrib, axis),
                                            neutral)

    def reg_at(values_local, r, neutral=0):
        """Row-registry gather: the registry arrays are block-sharded on
        the row axis, so a (tile of) global row id(s) is resolved by its
        owner shard and psum-broadcast."""
        return gather_at(values_local, r,
                         legality.shard_owns(r, rbase, rows_local),
                         rbase, neutral)

    def pool_at(values_local, pool, dev, neutral=0.0):
        """Gather from a ``(n_pools, n_dev)`` array partitioned on the
        device axis (``pool_counts`` / ``ideal``) at pool/device index
        pairs, with ``dev`` global."""
        owns = legality.shard_owns(dev, base, n_local)
        safe = jnp.where(owns, dev - base, 0)
        picked = values_local[pool, safe]
        contrib = legality.shard_gather_contrib(picked, owns.astype(i32),
                                                neutral)
        return legality.shard_gather_finish(lax.psum(contrib, axis),
                                            neutral)

    cap_lim_l = dslice(cap_lim)
    cap_l = dslice(cap)
    dev_class_l = dslice(dev_class)
    dev_in_l = dslice(dev_in)
    dev_domain_l = lax.dynamic_slice_in_dim(dev_domain, base, n_local,
                                            axis=1)

    def select_one(dyn, active, tel):
        """One §3.1 planning step — the serial walk with local tiles and
        the cross-shard winner combine."""
        used, util, us, usq, acting, pool_counts, dst_ok, \
            rows_on, nrows, order, c_dev, c_ok, c_clean, pruned = dyn
        used_l = dslice(used)
        util_l = dslice(util)
        order_k = order[:k]         # maintained == argsort(-util, stable)
        if bounds:
            owns_k = legality.shard_owns(order_k, base, n_local)
            pr_k = legality.shard_any(
                gather_at(pruned.astype(i32), order_k, owns_k, base))
            src_order, n_avail = compact_parked(order_k, pr_k)
        else:
            src_order, n_avail = order_k, jnp.int32(k)
        if k_pad > k:   # pad to a source-block multiple; masked from wins
            src_order = jnp.pad(src_order, (0, k_pad - k))
        # the walked sources' row lists live on their owner shards:
        # gather once per step, exactly like the serial engine's
        # rows_on[src_order] (pad entries gather device 0's rows and are
        # masked by in_avail, as in the serial engine)
        owns_s = legality.shard_owns(src_order, base, n_local)
        rows_k = gather_at(rows_on, src_order, owns_s, base, -1)
        n_rows_src = gather_at(nrows, src_order, owns_s, base)
        n_rows_k = jnp.where(jnp.arange(k_pad) < n_avail, n_rows_src, 0)

        def eval_static(sb, c):
            blk = lax.dynamic_slice(rows_k, (sb * kb, c * rb), (kb, rb))
            r = jnp.clip(blk, 0)
            pg = reg_at(sh_pg, r)
            lvl = reg_at(sh_level, r)
            slot = reg_at(sh_slot, r)
            sbase = reg_at(sh_sbase, r)
            scnt = reg_at(sh_scnt, r)
            dom = jnp.broadcast_to(dev_domain_l[0][None, None, :],
                                   (kb, rb, n_local))
            for l in range(1, dev_domain.shape[0]):
                dom = jnp.where((lvl == l)[..., None], dev_domain_l[l], dom)
            acting_t = gather_at(                                # (kb, rb, S)
                acting, pg, legality.shard_owns(pg, pgbase, n_pg_local),
                pgbase, -1)
            bad = jnp.zeros((kb, rb, n_local), bool)
            for j in range(n_slots):
                a_j = acting_t[..., j]                           # (kb, rb)
                in_step = (j >= sbase) & (j < sbase + scnt) & (j != slot)
                peer_dom = dev_domain[lvl, jnp.clip(a_j, 0)]
                bad |= a_j[..., None] == giota                   # member
                bad |= in_step[..., None] & (dom == peer_dom[..., None])
            cls = reg_at(sh_class, r)
            return legality.class_ok(cls[..., None],
                                     dev_class_l[None, None, :]) & ~bad

        def eval_cand(sb, c):
            blk = lax.dynamic_slice(rows_k, (sb * kb, c * rb), (kb, rb))
            src_b = lax.dynamic_slice_in_dim(src_order, sb * kb, kb)
            r = jnp.clip(blk, 0)
            size = jnp.where(blk >= 0, reg_at(sh_size, r, 0.0), 0.0)
            real = size > 0.0
            pool = reg_at(sh_pool, r)
            cap_ok = legality.capacity_ok(used_l[None, None, :], cap_lim_l,
                                          size[..., None])
            crit = dst_ok[pool]                              # (kb, rb, local)
            cnt_s = pool_at(pool_counts, pool, src_b[:, None])   # (kb, rb)
            idl_s = pool_at(ideal, pool, src_b[:, None])
            src_ok = legality.src_count_ok(cnt_s, idl_s, slack)
            u_s = util[src_b][:, None, None]
            not_self = giota[None, None, :] != src_b[:, None, None]
            before_src = legality.before_source(
                util_l[None, None, :], u_s, giota[None, None, :],
                src_b[:, None, None])
            return (eval_static(sb, c) & cap_ok & crit
                    & (real & src_ok)[..., None]
                    & not_self & dev_in_l[None, None, :] & before_src)

        def eval_var(sb, c):
            blk = lax.dynamic_slice(rows_k, (sb * kb, c * rb), (kb, rb))
            src_b = lax.dynamic_slice_in_dim(src_order, sb * kb, kb)
            r = jnp.clip(blk, 0)
            size = jnp.where(blk >= 0, reg_at(sh_size, r, 0.0), 0.0)
            u_s = util[src_b][:, None, None]
            return legality.variance_improves(
                used[src_b][:, None, None], used_l[None, None, :],
                cap[src_b][:, None, None], cap_l[None, None, :],
                u_s, util_l[None, None, :], size[..., None],
                us, usq, n_f, min_dvar)

        def body(carry):
            (sb, c, found_row, found_dst, win_j, win_row, win_dst, done,
             marg, pruned, tel, steps) = carry
            src_b = lax.dynamic_slice_in_dim(src_order, sb * kb, kb)
            cand = eval_cand(sb, c)                   # (kb, rb, n_local)
            any_local = jnp.any(cand, axis=(1, 2))    # this shard's share
            # the certificate predicate needs every shard's candidates
            any_rows = legality.shard_any(
                lax.psum(any_local.astype(i32), axis))           # (kb,)
            steps = steps + 1
            if telemetry:
                tel = tel.at[1].add(jnp.any(any_rows).astype(i32))
                tel = tel.at[2].add(jnp.any(any_local).astype(i32))
            # dead-tile short-circuit on the *global* any bit — replicated,
            # so every shard takes the same branch and the all_gather
            # below stays outside the cond
            anyv_l, dst_l = lax.cond(
                jnp.any(any_rows),
                lambda t: _select_rows(
                    (t & eval_var(sb, c)).reshape(kb * rb, n_local),
                    util_l, backend),
                lambda t: (jnp.zeros((kb * rb,), bool),
                           jnp.zeros((kb * rb,), jnp.int32)),
                cand)
            # cross-shard winner combine: fold the shard-local winners in
            # ascending shard order with the legality core's lexicographic
            # (util, global index) predicate — bit-identical to the serial
            # first-occurrence argmin over the full destination axis
            util_sel = util_l[dst_l]
            ga = lax.all_gather(anyv_l, axis)          # (n_shards, kb*rb)
            gu = lax.all_gather(util_sel, axis)
            gd = lax.all_gather(base.astype(jnp.int32) + dst_l, axis)
            anyv, usel, dstw = ga[0], gu[0], gd[0]
            for s in range(1, n_shards):
                better = legality.shard_winner_better(
                    ga[s], gu[s], gd[s], anyv, usel, dstw)
                usel = jnp.where(better, gu[s], usel)
                dstw = jnp.where(better, gd[s], dstw)
                anyv = anyv | ga[s]
            anyv = anyv.reshape(kb, rb)
            dst = dstw.reshape(kb, rb)
            first_i = jnp.argmax(anyv, axis=1)
            has = jnp.take_along_axis(anyv, first_i[:, None], 1)[:, 0]
            tile_dst = jnp.take_along_axis(dst, first_i[:, None], 1)[:, 0]
            idxb = jnp.arange(kb, dtype=jnp.int32)
            in_avail = sb * kb + idxb < n_avail
            has &= in_avail
            newly = has & (found_row < 0)
            found_row = jnp.where(newly, (c * rb + first_i).astype(jnp.int32),
                                  found_row)
            found_dst = jnp.where(newly, tile_dst.astype(jnp.int32),
                                  found_dst)
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
            if telemetry:
                tel = tel.at[3].add((decided & legality.shard_owns(
                    found_dst[jb], base, n_local)).astype(i32))
            if bounds:
                # certificates: `marg` accumulates the *global* any bit,
                # so a source fruitless here but live on another shard is
                # never pruned; the scatter is owner-local (non-owned and
                # not-prunable targets both map to the drop sentinel)
                marg = marg | any_rows
                scanned = (decided | exhausted) & ~found & ~unres
                prunable = scanned & ~marg & in_avail
                owns_t = prunable & legality.shard_owns(src_b, base, n_local)
                tgt = jnp.where(owns_t, src_b - base, n_local)
                pruned = pruned.at[tgt].set(True, mode="drop")
            next_sb = jnp.where(exhausted, sb + 1, sb)
            next_c = jnp.where(exhausted, 0, c + 1)
            done = decided | (exhausted & ((sb + 1) * kb >= n_avail))
            reset = jnp.full((kb,), -1, jnp.int32)
            found_row = jnp.where(exhausted, reset, found_row)
            found_dst = jnp.where(exhausted, 0, found_dst)
            marg = jnp.where(exhausted, False, marg)
            return (next_sb, next_c, found_row, found_dst,
                    win_j, win_row, win_dst, done, marg, pruned, tel, steps)

        def cond(carry):
            return active & ~carry[7]

        init = (jnp.int32(0), jnp.int32(0), jnp.full((kb,), -1, jnp.int32),
                jnp.zeros((kb,), jnp.int32), jnp.int32(-1), jnp.int32(-1),
                jnp.int32(0), jnp.bool_(False), jnp.zeros((kb,), bool),
                pruned, tel, jnp.int32(0))
        out = lax.while_loop(cond, body, init)
        win_j, win_row, win_dst = out[4], out[5], out[6]
        dyn = dyn[:13] + (out[9],)
        tel, steps = out[10], out[11]
        if telemetry:                   # tiles walked = the walk's steps
            tel = tel.at[0].add(steps)
        found = win_j >= 0
        jw = jnp.clip(win_j, 0, k_pad - 1)
        win_dev = src_order[jw]
        if bounds:
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
        """Verbatim serial re-sort — `order`/`util` are replicated, so
        every shard computes the identical new order."""
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
        """The serial ``apply_move`` with owner-local scatters for the
        sharded carry elements and owner gathers where a per-device value
        is needed at a global index.  Replicated elements are updated
        with the serial expressions verbatim."""
        used, util, us, usq, acting, pool_counts, dst_ok, \
            rows_on, nrows, order, c_dev, c_ok, c_clean, pruned = dyn
        okf = ok.astype(jnp.float64)
        oki = ok.astype(jnp.int32)
        row = jnp.where(ok, row, 0)
        size = reg_at(sh_size, row, 0.0)
        pgi = reg_at(sh_pg, row)
        pool = reg_at(sh_pool, row)
        slot = reg_at(sh_slot, row)
        both = jnp.stack([src, dst])
        owns_b = legality.shard_owns(both, base, n_local)
        lboth = jnp.where(owns_b, both - base, n_local)   # drop sentinel
        owns_src = legality.shard_owns(src, base, n_local)
        lsrc = jnp.where(owns_src, src - base, 0)
        owns_pg = legality.shard_owns(pgi, pgbase, n_pg_local)
        if bounds:
            util_src_before = util[src]
            used_src_before = used[src]
            dok_src_before = legality.shard_any(lax.psum(
                (dst_ok[pool, lsrc] & owns_src).astype(i32), axis))
        lpg = jnp.where(owns_pg & ok, pgi - pgbase, n_pg_local)
        acting = acting.at[lpg, slot].set(dst, mode="drop")
        pool_counts = pool_counts.at[pool, lboth].add(
            jnp.stack([-okf, okf]), mode="drop")
        c2 = pool_at(pool_counts, pool, both)
        i2 = pool_at(ideal, pool, both)
        ok2 = legality.dst_count_ok(c2, i2, slack)
        cur = dst_ok[pool, jnp.clip(lboth, 0, n_local - 1)]
        dst_ok = dst_ok.at[pool, lboth].set(jnp.where(ok, ok2, cur),
                                            mode="drop")
        # both endpoints' row lists, gathered from their owner shards
        rows_b = gather_at(rows_on, both, owns_b, base, -1)   # (2, r_cap)
        src_list, dst_list = rows_b[0], rows_b[1]
        pos_s = jnp.argmax(src_list == row).astype(jnp.int32)
        removed = _shift_remove(src_list, pos_s, jnp.int32(-1))
        dsz = jnp.where(dst_list >= 0,
                        reg_at(sh_size, jnp.clip(dst_list, 0), 0.0),
                        -jnp.inf)
        before = (dst_list >= 0) & ((dsz > size)
                                    | ((dsz == size) & (dst_list < row)))
        pos_d = jnp.sum(before).astype(jnp.int32)
        inserted = _shift_insert(dst_list, pos_d, row)
        rows_on = rows_on.at[lboth].set(
            jnp.stack([jnp.where(ok, removed, src_list),
                       jnp.where(ok, inserted, dst_list)]), mode="drop")
        nrows = nrows.at[lboth].add(jnp.stack([-oki, oki]), mode="drop")
        used = used.at[both].add(jnp.stack([-size * okf, size * okf]))
        for i in (src, dst):                  # source first, like apply_row
            u_new = used[i] / cap[i]
            us = us + (u_new - util[i])
            usq = usq + (u_new ** 2 - util[i] ** 2)
            util = util.at[i].set(u_new)
        order = jnp.where(ok, reorder(order, util, src, dst), order)
        if bounds:
            # surgical certificate invalidation over this shard's block
            # of the pruned vector — same trigger set as the serial
            # engine, evaluated at local destination indices
            util_l = dslice(util)
            acting_pg = gather_at(acting, pgi, owns_pg,      # (n_slots,)
                                  pgbase, -1)
            holder = jnp.any(acting_pg[None, :] == giota[:, None],
                             axis=1)
            touch = (giota == src) | (giota == dst) | holder
            crossed = legality.bound_crossed(util_src_before, util[src],
                                             util_l, src, giota)
            dok_src_after = legality.shard_any(lax.psum(
                (dst_ok[pool, lsrc] & owns_src).astype(i32), axis))
            flip = legality.count_flip_enables(dok_src_before,
                                               dok_src_after)
            holds_pool = pool_counts[pool] > 0.0          # local block
            # every shard needs the head-row sizes of *its own* device
            # block, and the registry rows live on arbitrary shards:
            # all_gather the queries, resolve them all, take our slice
            largest = rows_on[:, 0]
            largest_all = lax.all_gather(largest, axis)   # (shards, local)
            sz_all = reg_at(sh_size, jnp.clip(largest_all, 0), 0.0)
            sz_mine = lax.dynamic_slice_in_dim(sz_all, shard, 1)[0]
            maxsz = jnp.where(largest >= 0, sz_mine, 0.0)
            bind = legality.bound_capacity_binding(used_src_before,
                                                   cap_lim[src], maxsz)
            inval = touch | crossed | (flip & holds_pool) | bind
            pruned = jnp.where(ok, pruned & ~inval, pruned)
        return (used, util, us, usq, acting, pool_counts, dst_ok,
                rows_on, nrows, order, c_dev, c_ok, c_clean, pruned)

    def step(carry, _):
        dyn, done, overflow, tel = carry
        active = ~(done | overflow)
        found, row, src, dst, tried, skipped, dyn, tel, steps = \
            select_one(dyn, active, tel)
        owns_d = legality.shard_owns(dst, base, n_local)
        nr_dst = gather_at(dyn[8], dst, owns_d, base)
        ovf = found & (nr_dst >= r_cap)
        ok = active & found & ~ovf
        dyn = apply_move(dyn, ok, row, src, dst)
        emit = jnp.where(ok, jnp.stack([row, src, dst, tried, skipped]),
                         jnp.full((5,), -1, jnp.int32))
        done = done | (active & ~found)
        overflow = overflow | ovf
        return (dyn, done, overflow, tel), (emit, steps)

    carry0 = (dyn, jnp.bool_(False), jnp.bool_(False),
              jnp.zeros((4,), jnp.int32))
    (dyn, done, overflow, tel), (moves, steps) = lax.scan(
        step, carry0, None, length=m)
    nmax = lax.pmax(jnp.max(dyn[8]), axis)
    return dyn, done, overflow, tel[None, :], moves, steps, nmax


#: replicated spec shared by every non-sharded leaf
_R = P()
#: carry specs: acting is (n_pg, n_slots) → axis 0; pool_counts/dst_ok are
#: (n_pools, n_dev) → axis 1; rows_on/nrows/pruned carry the device axis
#: leading; the O(n_dev) order bookkeeping and the moments stay replicated
_DYN_SPECS = (_R, _R, _R, _R, P("dev", None), P(None, "dev"),
              P(None, "dev"), P("dev"), P("dev"),
              _R, _R, _R, _R, P("dev"))
#: const specs: the eight per-row registry arrays are block-sharded on
#: the row axis and ideal on the device axis; the device registry
#: (cap/class/in/domain) is read at arbitrary indices in every tile and
#: is O(n_dev) — it stays replicated
_CONST_SPECS = (_R, _R, _R, _R) + (P("dev"),) * 8 + (P(None, "dev"),)

_SHARD_FNS: dict[int, object] = {}


def shard_chunk_program(mesh: Mesh):
    """The jitted sharded chunk dispatch over a 1-D ``("dev",)`` mesh.
    The carry is donated, mirroring the serial wrapper; one compiled
    program per tile geometry, like the serial ``_plan_chunk``."""
    n_shards = mesh.devices.size

    @partial(jax.jit, static_argnames=("k", "kb", "rb", "m", "backend",
                                       "bounds", "telemetry"),
             donate_argnums=(0,))
    def fn(dyn, const, slack, headroom, min_dvar, n_real, *, k, kb, rb,
           m, backend, bounds, telemetry=False):
        body = partial(_shard_chunk_impl, k=k, kb=kb, rb=rb, m=m,
                       backend=backend, bounds=bounds,
                       telemetry=telemetry, axis="dev",
                       n_shards=n_shards)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(_DYN_SPECS, _CONST_SPECS, _R, _R, _R, _R),
            out_specs=(_DYN_SPECS, _R, _R, P("dev"), _R, _R, _R),
            check_vma=False,
        )(dyn, const, slack, headroom, min_dvar, n_real)

    return fn


def _shard_chunk_fn(n_shards: int):
    """:func:`shard_chunk_program` on the first ``n_shards`` visible JAX
    devices, cached per mesh size."""
    fn = _SHARD_FNS.get(n_shards)
    if fn is None:
        mesh = Mesh(np.asarray(jax.devices()[:n_shards]), ("dev",))
        fn = _SHARD_FNS[n_shards] = shard_chunk_program(mesh)
    return fn


class ShardedBatchPlanner(BatchPlanner):
    """:class:`~repro.core.equilibrium_batch.BatchPlanner` with the chunk
    step dispatched over an ``n_shards``-way device mesh.

    The host-side machinery — staleness, delta absorption, stash,
    re-pads, reconcile — is inherited unchanged: only the dispatch
    (:meth:`_dispatch_chunk`) and the carry's device-axis width differ.
    The carry lives mesh-padded (device axis rounded up to a multiple of
    ``n_shards`` with the neutral pad device); it is cropped back to the
    natural width around absorption/rebuild so the inherited host math
    never sees pads, and re-padded before dispatch.

    ``n_shards`` defaults to every visible JAX device (on CPU, force
    a mesh with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    ``pad_devices`` overrides the padded width (tests use it to exercise
    uneven padding at mesh size 1); it must be a multiple of
    ``n_shards``.  ``legality_cache`` is refused — its buffers are the
    one carry element whose repair loop is not worth sharding until an
    accelerator mesh exists to measure it on — and selection is forced
    to the jnp reference kernel (the Pallas interpreter does not run
    under ``shard_map``).
    """

    def __init__(self, state: ClusterState,
                 cfg: EquilibriumConfig | None = None, *,
                 n_shards: int | None = None,
                 pad_devices: int | None = None, **kwargs):
        if kwargs.get("legality_cache"):
            raise ValueError("the sharded engine does not support the "
                             "cross-move legality cache; plan unsharded "
                             "or drop legality_cache")
        if kwargs.get("select_backend", "ref") not in ("ref", "auto"):
            raise ValueError("the sharded engine selects with the jnp "
                             "reference kernel; Pallas backends are "
                             "per-device")
        kwargs["select_backend"] = "ref"
        n_shards = int(n_shards) if n_shards else len(jax.devices())
        if not 1 <= n_shards <= len(jax.devices()):
            raise ValueError(f"n_shards={n_shards} but only "
                             f"{len(jax.devices())} devices are visible")
        self.n_shards = n_shards
        self._n_real = 0                # natural device count of the carry
        self._rows_real = 0             # natural registry row count
        self._pgs_real = 0              # natural acting-table height
        if pad_devices is not None and pad_devices % n_shards:
            raise ValueError(f"pad_devices={pad_devices} is not a "
                             f"multiple of n_shards={n_shards}")
        self._pad_override = pad_devices
        super().__init__(state, cfg, **kwargs)

    # -- mesh padding ---------------------------------------------------------

    def _pad_width(self, n: int) -> int:
        w = -(-n // self.n_shards) * self.n_shards
        if self._pad_override is not None:
            if self._pad_override < w:
                raise ValueError(f"pad_devices={self._pad_override} < "
                                 f"required width {w}")
            w = self._pad_override
        return w

    def sync(self) -> None:
        """Crop the carry back to its natural sizes before the inherited
        build/absorb (whose host-side math assumes natural-width arrays
        on every axis), then re-pad each mesh-sharded axis."""
        if self._dyn is not None and self._n_real and self.stale:
            self._crop_carry()
        super().sync()
        self._pad_carry()

    def _crop_carry(self) -> None:
        n, r, g = self._n_real, self._rows_real, self._pgs_real
        d = self._dyn
        self._dyn = (d[0][:n], d[1][:n], d[2], d[3], d[4][:g],
                     d[5][:, :n], d[6][:, :n], d[7][:n], d[8][:n],
                     d[9][:n], d[10], d[11], d[12], d[13][:n])
        c = self._const
        self._const = (c[0][:n], c[1][:n], c[2][:n], c[3][:, :n],
                       *(a[:r] for a in c[4:12]), c[12][:, :n])

    def _pad_carry(self) -> None:
        if self._dyn is None:
            self._n_real = 0
            return
        # natural sizes from the authoritative (never padded) sources:
        # the cluster for the device axis, the dense mirror for the
        # registry rows and the acting height — so re-entering on an
        # already-padded carry computes zero-width pads (idempotent)
        ns = self.n_shards
        self._n_real = n = self.state.n_devices
        self._rows_real = len(self._dense.sh_pg)
        self._pgs_real = len(self._dense.pgs)
        w = self._pad_width(n)
        pad = w - int(self._dyn[0].shape[0])
        pad_r = (-(-self._rows_real // ns) * ns
                 - int(self._const[4].shape[0]))
        pad_g = -(-self._pgs_real // ns) * ns - int(self._dyn[4].shape[0])
        if pad == pad_r == pad_g == 0:
            return
        # device pads are the fleet pack's neutral device: capacity 1,
        # nothing stored, out of service, classless (-2 matches no shard
        # class), its own unreachable failure domain.  Pads sort behind
        # every real device in the maintained fullest-first order and
        # stay there.  Registry/acting pads are never referenced (row and
        # pg ids in the carry are always real).
        d = self._dyn
        self._dyn = (
            jnp.pad(d[0], (0, pad)),                       # used 0.0
            jnp.pad(d[1], (0, pad)),                       # util 0.0
            d[2], d[3],
            jnp.pad(d[4], ((0, pad_g), (0, 0)), constant_values=-1),
            jnp.pad(d[5], ((0, 0), (0, pad))),             # pool_counts 0
            jnp.pad(d[6], ((0, 0), (0, pad))),             # dst_ok False
            jnp.pad(d[7], ((0, pad), (0, 0)), constant_values=-1),
            jnp.pad(d[8], (0, pad)),                       # nrows 0
            jnp.concatenate([d[9], jnp.arange(d[9].shape[0], w,
                                              dtype=jnp.int32)]),
            d[10], d[11], d[12],
            jnp.pad(d[13], (0, pad)),                      # pruned False
        )
        c = self._const
        self._const = (
            jnp.pad(c[0], (0, pad), constant_values=1.0),  # cap
            jnp.pad(c[1], (0, pad), constant_values=-2),   # class
            jnp.pad(c[2], (0, pad)),                       # in: False
            jnp.pad(c[3], ((0, 0), (0, pad)), constant_values=-2),
            jnp.pad(c[4], (0, pad_r)),                     # sh_size 0.0
            *(jnp.pad(a, (0, pad_r)) for a in c[5:12]),
            jnp.pad(c[12], ((0, 0), (0, pad))),            # ideal 0.0
        )

    # -- dispatch -------------------------------------------------------------

    def _dispatch_chunk(self, telemetry: bool):
        fn = _shard_chunk_fn(self.n_shards)
        jit0 = fn._cache_size()
        self._dyn, done, overflow, tel, moves, steps, nmax = fn(
            self._dyn, self._const, self._slack, self._headroom,
            self._min_dvar, jnp.asarray(float(self._n_real), jnp.float64),
            k=self._k, kb=self._kb, rb=self._rb, m=self.chunk,
            backend=self.select_backend, bounds=self.source_bounds,
            telemetry=telemetry)
        recompiles = fn._cache_size() - jit0
        if recompiles:
            _obs_registry().inc("batch.jit_recompiles", recompiles)
        return (moves, steps, done, overflow, tel, nmax), recompiles

    def _record_chunk_tel(self, reg, tel_np) -> None:
        tel = np.asarray(tel_np)
        # rows 0/1 are replicated (lockstep walk): aggregate once
        reg.inc("batch.tiles_walked", int(tel[0, 0]))
        reg.inc("batch.cand_tiles", int(tel[0, 1]))
        for s in range(tel.shape[0]):
            reg.inc("batch.shard.tiles_walked", int(tel[s, 0]), shard=s)
            reg.inc("batch.shard.cand_tiles", int(tel[s, 2]), shard=s)
            reg.inc("batch.shard.wins", int(tel[s, 3]), shard=s)

    def _flush_stats(self, raw_moves, stats_out, snap, *,
                     pruned=None) -> None:
        super()._flush_stats(raw_moves, stats_out, snap, pruned=pruned)
        stats_out["shards"] = self.n_shards


def chunk_memory_stats(bp: BatchPlanner, telemetry: bool = False) -> dict:
    """Per-device memory profile of the planner's compiled chunk program
    (XLA's ``memory_analysis`` of the lowered executable — for an SPMD
    mesh these are *per-participant* figures, which is exactly the
    1/N-scaling claim the bench's ``peak_bytes_per_device`` fields
    report).  Syncs the planner (building the carry if needed) so the
    lowering sees the real shapes; returns {} for a degenerate cluster
    with nothing to plan."""
    with jax.enable_x64(True):
        bp.sync()
        if bp._dyn is None:
            return {}
        if isinstance(bp, ShardedBatchPlanner):
            fn = _shard_chunk_fn(bp.n_shards)
            lowered = fn.lower(
                bp._dyn, bp._const, bp._slack, bp._headroom, bp._min_dvar,
                jnp.asarray(float(bp._n_real), jnp.float64),
                k=bp._k, kb=bp._kb, rb=bp._rb, m=bp.chunk,
                backend=bp.select_backend, bounds=bp.source_bounds,
                telemetry=telemetry)
        else:
            lowered = _plan_chunk.lower(
                bp._dyn, bp._const, bp._slack, bp._headroom, bp._min_dvar,
                k=bp._k, kb=bp._kb, rb=bp._rb, m=bp.chunk,
                backend=bp.select_backend, cached=bp.legality_cache,
                bounds=bp.source_bounds, telemetry=telemetry)
        mem = lowered.compile().memory_analysis()
    if mem is None:                      # pragma: no cover - backend quirk
        return {}
    stats = {
        "argument_bytes": int(mem.argument_size_in_bytes),
        "output_bytes": int(mem.output_size_in_bytes),
        "temp_bytes": int(mem.temp_size_in_bytes),
        "alias_bytes": int(mem.alias_size_in_bytes),
    }
    # donated-carry aliasing means argument+output double-counts the
    # in-place buffers; alias_bytes subtracts them back out
    stats["peak_bytes"] = (stats["argument_bytes"] + stats["output_bytes"]
                           + stats["temp_bytes"] - stats["alias_bytes"])
    return stats


@register_planner("equilibrium_batch_sharded", sim_config_attr="equilibrium",
                  description="batch engine with the chunk step shard_map-"
                              "ped over the visible device mesh (device-"
                              "axis partitioned legality tiles; bit-"
                              "identical to equilibrium_batch)",
                  equivalence="equilibrium")
class ShardedBatchEquilibriumPlanner(BatchEquilibriumPlanner):
    """Protocol adapter over :class:`ShardedBatchPlanner` — the sharded
    twin of the ``equilibrium_batch`` registry entry (same protocol
    surface, inherited from its adapter; only the bound engine differs).
    With one visible device (the default CPU configuration) this is the
    serial engine on a 1-mesh; with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` (or a real
    accelerator mesh) the legality tiles split N ways."""

    name = "equilibrium_batch_sharded"
    engine = "batch-sharded"

    def __init__(self, cfg: EquilibriumConfig | None = None, chunk: int = 64,
                 source_block: int = 1, row_block: int | None = None,
                 row_capacity: int | None = None, warm: bool = True,
                 source_bounds: bool = True, pipeline: bool = True,
                 n_shards: int | None = None,
                 pad_devices: int | None = None):
        super().__init__(cfg, chunk=chunk, source_block=source_block,
                         row_block=row_block, row_capacity=row_capacity,
                         warm=warm, source_bounds=source_bounds,
                         pipeline=pipeline)
        del self._engine_kwargs["select_backend"]
        del self._engine_kwargs["legality_cache"]
        self._engine_kwargs.update(n_shards=n_shards,
                                   pad_devices=pad_devices)

    def _bind(self, state: ClusterState) -> ShardedBatchPlanner:
        if self._impl is None or self._impl.state is not state:
            self._impl = ShardedBatchPlanner(state, self.cfg,
                                             **self._engine_kwargs)
        return self._impl
