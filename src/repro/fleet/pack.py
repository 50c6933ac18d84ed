"""Shape-bucketed packing of batch-engine carries into one fleet pytree.

The batch engine's carry (:mod:`repro.core.equilibrium_batch`) is a pure
pytree of device arrays whose shapes are a cluster's natural dimensions
(devices, shard rows, PGs, pools, …).  ``vmap`` needs every cluster in a
batch to share one static shape, so this module:

* rounds each cluster's :class:`CarryDims` up to a power-of-two
  :class:`BucketShape` (clusters of similar size share a bucket — one
  compiled program per bucket, stable across fleet membership churn);
* pads each carry + const tuple to its bucket shape with **neutral
  values**, chosen so padding can never change a plan: pad devices are
  not ``in`` (never destinations), hold no rows (never winning sources),
  carry utilization 0.0 (they sort after every real device in the
  fullest-first order and in every ``reorder`` insertion count), and the
  per-cluster ``n_real`` / ``k_eff`` scalars keep the variance criterion
  and the source walk blind to them (see ``_plan_chunk_impl``'s
  docstring for the proof obligations);
* stacks the padded carries along a new leading cluster axis —
  the fleet pytree one vmapped device step plans for.

The stacked arrays are the *authoritative* carry while a fleet tick
runs; :meth:`FleetPack.crop_lane` hands a cluster's slice back to its
:class:`~repro.core.equilibrium_batch.BatchPlanner` afterwards.  Every
axis is cropped back to its natural extent **except** ``r_cap``: the
chunk step shifts rows across the full padded row axis, so entries may
legally sit beyond the old natural capacity — the planner adopts the
bucket width as its new ``_r_cap`` instead (still a ``row_block``
multiple, because bucket widths are powers of two ≥ ``row_block``).

When one cluster's growth overflows its padded slot, only that
cluster's slice moves to the next size bucket
(:meth:`FleetPack.rebucket`): the old slot is marked free — the other
clusters' stacked arrays are not rebuilt, so their carries (including
live source-bound certificates) survive bitwise untouched
(regression-tested in tests/test_fleet.py).
"""

from __future__ import annotations

import dataclasses
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["CarryDims", "BucketShape", "FleetPack"]


def _pow2(n: int) -> int:
    n = max(1, int(n))
    return 1 << (n - 1).bit_length()


@dataclasses.dataclass(frozen=True)
class CarryDims:
    """Natural (unpadded) shape of one cluster's batch-engine carry."""

    n_dev: int
    r_cap: int
    n_sh: int
    n_pg: int
    n_slots: int
    n_pools: int
    n_levels: int
    k: int          # the cluster's true source-queue depth (bp._k)

    @classmethod
    def of(cls, bp) -> "CarryDims":
        """Read the dims off a synced BatchPlanner (``bp._dyn`` set)."""
        const, dyn = bp._const, bp._dyn
        return cls(n_dev=int(const[0].shape[0]),
                   r_cap=int(dyn[7].shape[1]),
                   n_sh=int(const[4].shape[0]),
                   n_pg=int(dyn[4].shape[0]),
                   n_slots=int(dyn[4].shape[1]),
                   n_pools=int(const[12].shape[0]),
                   n_levels=int(const[3].shape[0]),
                   k=int(bp._k))


@dataclasses.dataclass(frozen=True)
class BucketShape:
    """Padded static shape shared by every cluster in one vmap bucket.
    Doubles as the bucket key: equal shapes ⇒ one compiled chunk step."""

    n_dev: int
    r_cap: int
    n_sh: int
    n_pg: int
    n_slots: int
    n_pools: int
    n_levels: int
    k: int          # static source-queue width (≥ every member's k_eff)

    @classmethod
    def for_dims(cls, dims: CarryDims, rb: int) -> "BucketShape":
        # rb is a power of two (asserted by FleetPack), so any pow2
        # r_cap ≥ rb stays a multiple of rb — the r_cap % rb == 0
        # invariant the chunk step's block walk relies on
        n_dev = _pow2(dims.n_dev)
        return cls(n_dev=n_dev, r_cap=max(rb, _pow2(dims.r_cap)),
                   n_sh=_pow2(dims.n_sh), n_pg=_pow2(dims.n_pg),
                   n_slots=_pow2(dims.n_slots),
                   n_pools=_pow2(dims.n_pools),
                   n_levels=_pow2(dims.n_levels),
                   k=min(n_dev, _pow2(dims.k)))

    def next_r_cap(self) -> "BucketShape":
        return dataclasses.replace(self, r_cap=self.r_cap * 2)

    def fits(self, dims: CarryDims) -> bool:
        return (dims.n_dev <= self.n_dev and dims.r_cap <= self.r_cap
                and dims.n_sh <= self.n_sh and dims.n_pg <= self.n_pg
                and dims.n_slots <= self.n_slots
                and dims.n_pools <= self.n_pools
                and dims.n_levels <= self.n_levels and dims.k <= self.k)

    def grown_to(self, dims: CarryDims, rb: int) -> "BucketShape":
        """The smallest bucket covering both this shape and ``dims`` —
        keeps a cluster's earlier r_cap escalation sticky when other
        axes grow."""
        want = BucketShape.for_dims(dims, rb)
        return BucketShape(*(max(a, b) for a, b in
                             zip(dataclasses.astuple(self),
                                 dataclasses.astuple(want))))


def pad_const(const, shape: BucketShape):
    """Pad one cluster's const tuple to the bucket shape.  Pad devices:
    capacity 1.0 (divisions stay finite), class -2 (matches no shard
    class), ``in`` False (the destination backstop), domain -2 (shared
    with no real device).  Pad shard rows: size 0.0 — the ``real`` mask
    every candidate test requires is size > 0, the same guard the
    natural -1 row padding already uses."""
    (cap, dev_class, dev_in, dev_domain, sh_size, sh_pg, sh_pool,
     sh_class, sh_level, sh_slot, sh_sbase, sh_scnt, ideal) = const
    d = shape.n_dev - cap.shape[0]
    s = shape.n_sh - sh_size.shape[0]
    p = shape.n_pools - ideal.shape[0]
    lv = shape.n_levels - dev_domain.shape[0]
    return (
        jnp.pad(cap, (0, d), constant_values=1.0),
        jnp.pad(dev_class, (0, d), constant_values=-2),
        jnp.pad(dev_in, (0, d)),                            # False
        jnp.pad(dev_domain, ((0, lv), (0, d)), constant_values=-2),
        jnp.pad(sh_size, (0, s)),                           # 0.0: not real
        jnp.pad(sh_pg, (0, s)),
        jnp.pad(sh_pool, (0, s)),
        jnp.pad(sh_class, (0, s), constant_values=-1),
        jnp.pad(sh_level, (0, s)),
        jnp.pad(sh_slot, (0, s)),
        jnp.pad(sh_sbase, (0, s)),
        jnp.pad(sh_scnt, (0, s)),
        jnp.pad(ideal, ((0, p), (0, d))),
    )


def pad_dyn(dyn, shape: BucketShape):
    """Pad one cluster's dyn carry to the bucket shape.  Pad devices
    enter the maintained fullest-first order *behind* every real device
    (utilization 0.0 ties break toward the lower real index, and
    ``reorder`` preserves that), their row lists are empty (-1), their
    ``dst_ok`` columns False, and they are never pruned — so
    ``order[:n_real]`` always holds exactly the real devices and the
    crop back to natural shape is a pure slice."""
    (used, util, us, usq, acting, pool_counts, dst_ok, rows_on, nrows,
     order, c_dev, c_ok, c_clean, pruned) = dyn
    n_nat = used.shape[0]
    d = shape.n_dev - n_nat
    g = shape.n_pg - acting.shape[0]
    sl = shape.n_slots - acting.shape[1]
    p = shape.n_pools - pool_counts.shape[0]
    r = shape.r_cap - rows_on.shape[1]
    order_pad = jnp.concatenate(
        [order, jnp.arange(n_nat, shape.n_dev, dtype=order.dtype)])
    return (
        jnp.pad(used, (0, d)),
        jnp.pad(util, (0, d)),
        us, usq,
        jnp.pad(acting, ((0, g), (0, sl)), constant_values=-1),
        jnp.pad(pool_counts, ((0, p), (0, d))),
        jnp.pad(dst_ok, ((0, p), (0, d))),                  # False
        jnp.pad(rows_on, ((0, d), (0, r)), constant_values=-1),
        jnp.pad(nrows, (0, d)),
        order_pad,
        c_dev, c_ok, c_clean,       # legality cache is off fleet-wide:
        #                             placeholder shapes, no device axis
        jnp.pad(pruned, (0, d)),
    )


def crop_dyn(dyn, dims: CarryDims):
    """Crop a planned lane back to its natural shape — every axis except
    ``r_cap`` (rows legally shift across the full padded width; the
    owning planner adopts the bucket width as its ``_r_cap``)."""
    (used, util, us, usq, acting, pool_counts, dst_ok, rows_on, nrows,
     order, c_dev, c_ok, c_clean, pruned) = dyn
    n = dims.n_dev
    return (used[:n], util[:n], us, usq,
            acting[:dims.n_pg, :dims.n_slots],
            pool_counts[:dims.n_pools, :n],
            dst_ok[:dims.n_pools, :n],
            rows_on[:n],                    # full bucket r_cap kept
            nrows[:n], order[:n],
            c_dev, c_ok, c_clean, pruned[:n])


@partial(jax.jit, static_argnames=("shape",))
def _write_lane(st_dyn, st_const, dyn, const, lane, *, shape: BucketShape):
    """Pad one carry and write it into lane ``lane`` of the stacked
    arrays as ONE fused dispatch (the eager pad + 27 ``.at[i].set``
    calls cost ~50 host round-trips per lane per tick otherwise).
    ``lane`` is traced, so all lanes share one compiled program per
    (carry dims, bucket shape) pair."""
    return (jax.tree_util.tree_map(lambda s, v: s.at[lane].set(v),
                                   st_dyn, pad_dyn(dyn, shape)),
            jax.tree_util.tree_map(lambda s, v: s.at[lane].set(v),
                                   st_const, pad_const(const, shape)))


@partial(jax.jit, static_argnames=("dims",))
def _crop_lane_fused(st_dyn, lane, *, dims: CarryDims):
    """Slice lane ``lane`` out of the stacked dyn arrays and crop it to
    its natural shape in ONE fused dispatch (eager slicing costs ~24
    host round-trips per lane per tick)."""
    return crop_dyn(jax.tree_util.tree_map(lambda s: s[lane], st_dyn), dims)


def _scalars_of(bp, dims: CarryDims):
    """The per-cluster traced scalars: (slack, headroom, min_dvar,
    n_real, k_eff) — read from the config (host floats), never from the
    device, so packing costs no sync."""
    cfg = bp.cfg
    return (np.float64(cfg.count_slack), np.float64(cfg.headroom),
            np.float64(cfg.min_variance_delta), np.float64(dims.n_dev),
            np.int32(dims.k))


class _Bucket:
    """One vmap group: stacked carries + per-lane bookkeeping.

    ``keys[i] is None`` marks lane ``i`` free (its stacked values are
    stale and inert: the planner never sets such a lane active, and an
    inactive lane's chunk step is a bitwise no-op).  Freed lanes are
    reused by the next :meth:`put` before the arrays grow."""

    def __init__(self, shape: BucketShape):
        self.shape = shape
        self.keys: list[object | None] = []
        self.dims: list[CarryDims | None] = []
        self.dyn = None                 # 14-tuple, leading axis = n lanes
        self.const = None               # 13-tuple, leading axis = n lanes
        # (slack, headroom, min_dvar, n_real) float64 + k_eff int32,
        # all (n lanes,) numpy — stacked host-side, converted at dispatch
        self.scalars = (np.zeros(0), np.zeros(0), np.zeros(0),
                        np.zeros(0), np.zeros(0, np.int32))
        # device-resident mirrors reused across dispatch rounds: the
        # scalar transfer (5 arrays) and the active mask only change
        # when a lane is (re)packed / the live set moves, not per round
        self.dev_scalars = None
        self._mask_cache: dict[bytes, object] = {}

    def __len__(self) -> int:
        return len(self.keys)

    def lanes(self) -> dict[object, int]:
        return {k: i for i, k in enumerate(self.keys) if k is not None}

    def put(self, key, dyn, const, scal, dims: CarryDims) -> int:
        """Insert or overwrite one lane from an *unpadded* carry
        (padding happens here; already-padded inputs pass through —
        every pad delta is 0); returns the lane index."""
        if key in self.keys:
            i = self.keys.index(key)
        elif None in self.keys:
            i = self.keys.index(None)
        else:
            i = len(self.keys)
            self.keys.append(key)
            self.dims.append(dims)
            dyn_pad = pad_dyn(dyn, self.shape)
            const_pad = pad_const(const, self.shape)
            if self.dyn is None:
                self.dyn = jax.tree_util.tree_map(lambda v: v[None],
                                                  dyn_pad)
                self.const = jax.tree_util.tree_map(lambda v: v[None],
                                                    const_pad)
            else:
                self.dyn = jax.tree_util.tree_map(
                    lambda s, v: jnp.concatenate([s, v[None]]),
                    self.dyn, dyn_pad)
                self.const = jax.tree_util.tree_map(
                    lambda s, v: jnp.concatenate([s, v[None]]),
                    self.const, const_pad)
            self.scalars = tuple(np.concatenate([a, np.asarray([v])])
                                 for a, v in zip(self.scalars, scal))
            self.dev_scalars = None
            self._mask_cache.clear()
            return i
        # overwrite an existing / freed lane in place — one fused
        # dispatch; only this lane's values change, every other lane
        # stays bitwise as it was
        self.keys[i] = key
        self.dims[i] = dims
        self.dyn, self.const = _write_lane(self.dyn, self.const, dyn,
                                           const, np.int32(i),
                                           shape=self.shape)
        for a, v in zip(self.scalars, scal):
            a[i] = v
        self.dev_scalars = None
        return i

    def dispatch_scalars(self):
        """The stacked traced scalars as device arrays (cached; callers
        must hold ``jax.enable_x64(True)`` so the float64 dtypes
        survive)."""
        if self.dev_scalars is None:
            self.dev_scalars = tuple(jnp.asarray(a) for a in self.scalars)
        return self.dev_scalars

    def dispatch_mask(self, mask):
        """Device mirror of one bool lane mask, cached by value (the
        live set repeats across rounds far more often than it changes)."""
        key = mask.tobytes()
        dev = self._mask_cache.get(key)
        if dev is None:
            if len(self._mask_cache) > 64:       # stale live-sets
                self._mask_cache.clear()
            dev = self._mask_cache[key] = jnp.asarray(mask)
        return dev

    def free(self, i: int) -> None:
        self.keys[i] = None
        self.dims[i] = None

    def slice_dyn(self, i: int):
        return jax.tree_util.tree_map(lambda s: s[i], self.dyn)

    def slice_const(self, i: int):
        return jax.tree_util.tree_map(lambda s: s[i], self.const)


class FleetPack:
    """The fleet pytree: shape buckets of stacked carries, plus the
    locator and identity tokens that keep re-packing incremental (an
    unchanged cluster's lane is reused as-is across ticks)."""

    def __init__(self, rb: int = 8):
        if rb < 1 or rb & (rb - 1):
            raise ValueError(f"row_block must be a power of two, got {rb}")
        self.rb = rb
        self.buckets: dict[BucketShape, _Bucket] = {}
        self.where: dict[object, tuple[BucketShape, int]] = {}
        # id(bp._dyn) of the tuple *we* wrote back at last crop: matching
        # means the stacked lane is still the authoritative carry
        self.tokens: dict[object, int] = {}

    # -- packing --------------------------------------------------------------

    def _insert(self, key, bp, dims: CarryDims, shape: BucketShape) -> None:
        bucket = self.buckets.get(shape)
        if bucket is None:
            bucket = self.buckets[shape] = _Bucket(shape)
        i = bucket.put(key, bp._dyn, bp._const,
                       _scalars_of(bp, dims), dims)
        self.where[key] = (shape, i)
        self.tokens[key] = id(bp._dyn)

    def ensure(self, key, bp) -> bool:
        """Make ``key``'s lane current with ``bp``'s carry; returns True
        when the lane had to be (re)packed, False when the stacked slice
        was still authoritative (nothing moved, nothing copied)."""
        dims = CarryDims.of(bp)
        loc = self.where.get(key)
        if loc is not None:
            shape, i = loc
            if self.tokens.get(key) == id(bp._dyn) and shape.fits(dims):
                return False
            if shape.fits(dims):        # same bucket, refreshed carry
                self._insert(key, bp, dims, shape)
                return True
            # outgrew the bucket: free the old lane, move this slice
            # only — no other cluster's arrays are rebuilt
            self.buckets[shape].free(i)
            del self.where[key]
            self._insert(key, bp, dims, shape.grown_to(dims, self.rb))
            return True
        self._insert(key, bp, dims,
                     BucketShape.for_dims(dims, self.rb))
        return True

    def remove(self, key) -> None:
        loc = self.where.pop(key, None)
        self.tokens.pop(key, None)
        if loc is not None:
            shape, i = loc
            self.buckets[shape].free(i)

    # -- mid-plan re-bucketing (the heterogeneous-shape overflow fix) ---------

    def rebucket(self, key) -> tuple[BucketShape, int]:
        """Move one overflowing lane to the next r_cap bucket, carrying
        its *current device values* (mid-plan state) along: the row axis
        is extended with -1 padding — exactly what the serial engine's
        host re-pad writes, since every entry past ``nrows`` already is
        -1 — and every other axis is unchanged.  The old lane is freed;
        no other cluster's slice is touched.  Returns the new
        (bucket shape, lane index)."""
        shape, i = self.where[key]
        old = self.buckets[shape]
        dyn = old.slice_dyn(i)
        const = old.slice_const(i)
        scal = tuple(a[i] for a in old.scalars)
        dims = old.dims[i]
        old.free(i)
        new_shape = shape.next_r_cap()
        grow = new_shape.r_cap - shape.r_cap
        rows_on = jnp.pad(dyn[7], ((0, 0), (0, grow)), constant_values=-1)
        dyn = dyn[:7] + (rows_on,) + dyn[8:]
        bucket = self.buckets.get(new_shape)
        if bucket is None:
            bucket = self.buckets[new_shape] = _Bucket(new_shape)
        j = bucket.put(key, dyn, const, scal, dims)
        self.where[key] = (new_shape, j)
        # the device carry moved buckets; the planner-side tuple is now
        # stale until the next crop writes it back
        self.tokens.pop(key, None)
        return new_shape, j

    # -- unpacking ------------------------------------------------------------

    def crop_lane(self, key, bp) -> None:
        """Write ``key``'s (possibly planned-on) lane back into its
        BatchPlanner: natural-shape crops for every axis except the row
        axis, whose bucket width ``bp`` adopts as its ``_r_cap``."""
        shape, i = self.where[key]
        bucket = self.buckets[shape]
        with jax.enable_x64(True):  # callers outside a plan tick (detach)
            bp._dyn = _crop_lane_fused(bucket.dyn, np.int32(i),
                                       dims=bucket.dims[i])
        bp._r_cap = shape.r_cap
        self.tokens[key] = id(bp._dyn)
