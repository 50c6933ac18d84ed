"""Pallas kernel: masked move-selection reduction for the batched planner.

The device-resident Equilibrium engine (:mod:`repro.core.equilibrium_batch`)
evaluates a ``(k_sources × row_block, n_devices)`` legality matrix per
planning step and then needs, **per candidate shard row**:

* ``any``  — does the row have at least one legal destination, and
* ``dst``  — the emptiest legal destination (min utilization, ties broken
  toward the lowest device index — the faithful planner's stable scan
  order).

That is a masked argmin over the (utilization, device index) order.  The
planner's utilizations are float64, which the TPU's vector units do not
have, and a float32 cast would merge or reorder near-equal values.  The
kernel therefore reduces an exact int32 *order key* instead
(:func:`order_key`): ``key[a] < key[b]`` iff ``util[a] < util[b]``, and
equal utilizations share a key, so the kernel's lowest-index tie-break
yields exactly the (util, index) minimum that ``masked_select_ref`` in
:mod:`repro.kernels.ref` computes from the float64 values.

Layout: a grid over row blocks, one ``(block_rows, D)`` int32 tile of the
validity matrix in VMEM per step, the ``(1, D)`` key row broadcast to
every step; every block is 2-D and every value 32-bit, so Mosaic needs
no relayout.  On TPU the call compiles to Mosaic; off-TPU it runs with
``interpret=True`` (tests only — the planner's CPU backend is the jnp
reference).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

#: masked-out key: above every real key (keys are < the device count)
_NO_DST = int(np.iinfo(np.int32).max)
#: int32 block index (under x64 a bare 0 would make the index map int64)
_I0 = np.int32(0)


def order_key(util: jax.Array, order: jax.Array) -> jax.Array:
    """Exact int32 emptiest-first key from a fullest-first order.

    ``order`` (D,) lists device indices sorted by (-util, index) — the
    batch engine's maintained order (``legality.fullest_first``).
    Equal-utilization runs of that order share one key; runs further
    back (emptier) get smaller keys, so ``key[a] < key[b]`` iff
    ``util[a] < util[b]``.  Only float equality is evaluated, never
    float arithmetic, so the key is exact on every backend.
    """
    n = order.shape[0]
    u = util[order]
    starts = jnp.concatenate([jnp.ones((1,), bool), u[1:] != u[:-1]])
    run = jnp.cumsum(starts.astype(jnp.int32))          # 1 = fullest run
    return jnp.zeros((n,), jnp.int32).at[order].set(n - run)


def _select_kernel(valid_ref, key_ref, any_ref, dst_ref):
    """One grid step: a (block_rows, D) tile of the validity matrix."""
    # every constant is an explicit int32: the kernel may be traced with
    # x64 enabled, where a bare Python int would widen to int64
    none = jnp.int32(_NO_DST)
    masked = jnp.where(valid_ref[...] != jnp.int32(0), key_ref[...], none)
    best = jnp.min(masked, axis=1, keepdims=True)             # (bm, 1)
    lane = lax.broadcasted_iota(jnp.int32, masked.shape, 1)
    past = jnp.int32(masked.shape[1])
    dst_ref[...] = jnp.min(jnp.where(masked == best, lane, past),
                           axis=1, keepdims=True)
    any_ref[...] = (best < none).astype(jnp.int32)


def masked_select_fwd(valid: jax.Array, key: jax.Array, *,
                      block_rows: int = 256,
                      interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """valid: (M, D) bool, key: (D,) int32 → (any (M,) bool, dst (M,) int32).

    Rows are padded to a ``block_rows`` multiple (a multiple of 8) and the
    device axis to a 128-lane multiple (padding is invalid, so it never
    wins the argmin and never sets ``any``).  Rows with no legal
    destination return dst 0, like the reference.
    """
    M, D = valid.shape
    bm = min(-(-block_rows // 8) * 8, -(-max(M, 1) // 8) * 8)
    nm = -(-M // bm)
    pad_m = nm * bm - M
    pad_d = (-D) % 128
    valid = jnp.pad(valid.astype(jnp.int32), ((0, pad_m), (0, pad_d)))
    key = jnp.pad(key.astype(jnp.int32), (0, pad_d),
                  constant_values=_NO_DST)[None, :]
    Dp = D + pad_d

    any_out, dst_out = pl.pallas_call(
        _select_kernel,
        grid=(nm,),
        in_specs=[
            pl.BlockSpec((bm, Dp), lambda i: (i, _I0)),
            pl.BlockSpec((1, Dp), lambda i: (_I0, _I0)),
        ],
        out_specs=[
            pl.BlockSpec((bm, 1), lambda i: (i, _I0)),
            pl.BlockSpec((bm, 1), lambda i: (i, _I0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nm * bm, 1), jnp.int32),
            jax.ShapeDtypeStruct((nm * bm, 1), jnp.int32),
        ],
        interpret=interpret,
        name="masked_select",
    )(valid, key)
    return any_out[:M, 0] != 0, dst_out[:M, 0]


def compact_parked(order_k: jax.Array,
                   parked: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Stable partition of the top-k source ranks by an arbitrary
    per-rank ``parked`` mask: unparked ranks first (fullest-first order
    preserved), parked ranks at the back.

    order_k: (k,) device indices, fullest first.  parked: (k,) bool, one
    flag per *rank*.  Returns (compacted (k,) order, int32 count of
    unparked ranks).  k is a handful of lanes, so this is a jnp sort,
    not a Pallas grid; the stable partition is encoded in the sort key
    (parked ranks shifted past every unparked rank) to avoid relying on
    argsort stability.

    The per-rank mask is what lets the fleet planner
    (:mod:`repro.fleet.planner`) park the shape-padding ranks beyond a
    cluster's true ``k_eff`` through the same partition its pruned
    sources use — one code path, one proof of order preservation.
    """
    k = order_k.shape[0]
    rank = jnp.arange(k, dtype=jnp.int32)
    perm = jnp.argsort(jnp.where(parked, rank + k, rank))
    return order_k[perm], jnp.sum(~parked).astype(jnp.int32)


def compact_sources(order_k: jax.Array,
                    pruned: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Masked-select over the pruned source set: stable partition of the
    top-k source ranks so unpruned sources come first (fullest-first
    order preserved) and pruned sources are parked at the back.

    order_k: (k,) device indices, fullest first.  pruned: (n_dev,) bool.
    Returns (compacted (k,) order, int32 count of unpruned sources).
    The scan then starts at the first plausible source and stops after
    ``count`` ranks; parked entries keep their devices (so downstream
    gathers stay in-bounds) but are masked out of winning/pruning by the
    ``count`` guard.
    """
    return compact_parked(order_k, pruned[order_k])
