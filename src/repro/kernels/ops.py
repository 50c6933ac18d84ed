"""Jit'd public wrappers for the Pallas kernels: model-layout in,
kernel-layout inside, validated against ref.py.

On this CPU container the kernels run with ``interpret=True`` (Pallas
executes the kernel body in Python per grid step — bit-accurate to the
TPU lowering semantics); on TPU the same call sites compile to Mosaic.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_fwd
from .select_move import masked_select_fwd
from .ssd_scan import ssd_scan_fwd


@partial(jax.jit, static_argnames=("causal", "window", "cap", "block_q",
                                   "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, cap=None,
                    block_q=128, block_k=512, interpret=False):
    """Model layout: q (B,T,H,Dh), k/v (B,T,KV,Dh) → (B,T,H,Dh)."""
    B, T, H, Dh = q.shape
    KV = k.shape[2]
    if KV != H:
        k = jnp.repeat(k, H // KV, axis=2)
        v = jnp.repeat(v, H // KV, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, T, Dh)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, k.shape[1], Dh)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, v.shape[1], Dh)
    out = flash_attention_fwd(qf, kf, vf, causal=causal, window=window,
                              cap=cap, block_q=block_q, block_k=block_k,
                              interpret=interpret)
    return out.reshape(B, H, T, Dh).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_rows", "interpret"))
def masked_select(valid, key, *, block_rows=256, interpret=False):
    """Masked move-selection reduction (the batched planner's inner kernel).

    valid (M, D) bool, key (D,) int32 exact emptiest-first order key
    (:func:`repro.kernels.select_move.order_key`) → (any (M,) bool,
    dst (M,) int32): per candidate row, whether any destination is legal
    and the emptiest legal destination (min key, ties → lowest device
    index).  Also callable inside an enclosing jit/scan (the planner's
    hot loop).
    """
    return masked_select_fwd(valid, key, block_rows=block_rows,
                             interpret=interpret)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, Bm, Cm, *, chunk=128, interpret=False):
    """Model layout: x (B,T,H,P), dt (B,T,H), A (H,), B/C (B,T,G,N)
    → y (B,T,H,P)."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    Bh = jnp.repeat(Bm, rep, axis=2)
    Ch = jnp.repeat(Cm, rep, axis=2)
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, T, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, T)
    Af = jnp.tile(A, B)
    Bf = Bh.transpose(0, 2, 1, 3).reshape(B * H, T, N)
    Cf = Ch.transpose(0, 2, 1, 3).reshape(B * H, T, N)
    y = ssd_scan_fwd(xf, dtf, Af, Bf, Cf, chunk=chunk, interpret=interpret)
    return y.reshape(B, H, T, P).transpose(0, 2, 1, 3)
