"""Pallas kernels vs ref.py oracles, interpret=True shape/dtype sweeps."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ops import flash_attention, masked_select, ssd_scan
from repro.core.legality import fullest_first
from repro.kernels.ref import (flash_attention_ref, masked_select_ref,
                               ssd_scan_ref)
from repro.kernels.select_move import order_key

TOL = {jnp.float32: dict(rtol=2e-3, atol=2e-3),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("T,Dh,dtype", [
    (128, 64, jnp.float32),
    (256, 64, jnp.float32),
    (128, 128, jnp.float32),
    (96, 64, jnp.float32),          # non-multiple of block (padding path)
    (128, 64, jnp.bfloat16),
])
def test_flash_fwd_shapes_dtypes(T, Dh, dtype):
    key = jax.random.PRNGKey(0)
    ks = jax.random.split(key, 3)
    B, H, KV = 2, 4, 2
    q = jax.random.normal(ks[0], (B, T, H, Dh), dtype)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), dtype)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), dtype)
    out = flash_attention(q, k, v, block_q=64, block_k=64, interpret=True)
    kf = jnp.repeat(k, H // KV, axis=2)
    vf = jnp.repeat(v, H // KV, axis=2)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, T, Dh),
        kf.transpose(0, 2, 1, 3).reshape(B * H, T, Dh),
        vf.transpose(0, 2, 1, 3).reshape(B * H, T, Dh))
    ref = ref.reshape(B, H, T, Dh).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **TOL[dtype])


@pytest.mark.parametrize("window,cap,causal", [
    (None, None, True), (32, None, True), (None, 50.0, True),
    (48, 30.0, True), (None, None, False),
])
@pytest.mark.slow
def test_flash_fwd_mask_variants(window, cap, causal):
    key = jax.random.PRNGKey(1)
    ks = jax.random.split(key, 3)
    B, T, H, Dh = 1, 128, 2, 64
    q = jax.random.normal(ks[0], (B, T, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, H, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, H, Dh), jnp.float32)
    out = flash_attention(q, k, v, causal=causal, window=window, cap=cap,
                          block_q=32, block_k=32, interpret=True)
    ref = flash_attention_ref(
        q.transpose(0, 2, 1, 3).reshape(B * H, T, Dh),
        k.transpose(0, 2, 1, 3).reshape(B * H, T, Dh),
        v.transpose(0, 2, 1, 3).reshape(B * H, T, Dh),
        causal=causal, window=window, cap=cap)
    ref = ref.reshape(B, H, T, Dh).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


def test_flash_matches_model_attention():
    """Kernel == the model's custom-VJP flash (same math, two impls)."""
    from repro.models.layers import attention
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 3)
    B, T, H, KV, Dh = 2, 128, 8, 4, 64
    q = jax.random.normal(ks[0], (B, T, H, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, KV, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, KV, Dh), jnp.float32)
    ker = flash_attention(q, k, v, window=64, block_q=64, block_k=64,
                          interpret=True)
    mdl = attention(q, jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2),
                    causal=True, window=64)
    np.testing.assert_allclose(np.asarray(ker), np.asarray(mdl),
                               rtol=2e-3, atol=2e-3)


# ---------------------------------------------------------------------------
# masked move-selection reduction (the batched planner's inner kernel)


def _key(util):
    """The planner's kernel input: the exact order key of ``util``."""
    order = jnp.asarray(fullest_first(np.asarray(util)), jnp.int32)
    return order_key(jnp.asarray(util), order)


@pytest.mark.parametrize("M,D,block_rows", [
    (8, 16, 256),
    (200, 995, 256),      # planner-shaped: k*row_block rows × OSDs
    (100, 300, 32),       # multi-block grid with row padding
    (1, 1, 8),
])
def test_masked_select_matches_ref(M, D, block_rows):
    rng = np.random.default_rng(42)
    valid = jnp.asarray(rng.random((M, D)) < 0.05)
    # float64 utilizations with runs of exact ties (the faithful order's
    # index tie-break must survive the key encoding)
    util = np.where(rng.random(D) < 0.3, rng.integers(0, 4, D) / 4.0,
                    rng.random(D))
    with jax.enable_x64(True):
        util = jnp.asarray(util)
        any_k, dst_k = masked_select(valid, _key(util),
                                     block_rows=block_rows, interpret=True)
        any_r, dst_r = masked_select_ref(valid, util)
    np.testing.assert_array_equal(np.asarray(any_k), np.asarray(any_r))
    # dst is defined only where a legal destination exists
    sel = np.asarray(any_r)
    np.testing.assert_array_equal(np.asarray(dst_k)[sel],
                                  np.asarray(dst_r)[sel])


def test_masked_select_tie_break_lowest_index():
    """Equal-utilization legal destinations resolve to the lowest device
    index — the faithful planner's stable emptiest-first scan order."""
    valid = jnp.asarray(np.array([[True, True, True, False]]))
    util = jnp.asarray(np.array([0.5, 0.2, 0.2, 0.0], np.float32))
    for fn in (lambda v, u: masked_select(v, _key(u), interpret=True),
               masked_select_ref):
        anyv, dst = fn(valid, util)
        assert bool(anyv[0]) and int(dst[0]) == 1


def test_masked_select_all_invalid_row():
    valid = jnp.asarray(np.zeros((3, 7), bool))
    util = jnp.asarray(np.linspace(0, 1, 7).astype(np.float32))
    anyv, _ = masked_select(valid, _key(util), interpret=True)
    assert not np.asarray(anyv).any()


def test_order_key_separates_values_float32_would_merge():
    """Utilizations one float64 ulp apart round to the same float32; the
    order key keeps them apart, so the kernel still picks the smaller."""
    base = 0.7
    util = np.array([np.nextafter(base, 1.0), base, base, 0.9])
    assert len(set(util.astype(np.float32).tolist())) == 2
    valid = jnp.asarray(np.array([[True, True, False, True],
                                  [True, False, True, True]]))
    with jax.enable_x64(True):
        key = _key(jnp.asarray(util))
        anyv, dst = masked_select(valid, key, interpret=True)
        _, dst_r = masked_select_ref(valid, jnp.asarray(util))
    assert np.asarray(key).tolist() == [2, 1, 1, 3]
    assert np.asarray(dst).tolist() == np.asarray(dst_r).tolist() == [1, 2]


# ---------------------------------------------------------------------------
# SSD scan


@pytest.mark.parametrize("T,P,N,chunk,dtype", [
    (64, 16, 16, 16, jnp.float32),
    (128, 32, 16, 32, jnp.float32),
    (64, 16, 16, 64, jnp.float32),   # single chunk
    (64, 16, 16, 16, jnp.bfloat16),
])
@pytest.mark.slow
def test_ssd_scan_shapes_dtypes(T, P, N, chunk, dtype):
    key = jax.random.PRNGKey(3)
    ks = jax.random.split(key, 5)
    B, H, G = 2, 4, 2
    x = jax.random.normal(ks[0], (B, T, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = (jax.random.normal(ks[3], (B, T, G, N)) * 0.5).astype(dtype)
    Cm = (jax.random.normal(ks[4], (B, T, G, N)) * 0.5).astype(dtype)
    y = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)

    rep = H // G
    xf = x.transpose(0, 2, 1, 3).reshape(B * H, T, P)
    dtf = dt.transpose(0, 2, 1).reshape(B * H, T).astype(jnp.float32)
    Af = jnp.tile(A, B)
    Bf = jnp.repeat(Bm, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, T, N).astype(jnp.float32)
    Cf = jnp.repeat(Cm, rep, axis=2).transpose(0, 2, 1, 3).reshape(B * H, T, N).astype(jnp.float32)
    y_ref, _ = ssd_scan_ref(xf.astype(jnp.float32), dtf, Af, Bf, Cf)
    y_ref = y_ref.reshape(B, H, T, P).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **TOL[dtype])


def test_ssd_kernel_matches_model_ssd():
    """Kernel == the model's chunked SSD (two implementations, one math)."""
    from repro.models.ssm import ssd_chunked
    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 5)
    B, T, H, P, G, N = 2, 64, 4, 16, 1, 16
    x = jax.random.normal(ks[0], (B, T, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, T, G, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, T, G, N)) * 0.5
    y_kernel = ssd_scan(x, dt, A, Bm, Cm, chunk=16, interpret=True)
    y_model, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=16, superchunk=2)
    np.testing.assert_allclose(np.asarray(y_kernel), np.asarray(y_model),
                               rtol=2e-3, atol=2e-3)
