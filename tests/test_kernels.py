"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs pure-jnp oracle.

Every kernel in ``repro.kernels`` is validated against its ``ref.py`` across
shapes (tile-aligned and ragged), dtypes, and feature flags (causal/window/
GQA groups/heads/chunk sizes).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.block_attention import ops as ba_ops
from repro.kernels.block_attention import ref as ba_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.gae_project import ops as gp_ops
from repro.kernels.gae_project import ref as gp_ref
from repro.kernels.quantize import ops as qz_ops
from repro.kernels.quantize import ref as qz_ref
from repro.kernels.ssd_scan import ops as ssd_ops
from repro.kernels.ssd_scan import ref as ssd_ref

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(atol=5e-2, rtol=5e-2) if dtype == jnp.bfloat16 else \
        dict(atol=3e-5, rtol=3e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,t,h,kv,hd", [
    (2, 256, 256, 4, 2, 64),      # tile-aligned GQA
    (1, 200, 200, 8, 1, 32),      # ragged seq, MQA
    (2, 128, 128, 4, 4, 128),     # MHA, wide head
    (1, 64, 192, 2, 2, 16),       # t > s: suffix-aligned queries
    (1, 96, 96, 6, 3, 48),        # ragged everything
])
@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 64)])
def test_flash_attention_sweep(b, s, t, h, kv, hd, causal, window):
    ks = jax.random.split(jax.random.fold_in(KEY, s * h + hd + window), 3)
    q = jax.random.normal(ks[0], (b, s, h, hd), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, kv, hd), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=causal, window=window)
    exp = fa_ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), **_tol(q.dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 4, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 128, 2, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 128, 2, 64)).astype(dtype)
    out = fa_ops.flash_attention(q, k, v, causal=True)
    exp = fa_ref.flash_attention_ref(q, k, v, causal=True)
    assert out.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def test_flash_attention_small_block_sizes():
    """Multi-block online-softmax path (several kv iterations)."""
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (1, 128, 2, 32), jnp.float32)
    k = jax.random.normal(ks[1], (1, 128, 2, 32), jnp.float32)
    v = jax.random.normal(ks[2], (1, 128, 2, 32), jnp.float32)
    out = fa_ops.flash_attention(q, k, v, causal=True, bq=32, bk=32)
    exp = fa_ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=3e-5,
                               rtol=3e-5)


# ---------------------------------------------------------------------------
# hyper-block attention (HBAE)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,n,d,heads", [
    (37, 10, 128, 1),     # paper config: k=10 blocks, d=128, single head
    (256, 8, 64, 4),
    (5, 5, 32, 2),
    (1, 2, 16, 1),
    (300, 16, 128, 8),
])
def test_block_attention_sweep(b, n, d, heads):
    ks = jax.random.split(jax.random.fold_in(KEY, b * n + d), 3)
    q, k, v = (jax.random.normal(kk, (b, n, d), jnp.float32) for kk in ks)
    out = ba_ops.block_attention(q, k, v, heads=heads)
    exp = ba_ref.block_attention_ref(q, k, v, heads=heads)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=3e-5,
                               rtol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_block_attention_dtype_and_lead_shape(dtype):
    ks = jax.random.split(KEY, 3)
    q, k, v = (jax.random.normal(kk, (4, 9, 10, 64)).astype(dtype) for kk in ks)
    out = ba_ops.block_attention(q, k, v, heads=1)
    exp = ba_ref.block_attention_ref(q.reshape(36, 10, 64),
                                     k.reshape(36, 10, 64),
                                     v.reshape(36, 10, 64), heads=1)
    assert out.shape == (4, 9, 10, 64)
    np.testing.assert_allclose(np.asarray(out.reshape(36, 10, 64), np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# GAE projection
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,d", [
    (100, 80),        # paper S3D GAE block 5*4*4=80
    (64, 256),        # E3SM GAE block 16*16
    (1024, 1521),     # XGC 39*39 (column-tiled basis path)
    (7, 9),           # tiny ragged
    (512, 512),       # tile-exact
])
def test_gae_project_sweep(n, d):
    ks = jax.random.split(jax.random.fold_in(KEY, n + d), 2)
    r = jax.random.normal(ks[0], (n, d), jnp.float32)
    u = jax.random.normal(ks[1], (d, d), jnp.float32) / np.sqrt(d)
    c, c2 = gp_ops.gae_project(r, u)
    ce, c2e = gp_ref.gae_project_ref(r, u)
    np.testing.assert_allclose(np.asarray(c), np.asarray(ce), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(c2), np.asarray(c2e), atol=1e-4, rtol=1e-4)


def test_gae_project_matches_gae_select_path():
    """The kernel path inside gae_select must agree with the jnp path."""
    from repro.core.gae import fit_pca_basis, gae_correct, gae_select
    ks = jax.random.split(KEY, 2)
    r = jax.random.normal(ks[0], (50, 40), jnp.float32) * 0.1
    basis = fit_pca_basis(r)
    a = gae_select(r, basis, tau=0.05, bin_size=0.01, use_kernel=False)
    b = gae_select(r, basis, tau=0.05, bin_size=0.01, use_kernel=True)
    np.testing.assert_array_equal(np.asarray(a.m), np.asarray(b.m))
    a_corrected, _ = gae_correct(r, basis, a, 0.01)
    b_corrected, _ = gae_correct(r, basis, b, 0.01)
    np.testing.assert_allclose(np.asarray(a_corrected), np.asarray(b_corrected),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# fused quantize
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(1000,), (64, 33, 7), (2, 3), (4096,),
                                   (1920, 256)])   # rows not a tile multiple
@pytest.mark.parametrize("bin_size", [0.005, 0.1, 0.5])
def test_quantize_sweep(shape, bin_size):
    x = jax.random.normal(jax.random.fold_in(KEY, shape[0] + int(bin_size * 1e3)),
                          shape, jnp.float32)
    q, deq, err2 = qz_ops.quantize_fused(x, bin_size)
    qe, deqe, err2e = qz_ref.quantize_fused_ref(x, bin_size)
    # values landing exactly on a bin boundary may flip by one bin between
    # the kernel's true division and XLA's multiply-by-reciprocal — both are
    # valid round-to-nearest results within bin/2 of x.
    dq = np.abs(np.asarray(q, np.int64) - np.asarray(qe, np.int64))
    assert dq.max() <= 1 and (dq != 0).mean() < 1e-3
    np.testing.assert_allclose(np.asarray(deq), np.asarray(x),
                               atol=bin_size * 0.500001)
    assert float(np.max(err2)) <= (bin_size / 2) ** 2 * 1.0001


def test_quantize_matches_core_quantization():
    from repro.core.quantization import dequantize, quantize
    x = jax.random.normal(KEY, (257,), jnp.float32)
    q, deq, _ = qz_ops.quantize_fused(x, 0.01)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(quantize(x, 0.01)))
    np.testing.assert_allclose(np.asarray(deq),
                               np.asarray(dequantize(quantize(x, 0.01), 0.01)),
                               atol=1e-7)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (2, 64, 4, 16, 1, 8, 16),
    (1, 100, 2, 8, 2, 4, 32),     # ragged seq (padded path)
    (1, 128, 8, 32, 1, 16, 64),
    (3, 32, 2, 64, 2, 128, 16),   # fat state
])
def test_ssd_scan_sweep(b, s, h, p, g, n, chunk):
    ks = jax.random.split(jax.random.fold_in(KEY, s * h + p + n), 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    a_log = jax.random.uniform(ks[2], (h,), jnp.float32, 0.0, 1.0)
    bb = jax.random.normal(ks[3], (b, s, g, n), jnp.float32)
    cc = jax.random.normal(ks[4], (b, s, g, n), jnp.float32)
    y, st = ssd_ops.ssd(x, dt, a_log, bb, cc, chunk=chunk)
    pad = -s % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        bb = jnp.pad(bb, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cc = jnp.pad(cc, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ye, ste = ssd_ref.ssd_scan_ref(x, dt, a_log, bb, cc, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ye[:, :s]), atol=3e-4,
                               rtol=3e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(ste), atol=3e-4,
                               rtol=3e-4)


def test_ssd_scan_matches_model_ref():
    """Kernel oracle == the model's own ssd_ref (two independent paths)."""
    from repro.models.ssd import ssd_ref as model_ref
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (2, 64, 4, 16), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, 64, 4), jnp.float32))
    a_log = jax.random.uniform(ks[2], (4,), jnp.float32, 0.0, 1.0)
    bb = jax.random.normal(ks[3], (2, 64, 1, 8), jnp.float32)
    cc = jax.random.normal(ks[4], (2, 64, 1, 8), jnp.float32)
    y1, s1 = ssd_ops.ssd(x, dt, a_log, bb, cc, chunk=16)
    y2, s2 = model_ref(x, dt, a_log, bb, cc, chunk=16)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=3e-4, rtol=3e-4)


def test_ssd_decode_consistency_with_scan():
    """Step-by-step decode must reproduce the chunked scan's final state."""
    from repro.models.ssd import ssd_decode_step
    ks = jax.random.split(KEY, 5)
    b, s, h, p, n = 1, 16, 2, 8, 4
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h), jnp.float32))
    a_log = jax.random.uniform(ks[2], (h,), jnp.float32, 0.0, 1.0)
    bb = jax.random.normal(ks[3], (b, s, 1, n), jnp.float32)
    cc = jax.random.normal(ks[4], (b, s, 1, n), jnp.float32)
    _, st_scan = ssd_ops.ssd(x, dt, a_log, bb, cc, chunk=8)
    hstate = jnp.zeros((b, h, p, n), jnp.float32)
    ys = []
    for t in range(s):
        y, hstate = ssd_decode_step(hstate, x[:, t], dt[:, t], a_log,
                                    bb[:, t], cc[:, t])
        ys.append(y)
    np.testing.assert_allclose(np.asarray(hstate), np.asarray(st_scan),
                               atol=3e-4, rtol=3e-4)
    y_scan, _ = ssd_ops.ssd(x, dt, a_log, bb, cc, chunk=8)
    np.testing.assert_allclose(np.asarray(jnp.stack(ys, axis=1)),
                               np.asarray(y_scan), atol=3e-4, rtol=3e-4)
