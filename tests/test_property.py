"""Property-based tests (hypothesis) on the system's core invariants.

The paper's central claim is an INVARIANT, not a benchmark: after GAE
post-processing every block satisfies ||x - x^G||_2 <= tau, for any data, any
basis quality, any tau, any bin size.  These tests attack it with adversarial
inputs, plus the supporting algebraic invariants the pipeline relies on
(one-shot selection == Algorithm 1, quantization error bounds, bitstream
round-trips, blocking round-trips).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis_fallback import given, settings, st

from repro.core import entropy, gae
from repro.core.quantization import (dequantize, quantization_error_bound,
                                     quantize)

_sizes = st.tuples(st.integers(2, 24), st.integers(2, 48))   # (N blocks, D)


def _blocks(draw, n, d, scale):
    rng = np.random.default_rng(draw(st.integers(0, 2**31 - 1)))
    kind = draw(st.sampled_from(["gauss", "outliers", "lowrank", "const"]))
    if kind == "gauss":
        x = rng.standard_normal((n, d))
    elif kind == "outliers":
        x = rng.standard_normal((n, d))
        x[rng.integers(0, n, 3), rng.integers(0, d, 3)] *= 100.0
    elif kind == "lowrank":
        x = rng.standard_normal((n, 2)) @ rng.standard_normal((2, d))
    else:
        x = np.ones((n, d)) * rng.uniform(-5, 5)
    return (scale * x).astype(np.float32)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_gae_guarantee_holds_for_any_input(data):
    """THE invariant: per-block l2 error <= tau after GAE encode/decode."""
    n, d = data.draw(_sizes)
    x = _blocks(data.draw, n, d, scale=data.draw(st.floats(0.01, 10.0)))
    x_r = x + _blocks(data.draw, n, d, scale=0.3)       # bad reconstruction
    tau = data.draw(st.floats(0.05, 2.0))
    bin_size = data.draw(st.floats(1e-4, 0.5))
    basis = np.asarray(gae.fit_pca_basis(jnp.asarray(x - x_r)))
    out, codes = gae.gae_encode_blocks(x, x_r, basis, tau, bin_size)
    errs = np.linalg.norm(x - out, axis=1)
    assert errs.max() <= tau * (1 + 1e-5), (errs.max(), tau)
    # decode path reproduces the encoder's reconstruction exactly
    dec = gae.gae_decode_blocks(x_r, basis, codes,  bin_size)
    np.testing.assert_allclose(dec, out, atol=1e-5)


@settings(max_examples=15, deadline=None)
@given(st.data())
def test_one_shot_selection_matches_algorithm1(data):
    """gae_select (batched, branch-free) == the paper's serial Algorithm 1."""
    n, d = data.draw(st.tuples(st.integers(2, 12), st.integers(2, 24)))
    x = _blocks(data.draw, n, d, scale=1.0)
    x_r = x + _blocks(data.draw, n, d, scale=0.2)
    tau = data.draw(st.floats(0.1, 1.0))
    bin_size = data.draw(st.floats(1e-3, 0.05))
    basis = np.asarray(gae.fit_pca_basis(jnp.asarray(x - x_r)))
    ref_out, ref_ms = gae.gae_reference_loop(x, x_r, basis, tau, bin_size)
    r, u = jnp.asarray(x - x_r), jnp.asarray(basis)
    sel = gae.gae_select(r, u, tau, bin_size)
    _, sel_err = gae.gae_correct(r, u, sel, bin_size)
    # same minimal M wherever Algorithm 1 terminated within D
    m = np.asarray(sel.m)
    for i in range(n):
        if ref_ms[i] < d:
            assert m[i] == ref_ms[i], (i, m[i], ref_ms[i])
    ref_err = np.linalg.norm(x - ref_out, axis=1)
    sel_err = np.asarray(sel_err)
    np.testing.assert_allclose(sel_err, ref_err, atol=1e-4)


@settings(max_examples=50, deadline=None)
@given(st.floats(1e-4, 10.0), st.integers(1, 4096),
       st.floats(-1e4, 1e4))
def test_quantization_error_bound(bin_size, n, val):
    """|x - deq(q(x))| <= bin/2 + fp32 ulp slack elementwise.

    The exact-arithmetic bound is bin/2; in fp32, when |x|/bin > 2^24 the
    dequantized product q*bin itself rounds (ulp(|x|) error) — the GAE
    encoder is immune because it verifies REALIZED error, but the
    theoretical bound needs the ulp term."""
    x = jnp.full((n,), val, jnp.float32)
    err = jnp.abs(x - dequantize(quantize(x, bin_size), bin_size))
    ulp = abs(val) * 2.0 ** -23 * 4
    assert float(err.max()) <= bin_size * 0.5 + 1e-3 * bin_size + ulp
    assert float(jnp.linalg.norm(err)) <= \
        quantization_error_bound(bin_size, n) * (1 + 1e-3) + ulp * n ** 0.5


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(-5000, 5000), min_size=1, max_size=2000))
def test_huffman_roundtrip(values):
    arr = np.asarray(values, np.int64)
    stream = entropy.huffman_compress(arr)
    np.testing.assert_array_equal(entropy.huffman_decompress(stream), arr)


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_index_bitmask_roundtrip(data):
    dim = data.draw(st.integers(1, 200))
    n_sets = data.draw(st.integers(0, 20))
    sets = []
    for _ in range(n_sets):
        k = data.draw(st.integers(0, dim))
        idx = np.sort(np.random.default_rng(
            data.draw(st.integers(0, 1000))).permutation(dim)[:k]).astype(np.int32)
        sets.append(idx)
    blob = entropy.encode_index_sets(sets, dim)
    out = entropy.decode_index_sets(blob)
    assert len(out) == len(sets)
    for a, b in zip(sets, out):
        np.testing.assert_array_equal(np.sort(a), b)


@settings(max_examples=20, deadline=None)
@given(st.data())
def test_blocking_roundtrip_any_divisible_shape(data):
    from repro.data.blocks import block_nd, unblock_nd
    dims = data.draw(st.integers(1, 3))
    shape, bshape = [], []
    for _ in range(dims):
        b = data.draw(st.integers(1, 4))
        m = data.draw(st.integers(1, 4))
        shape.append(b * m)
        bshape.append(b)
    rng = np.random.default_rng(data.draw(st.integers(0, 1000)))
    x = rng.standard_normal(shape).astype(np.float32)
    blocks, meta = block_nd(x, bshape)
    np.testing.assert_array_equal(unblock_nd(blocks, meta), x)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 64))
def test_grad_compress_error_feedback_is_lossless_in_the_limit(seed, rank):
    """EF invariant: compressed + error buffer == input (exact split)."""
    from repro.runtime import grad_compress
    rng = np.random.default_rng(seed)
    g = {"w": jnp.asarray(rng.standard_normal((8, 96)).astype(np.float32))}
    st_ = grad_compress.init_state(g, block=32, rank=min(rank, 32))
    ghat, new_st, _ = grad_compress.compress_update(g, st_)
    # ghat + error == g exactly (up to fp) when bin_size == 0
    total = ghat["w"] + new_st.error["w"]
    np.testing.assert_allclose(np.asarray(total), np.asarray(g["w"]),
                               atol=1e-5)
