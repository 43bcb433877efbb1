"""Unit tests for the paper's core: HBAE, BAE, GAE (Algorithm 1 equivalence)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bae as bae_mod
from repro.core import gae
from repro.core import hbae as hbae_mod
from repro.core.attention import attention_block, attention_block_init


def test_attention_block_shapes_and_residual():
    key = jax.random.PRNGKey(0)
    params = attention_block_init(key, d=32, heads=1)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 7, 32))
    y = attention_block(params, x)
    assert y.shape == x.shape
    # with zeroed value/out projections the block must reduce to identity
    params2 = jax.tree.map(lambda a: jnp.zeros_like(a) if hasattr(a, "shape") else a,
                           params)
    y2 = attention_block(params2, x)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(x), atol=1e-6)


def test_attention_multihead_matches_singlehead_dims():
    key = jax.random.PRNGKey(0)
    params = attention_block_init(key, d=64, heads=4)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 10, 64))
    assert attention_block(params, x).shape == (3, 10, 64)


def test_hbae_roundtrip_shapes():
    key = jax.random.PRNGKey(0)
    p = hbae_mod.hbae_init(key, in_dim=80, k=10, emb=32, hidden=64, latent=24)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 10, 80))
    y, lat = hbae_mod.hbae_apply(p, x)
    assert y.shape == (6, 10, 80)
    assert lat.shape == (6, 24)


def test_hbae_no_attention_variant():
    key = jax.random.PRNGKey(0)
    p = hbae_mod.hbae_init(key, in_dim=16, k=4, emb=8, hidden=16, latent=8,
                           use_attention=False)
    assert "enc_attn" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 4, 16))
    y, _ = hbae_mod.hbae_apply(p, x)
    assert y.shape == x.shape


def test_hbae_trains_under_jit():
    from repro.core import training
    rng = np.random.default_rng(0)
    # rank-4 data (4 < latent 8): compressible, so the AE must beat the mean
    lat = rng.standard_normal((32, 1, 4)).astype(np.float32)
    mix = rng.standard_normal((4, 20)).astype(np.float32)
    data = np.tile(lat @ mix, (1, 4, 1)) + 0.01 * rng.standard_normal((32, 4, 20)).astype(np.float32)
    p = training.train_hbae(jax.random.PRNGKey(0), data, emb=16, hidden=32,
                            latent=8, epochs=120, batch=16)
    y, _ = hbae_mod.hbae_apply(p, jnp.asarray(data))
    mse = float(jnp.mean(jnp.square(y - data)))
    assert mse < float(np.var(data)) * 0.5, mse  # beats predicting the mean


def test_bae_roundtrip_shapes():
    p = bae_mod.bae_init(jax.random.PRNGKey(0), in_dim=80, hidden=64, latent=16)
    r = jax.random.normal(jax.random.PRNGKey(1), (12, 80)) * 0.01
    r_hat, lb = bae_mod.bae_apply(p, r)
    assert r_hat.shape == (12, 80) and lb.shape == (12, 16)


# ---------------------------------------------------------------------------
# GAE
# ---------------------------------------------------------------------------

def _setup_gae(n=40, d=24, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    x_r = x + 0.3 * rng.standard_normal((n, d)).astype(np.float32)
    basis = np.asarray(gae.fit_pca_basis(jnp.asarray(x - x_r)))
    return x, x_r, basis


def test_pca_basis_orthonormal():
    _, _, basis = _setup_gae()
    np.testing.assert_allclose(basis.T @ basis, np.eye(basis.shape[0]),
                               atol=1e-4)


def test_gae_select_matches_reference_loop():
    x, x_r, basis = _setup_gae()
    tau, bin_size = 0.8, 0.01
    r, u = jnp.asarray(x - x_r), jnp.asarray(basis)
    sel = gae.gae_select(r, u, tau, bin_size)
    corrected, _ = gae.gae_correct(r, u, sel, bin_size)
    ref_out, ref_ms = gae.gae_reference_loop(x, x_r, basis, tau, bin_size)
    np.testing.assert_array_equal(np.asarray(sel.m), np.asarray(ref_ms))
    np.testing.assert_allclose(x_r + np.asarray(corrected), ref_out,
                               atol=1e-4)


def _decaying_residuals(d, tau, n=12):
    """Orthonormal (d, d) basis and residuals whose coefficients decay over
    it (a dozen or so per block above tau); a quarter of the blocks are
    already within tau, and block 0 is zero, so every magnitude ties."""
    rng = np.random.default_rng(d)
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    c = 2 * tau * rng.standard_normal((n, d)) * np.exp(-np.arange(d) / 8)
    c[:n // 4] *= 0.1
    c[0] = 0.0
    return (c @ basis.T).astype(np.float32), basis


@pytest.mark.parametrize("d, tau, bin_size", [
    (80, 0.0447, 0.01), (256, 0.2, 0.02), (1521, 0.4875, 0.05),
], ids=["s3d", "e3sm", "xgc"])
def test_gae_select_codes_match_numpy_twin(d, tau, bin_size):
    """At each cell's GAE width, tau and bin, the jitted selection's codes
    equal, bit for bit, a numpy twin that sorts by magnitude and gathers the
    coefficients back by the order; its program sorts once and gathers
    nothing."""
    r, basis = _decaying_residuals(d, tau)
    select = jax.jit(gae.gae_select)
    sel = select(r, basis, tau, bin_size)

    c = np.asarray(jnp.asarray(r) @ jnp.asarray(basis))   # the same projection
    c2 = np.square(c)
    order = np.argsort(-c2, axis=1, kind="stable")
    c_sorted = np.take_along_axis(c, order, 1)
    c2_sorted = np.take_along_axis(c2, order, 1)
    q_sorted = np.round(c_sorted / np.float32(bin_size)).astype(np.int32)
    deq = q_sorted.astype(np.float32) * np.float32(bin_size)
    total = c2_sorted.sum(axis=1, keepdims=True, dtype=np.float32)
    err2 = np.concatenate([total, total - np.cumsum(c2_sorted, axis=1)
                           + np.cumsum(np.square(c_sorted - deq), axis=1)],
                          axis=1)
    ok_any = err2 <= np.float32(tau) * np.float32(tau)
    m = np.where(ok_any.any(axis=1), ok_any.argmax(axis=1), d)

    np.testing.assert_array_equal(np.asarray(sel.order), order)
    np.testing.assert_array_equal(np.asarray(sel.q_sorted), q_sorted)
    np.testing.assert_array_equal(np.asarray(sel.m), m)
    assert (m == 0).any() and ((m > 0) & (m < d)).any()
    np.testing.assert_array_equal(order[0], np.arange(d))   # ties keep index order

    text = select.lower(r, basis, tau, bin_size).as_text()
    assert text.count("stablehlo.sort") == 1
    assert "gather" not in text


def test_gae_correct_matches_unpermute_and_matmul():
    """``gae_correct``'s sort keyed on ``order`` gives, bit for bit, what
    the inverse-permutation gather and basis product gave."""
    rng = np.random.default_rng(7)
    n, d, bin_size = 64, 96, 0.01
    r = rng.standard_normal((n, d)).astype(np.float32)
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    order = np.argsort(rng.random((n, d)), axis=1).astype(np.int32)
    sel = gae.GAESelection(
        m=jnp.asarray(rng.integers(0, d + 1, n), jnp.int32),
        order=jnp.asarray(order),
        q_sorted=jnp.asarray(rng.integers(-300, 300, (n, d)), jnp.int32),
        ok=jnp.ones(n, bool))

    @jax.jit
    def unpermute_and_matmul(r, basis, sel):
        keep = jnp.arange(d)[None, :] < sel.m[:, None]
        deq_masked = jnp.where(keep, sel.q_sorted.astype(jnp.float32)
                               * bin_size, 0.0)
        inv_order = jnp.argsort(sel.order, axis=-1)
        c_hat = jnp.take_along_axis(deq_masked, inv_order, axis=-1)
        corrected = c_hat @ basis.T
        return corrected, jnp.linalg.norm(r - corrected, axis=-1)

    corrected, err = gae.gae_correct(r, basis, sel, bin_size)
    want_corrected, want_err = unpermute_and_matmul(r, basis, sel)
    np.testing.assert_array_equal(np.asarray(corrected),
                                  np.asarray(want_corrected))
    np.testing.assert_array_equal(np.asarray(err), np.asarray(want_err))


def test_gae_select_zero_m_for_small_residuals():
    x, x_r, basis = _setup_gae()
    sel = gae.gae_select(jnp.asarray(x - x_r), jnp.asarray(basis), tau=1e9,
                         bin_size=0.01)
    assert int(np.asarray(sel.m).max()) == 0


def test_gae_encode_blocks_hard_bound_and_roundtrip():
    x, x_r, basis = _setup_gae()
    tau, bin_size = 0.5, 0.02
    out, codes = gae.gae_encode_blocks(x, x_r, basis, tau, bin_size)
    errs = np.linalg.norm(x - out, axis=1)
    assert np.all(errs <= tau + 1e-5), errs.max()
    dec = gae.gae_decode_blocks(x_r, basis, codes, bin_size)
    np.testing.assert_allclose(dec, out, atol=1e-5)


def test_gae_encode_blocks_coarse_bin_fallback():
    # bin so coarse the global size can never satisfy tau without refinement
    x, x_r, basis = _setup_gae()
    tau = 0.05
    out, codes = gae.gae_encode_blocks(x, x_r, basis, tau, bin_size=10.0)
    errs = np.linalg.norm(x - out, axis=1)
    assert np.all(errs <= tau + 1e-5)
    assert any(c.bin_exp > 0 for c in codes)


@pytest.mark.parametrize("d, tau, bin_size", [
    (80, 0.0447, 0.01), (256, 0.2, 0.02), (1521, 0.4875, 0.05),
], ids=["s3d", "e3sm", "xgc"])
def test_gae_encode_blocks_matches_reference_at_cell_widths(d, tau,
                                                            bin_size):
    """At each benchmark cell's GAE width, tau and bin, the encoder (jitted
    selection, then verify and repair) gives every block the repair left
    alone the M of the paper's Algorithm 1, and decodes within tau."""
    from repro.core import exec as exec_mod
    rng = np.random.default_rng(d)
    n = 12
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    # residual spectra decaying over the basis (a dozen or so coefficients
    # per block); a quarter of the blocks are already within tau
    c = 2 * tau * rng.standard_normal((n, d)) * np.exp(-np.arange(d) / 8)
    c[:n // 4] *= 0.1
    x_r = rng.standard_normal((n, d)).astype(np.float32)
    x = (x_r + c @ basis.T).astype(np.float32)

    exec_mod.reset_stage_stats()
    _, codes = gae.gae_encode_blocks(x, x_r, basis, tau, bin_size)
    repaired = exec_mod.counters().get("gae.repaired_blocks", 0)
    select = exec_mod.cache().get("gae_select", gae.gae_select,
                                  static_argnames=("use_kernel",))
    sel_m = np.asarray(select(x - x_r, basis, tau, bin_size).m)
    ms = np.array([code.m for code in codes])
    # a repair round raises a block's M or its bin exponent
    untouched = (ms == sel_m) & np.array([code.bin_exp == 0
                                          for code in codes])
    assert (~untouched).sum() == repaired
    assert untouched.sum() >= n // 2
    _, ref_ms = gae.gae_reference_loop(x, x_r, basis, tau, bin_size)
    np.testing.assert_array_equal(ms[untouched], np.asarray(ref_ms)[untouched])
    assert (ms == 0).any() and (ms > 0).any()

    dec = gae.gae_decode_blocks(x_r, basis, codes, bin_size)
    errs = np.linalg.norm(x - dec, axis=1)
    assert errs.max() <= tau, errs.max()
    exec_mod.reset_stage_stats()


@pytest.mark.parametrize("operand_dtype", [jnp.float32, jnp.bfloat16])
def test_gae_encode_device_branch_holds_tau_on_decode(monkeypatch,
                                                      operand_dtype):
    """The encoder selects with the jitted ``gae_select``, whose matmuls a
    TPU may run with bf16 operands at default precision.  Run it (bf16 case:
    with the selection's operands rounded as such a pass rounds them) and
    check every block the decoder rebuilds from the emitted codes against
    tau, with no slack."""
    from repro.core import exec as exec_mod
    rng = np.random.default_rng(5)
    n, d, tau, bin_size = 2048, 256, 0.5, 0.01
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    x_r = x + 0.05 * rng.standard_normal((n, d)).astype(np.float32)

    exact_select = gae.gae_select

    def select_at_operand_precision(residuals, u, tau, bin_size,
                                    use_kernel=False):
        def rd(a):
            return a.astype(operand_dtype).astype(jnp.float32)
        return exact_select(rd(residuals), rd(u), tau, bin_size)

    monkeypatch.setattr(exec_mod, "_CACHE", exec_mod.JitCache())
    monkeypatch.setattr(gae, "gae_select", select_at_operand_precision)
    out, codes = gae.gae_encode_blocks(x, x_r, basis, tau, bin_size)
    assert exec_mod.retrace_counts() == {"gae_select": 1}   # jitted select
    dec = gae.gae_decode_blocks(x_r, basis, codes, bin_size)
    np.testing.assert_array_equal(dec, out)
    errs = np.linalg.norm(x - dec, axis=1)
    assert errs.max() <= tau, (int((errs > tau).sum()), errs.max())
    assert all(c.m > 0 for c in codes)
