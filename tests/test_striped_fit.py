"""``fit`` and ``fit_basis`` run stripe by stripe: the striped BAE training
residual and the striped residual covariance equal their whole-array forms,
the bases span the same subspaces, no program of either sees more than a
stripe, and a traced set-up shows their spans and counter.  The mesh form
(covariances ``psum``-ed over shard groups) is checked under four devices
by ``repro.parallel.mesh_check`` (``psum_basis_consistent``), which
``tests/test_mesh_exec.py`` runs."""
import dataclasses
import glob
import os

import numpy as np
import pytest

import jax

from repro.core import CompressorConfig, HierarchicalCompressor
from repro.core import bae as bae_mod
from repro.core import exec as exec_mod
from repro.core import gae
from repro.core import hbae as hbae_mod
from repro.core import pipeline
from repro.core import training

CFG = CompressorConfig(block_elems=40, k=2, emb=16, hidden=32, hb_latent=8,
                       bae_hidden=32, bae_latent=4, gae_block_elems=20,
                       hb_bin=0.01, bae_bin=0.01, gae_bin=0.02,
                       epochs_hbae=1, epochs_bae=1, batch=8)
#: hyper-blocks: 200 GAE blocks of 20 values, a full-rank covariance
N_HB = 50


def _field(seed: int = 0) -> np.ndarray:
    rng = np.random.default_rng(seed)
    return 0.1 * rng.standard_normal(
        (N_HB, CFG.k, CFG.block_elems)).astype(np.float32)


@pytest.fixture(scope="module")
def comp_hb():
    """An untrained compressor with seeded random weights."""
    comp = HierarchicalCompressor(CFG)
    khb, kb = jax.random.split(jax.random.PRNGKey(3))
    comp.hbae_params = hbae_mod.hbae_init(
        khb, in_dim=CFG.block_elems, k=CFG.k, emb=CFG.emb, hidden=CFG.hidden,
        latent=CFG.hb_latent, heads=CFG.heads)
    comp.bae_params = [bae_mod.bae_init(kb, in_dim=CFG.block_elems,
                                        hidden=CFG.bae_hidden,
                                        latent=CFG.bae_latent)]
    return comp, _field()


def _whole_residuals(comp, hb) -> np.ndarray:
    """GAE-block residuals of the whole field through one program call."""
    _, _, recon = exec_mod.run_compress_stage(
        comp.hbae_params, comp.bae_params, hb, CFG.hb_bin, CFG.bae_bin)
    return (hb - recon).reshape(-1, CFG.gae_block_elems)


@pytest.mark.parametrize("width", [4, 7, 64])
def test_striped_covariance_equals_whole_array(comp_hb, width):
    comp, hb = comp_hb
    cov = comp.residual_covariance(hb, chunk_hyperblocks=width)
    r = _whole_residuals(comp, hb).astype(np.float64)
    want = r.T @ r
    assert cov.dtype == np.float32 and cov.shape == want.shape
    np.testing.assert_allclose(cov, want, rtol=1e-5,
                               atol=1e-6 * np.abs(want).max())


@pytest.mark.parametrize("width", [4, 7])
def test_striped_basis_spans_the_whole_array_subspaces(comp_hb, width):
    comp, hb = comp_hb
    striped = comp.fit_basis(hb, chunk_hyperblocks=width)
    whole = np.asarray(gae.fit_pca_basis(_whole_residuals(comp, hb)))
    np.testing.assert_allclose(striped.T @ striped, np.eye(20), atol=1e-5)
    # the projector onto the leading m components, for every m
    for m in range(1, 20):
        p_s = striped[:, :m] @ striped[:, :m].T
        p_w = whole[:, :m] @ whole[:, :m].T
        assert np.abs(p_s - p_w).max() < 1e-3, m


def _bae_inputs(monkeypatch, cfg, hb, width) -> tuple[HierarchicalCompressor,
                                                       list[np.ndarray]]:
    """Fit at ``width`` and keep the residual each BAE stage trained on."""
    seen = []
    train = training.train_bae

    def keep(key, residuals, **kwargs):
        seen.append(np.array(residuals))
        return train(key, residuals, **kwargs)
    monkeypatch.setattr(training, "train_bae", keep)
    monkeypatch.setattr(pipeline, "STRIPE_HYPERBLOCKS", width)
    comp = HierarchicalCompressor(cfg).fit(hb, seed=1)
    return comp, seen


@pytest.mark.parametrize("width", [4, 64])
def test_striped_fit_forward_equals_whole_array(monkeypatch, width):
    cfg = dataclasses.replace(CFG, n_bae_stages=2)
    hb = _field(1)
    comp, seen = _bae_inputs(monkeypatch, cfg, hb, width)
    assert len(seen) == 2
    y, _ = hbae_mod.hbae_apply(comp.hbae_params, hb)
    first = (hb - np.asarray(y)).reshape(-1, cfg.block_elems)
    np.testing.assert_allclose(seen[0], first, rtol=1e-6, atol=1e-7)
    r_hat, _ = bae_mod.bae_apply(comp.bae_params[0], first)
    np.testing.assert_allclose(seen[1], first - np.asarray(r_hat),
                               rtol=1e-6, atol=1e-7)


def test_fit_and_basis_programs_see_one_stripe_at_a_time(monkeypatch):
    """Every upload of ``fit``'s forwards and ``fit_basis`` is one stripe
    (the training steps gather from the field they hold on the device)."""
    hb = _field(2)
    shapes = []
    upload = exec_mod.to_device

    def record(a, sharding=None):
        shapes.append(np.shape(a))
        return upload(a, sharding)
    monkeypatch.setattr(exec_mod, "to_device", record)
    monkeypatch.setattr(pipeline, "STRIPE_HYPERBLOCKS", 4)
    comp = HierarchicalCompressor(CFG).fit(hb)
    comp.fit_basis(hb, chunk_hyperblocks=4)
    stripes = [s for s in shapes if len(s) == 3]
    assert stripes and max(s[0] for s in stripes) == 4
    assert all(s[0] <= 4 for s in shapes if s != (20, 20))


@pytest.mark.parametrize("shape", [(3, 40), (1521,), (1536,), (10, 130)])
def test_training_batches_equal_indexing_the_data(shape):
    """The training loops hold their data as lane-padded rows; a batch
    gathered from them holds the values of indexing the data itself."""
    rng = np.random.default_rng(4)
    data = rng.standard_normal((20,) + shape).astype(np.float32)
    rows = training._device_rows(data, shape[-1])
    assert rows.shape[1] % training.LANES == 0
    idx = np.array([7, 0, 19, 3, 3])
    batch = training._batch(rows, idx, shape)
    assert np.array_equal(np.asarray(batch), data[idx])


def test_traced_set_up_shows_fit_spans_and_stripes(tmp_path, monkeypatch):
    hb = _field(3)
    monkeypatch.setattr(pipeline, "STRIPE_HYPERBLOCKS", 16)
    exec_mod.reset_stage_stats()
    with jax.profiler.trace(str(tmp_path)):
        comp = HierarchicalCompressor(CFG).fit(hb)
        comp.fit_basis(hb, chunk_hyperblocks=16)
    stats = exec_mod.stage_stats()
    assert stats["fit_forward"].calls == 1
    assert stats["fit_forward"].values == hb.size
    assert stats["basis_fit"].calls == 1
    # 50 hyper-blocks in stripes of 16: 4 for the forward, 4 for the basis
    assert exec_mod.counters()["fit.stripes"] == 8
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    names = {e.name for plane in jax.profiler.ProfileData.from_file(
        path).planes for line in plane.lines for e in line.events}
    assert {"repro/fit_forward", "repro/basis_fit"} <= names
    exec_mod.reset_stage_stats()
