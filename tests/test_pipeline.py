"""End-to-end compressor pipeline tests (paper Fig. 1 path)."""
import numpy as np
import pytest

from repro.core import CompressorConfig, HierarchicalCompressor
from repro.core.options import CompressOptions
from repro.data import blocks as blocks_mod
from repro.data import synthetic


@pytest.fixture(scope="module")
def s3d_small():
    # tiny S3D-like cube: 8 species, 10 steps, 16x16 grid
    data = synthetic.s3d_like(n_species=8, t=10, h=16, w=16, seed=0)
    norm = blocks_mod.Normalizer.fit(data, mode="range", axis=0)
    return norm.forward(data)


@pytest.fixture(scope="module")
def fitted(s3d_small):
    # block (8,5,4,4) like the paper (species,t,y,x); hyper-blocks of k=2
    blocks, meta = blocks_mod.block_nd(s3d_small, (8, 5, 4, 4))
    hb = blocks_mod.group_hyperblocks(blocks, k=2)
    cfg = CompressorConfig(block_elems=blocks.shape[1], k=2, emb=32, hidden=64,
                           hb_latent=16, bae_latent=8, gae_block_elems=80,
                           epochs_hbae=15, epochs_bae=10, batch=16,
                           hb_bin=0.01, bae_bin=0.01, gae_bin=0.02)
    comp = HierarchicalCompressor(cfg).fit(hb, seed=0)
    return comp, hb, blocks, meta


def test_blocking_roundtrip(s3d_small):
    blocks, meta = blocks_mod.block_nd(s3d_small, (8, 5, 4, 4))
    back = blocks_mod.unblock_nd(blocks, meta)
    np.testing.assert_array_equal(back, s3d_small)


def test_hyperblock_roundtrip(s3d_small):
    blocks, _ = blocks_mod.block_nd(s3d_small, (8, 5, 4, 4))
    hb = blocks_mod.group_hyperblocks(blocks, 2)
    np.testing.assert_array_equal(blocks_mod.ungroup_hyperblocks(hb), blocks)


def test_compress_decompress_roundtrip_no_gae(fitted):
    comp, hb, _, _ = fitted
    archive = comp.compress(hb, options=CompressOptions(tau=None))
    recon = comp.decompress(archive)
    assert recon.shape == hb.shape
    assert np.isfinite(recon).all()
    assert archive.compression_ratio() > 1.0


def test_gae_guarantee_end_to_end(fitted):
    comp, hb, _, _ = fitted
    tau = 0.25
    archive = comp.compress(hb, options=CompressOptions(tau=tau))
    recon = comp.decompress(archive)
    d_gae = comp.cfg.gae_block_elems
    x = hb.reshape(-1, d_gae)
    r = recon.reshape(-1, d_gae)
    errs = np.linalg.norm(x - r, axis=1)
    assert np.all(errs <= tau + 1e-4), errs.max()


def test_tighter_tau_costs_more_bytes(fitted):
    comp, hb, _, _ = fitted
    loose = comp.compress(hb, options=CompressOptions(tau=0.5))
    tight = comp.compress(hb, options=CompressOptions(tau=0.05))
    assert tight.compressed_bytes() > loose.compressed_bytes()


def test_archive_accounting(fitted):
    comp, hb, _, _ = fitted
    archive = comp.compress(hb, options=CompressOptions(tau=0.25))
    assert archive.n_values == hb.size
    assert archive.compressed_bytes() > 0
    assert archive.compression_ratio(include_model_bytes=comp.model_bytes()) < \
        archive.compression_ratio()


def test_save_load_roundtrip(fitted, tmp_path):
    comp, hb, _, _ = fitted
    p = str(tmp_path / "comp.pkl")
    comp.save(p)
    comp2 = HierarchicalCompressor.load(p)
    a1 = comp.compress(hb, options=CompressOptions(tau=0.25))
    a2 = comp2.compress(hb, options=CompressOptions(tau=0.25))
    np.testing.assert_allclose(comp.decompress(a1), comp2.decompress(a2),
                               atol=1e-6)


def test_normalizer_roundtrip():
    data = synthetic.e3sm_like(t=12, h=16, w=32, seed=1)
    nz = blocks_mod.Normalizer.fit(data, mode="zscore")
    np.testing.assert_allclose(nz.inverse(nz.forward(data)), data, rtol=1e-4,
                               atol=1e-3)


def test_fourier_field_independent_of_worker_count(monkeypatch):
    # 20 time steps of 256x512 sum in three slabs on the codec pool
    fields = []
    for workers in ("1", "4"):
        monkeypatch.setenv("REPRO_CODEC_WORKERS", workers)
        fields.append(synthetic.e3sm_like(t=20, h=256, w=512, seed=3))
    np.testing.assert_array_equal(fields[0], fields[1])
