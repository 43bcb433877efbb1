"""Durable archive container: round-trip, digests, chunk-scoped degradation,
fault-injection containment, and the pickle-free model manifest."""
from __future__ import annotations

import os
import pickle

import numpy as np
import pytest

from repro.core import (ArchiveError, ChecksumMismatch, CompressorConfig,
                        HierarchicalCompressor, MalformedStream,
                        TruncatedArchive)
from repro.core.options import CompressOptions
from repro.runtime import archive_io, faultinject

TAU = 0.3
N_HB, K, D = 48, 2, 16
D_GAE = 16
GAE_PER_HB = (K * D) // D_GAE


@pytest.fixture(scope="module")
def fitted():
    rng = np.random.default_rng(0)
    base = rng.standard_normal((N_HB, 1, D)).astype(np.float32)
    hb = (base + 0.1 * rng.standard_normal((N_HB, K, D))).astype(np.float32)
    cfg = CompressorConfig(block_elems=D, k=K, emb=8, hidden=16, hb_latent=6,
                           bae_latent=4, epochs_hbae=3, epochs_bae=2, batch=16,
                           hb_bin=0.02, bae_bin=0.02, gae_bin=0.02,
                           gae_block_elems=D_GAE)
    comp = HierarchicalCompressor(cfg).fit(hb, seed=0)
    archive = comp.compress(
        hb, options=CompressOptions(tau=TAU, chunk_hyperblocks=16))
    return comp, hb, archive, archive_io.serialize_archive(archive)


def _block_errs(hb, recon):
    return np.linalg.norm((hb - recon).reshape(-1, D_GAE), axis=1)


def _intact_mask(report):
    mask = np.ones(N_HB * GAE_PER_HB, bool)
    for h in report.damaged_hyperblocks():
        mask[h * GAE_PER_HB:(h + 1) * GAE_PER_HB] = False
    return mask


# ---------------------------------------------------------------------------
# round-trip + accounting
# ---------------------------------------------------------------------------

def test_container_roundtrip_bitexact(fitted, tmp_path):
    comp, hb, archive, _ = fitted
    path = str(tmp_path / "a.rba")
    archive_io.write_archive(archive, path)
    back = archive_io.read_archive(path)
    np.testing.assert_array_equal(comp.decompress(back),
                                  comp.decompress(archive))
    assert _block_errs(hb, comp.decompress(back)).max() <= TAU * (1 + 1e-5)


def test_compressed_bytes_matches_disk(fitted, tmp_path):
    _, _, archive, blob = fitted
    path = str(tmp_path / "a.rba")
    written = archive_io.write_archive(archive, path)
    assert written == os.path.getsize(path) == len(blob)
    assert archive.compressed_bytes() == os.path.getsize(path)


def test_multi_chunk_striping(fitted):
    _, _, archive, _ = fitted
    assert len(archive.chunks) == 3          # 48 hyper-blocks / stripe 16
    assert [c.hb_start for c in archive.chunks] == [0, 16, 32]
    # every chunk decodes independently: its GAE section covers exactly its
    # own hyper-blocks' GAE blocks
    for c in archive.chunks:
        from repro.core import entropy
        sets = entropy.decode_index_sets(c.gae_index_blob,
                                         expect_dim=archive.gae_dim)
        assert len(sets) == c.n_hyperblocks * GAE_PER_HB


def test_tolerant_read_of_intact_archive_reports_clean(fitted):
    comp, _, _, blob = fitted
    archive = archive_io.deserialize_archive(blob, strict=False)
    recon, report = comp.decompress(archive, strict=False)
    assert report.ok and report.intact_fraction() == 1.0
    assert "intact" in report.summary()


# ---------------------------------------------------------------------------
# corruption: detected or survived, never a raw crash
# ---------------------------------------------------------------------------

def test_truncation_raises_typed(fitted):
    _, _, _, blob = fitted
    for cut in (0, 4, archive_io._PROLOGUE.size + 1, len(blob) // 2,
                len(blob) - 3):
        with pytest.raises(ArchiveError):
            archive_io.deserialize_archive(blob[:cut])


def test_bad_magic_and_version(fitted):
    _, _, _, blob = fitted
    with pytest.raises(MalformedStream):
        archive_io.deserialize_archive(b"NOTMAGIC" + blob[8:])
    bad_ver = blob[:8] + b"\xff\xff\xff\x7f" + blob[12:]
    with pytest.raises((MalformedStream, ChecksumMismatch)):
        archive_io.deserialize_archive(bad_ver)


def test_zero_chunk_graceful_degradation(fitted):
    comp, hb, _, blob = fitted
    # zero a span deep in the payload: damages some (not all) chunk sections
    pos = int(len(blob) * 0.6)
    bad = blob[:pos] + b"\x00" * 64 + blob[pos + 64:]
    with pytest.raises(ChecksumMismatch):
        archive_io.deserialize_archive(bad, strict=True)
    archive = archive_io.deserialize_archive(bad, strict=False)
    assert archive.chunk_errors                      # something was damaged
    recon, report = comp.decompress(archive, strict=False)
    assert not report.ok
    assert 0 < report.intact_fraction() < 1.0
    mask = _intact_mask(report)
    assert _block_errs(hb, recon)[mask].max() <= TAU * (1 + 1e-5)
    assert "damaged" in report.summary()


def test_strict_decompress_refuses_damaged_archive(fitted):
    comp, _, _, blob = fitted
    pos = int(len(blob) * 0.6)
    bad = blob[:pos] + b"\xff" * 16 + blob[pos + 16:]
    archive = archive_io.deserialize_archive(bad, strict=False)
    with pytest.raises(ArchiveError):
        comp.decompress(archive, strict=True)


def test_corruption_containment_property(fitted):
    """THE robustness invariant: for seeded bit-flips, truncations, zeroed
    spans and header fuzz, decode either raises a typed ArchiveError or
    returns a damage report under which every undamaged GAE block still meets
    the tau bound.  No raw struct/zlib/Index errors escape."""
    comp, hb, _, blob = fitted

    def decode(archive):
        recon, report = comp.decompress(archive, strict=False)
        mask = _intact_mask(report)
        if mask.any():
            assert _block_errs(hb, recon)[mask].max() <= TAU * (1 + 1e-5), \
                report.summary()

    result = faultinject.check_containment(blob, trials=48, seed=7,
                                           decode=decode)
    assert result.ok, result.summary()
    outcomes = {t.outcome for t in result.trials}
    assert "survived" in outcomes or "detected" in outcomes


def test_faultinject_cli(fitted, tmp_path, capsys):
    _, _, archive, _ = fitted
    path = str(tmp_path / "a.rba")
    archive_io.write_archive(archive, path)
    rc = faultinject.main([path, "--trials", "12", "--seed", "3"])
    assert rc == 0
    assert "OK" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# no pickle on the read path
# ---------------------------------------------------------------------------

def test_no_pickle_on_read_path(fitted, tmp_path, monkeypatch):
    comp, hb, archive, _ = fitted
    apath = str(tmp_path / "a.rba")
    mpath = str(tmp_path / "model.npz")
    archive_io.write_archive(archive, apath)
    comp.save(mpath)

    def boom(*a, **k):
        raise AssertionError("pickle used on the archive read path")

    monkeypatch.setattr(pickle, "load", boom)
    monkeypatch.setattr(pickle, "loads", boom)
    monkeypatch.setattr(pickle, "Unpickler", boom)
    back = archive_io.read_archive(apath)
    comp2 = HierarchicalCompressor.load(mpath)
    recon = comp2.decompress(back)
    assert _block_errs(hb, recon).max() <= TAU * (1 + 1e-5)


# ---------------------------------------------------------------------------
# model manifest + npz persistence
# ---------------------------------------------------------------------------

def test_model_save_load_roundtrip(fitted, tmp_path):
    comp, hb, archive, _ = fitted
    path = str(tmp_path / "model.npz")
    comp.save(path)
    comp2 = HierarchicalCompressor.load(path)
    assert comp2.cfg == comp.cfg
    np.testing.assert_allclose(comp2.decompress(archive),
                               comp.decompress(archive), atol=1e-6)
    # loadable with pickle hard-disabled at the numpy layer too
    np.load(path, allow_pickle=False).close()


def test_model_tamper_detected(fitted, tmp_path):
    comp, _, _, _ = fitted
    path = str(tmp_path / "model.npz")
    comp.save(path)
    data = dict(np.load(path, allow_pickle=False))
    key = next(k for k in data if k.startswith("t"))
    data[key] = data[key] + 1.0
    np.savez(path, **data)
    with pytest.raises(ChecksumMismatch):
        HierarchicalCompressor.load(path)


def test_legacy_pickle_model_rejected(fitted, tmp_path):
    path = str(tmp_path / "legacy.pkl")
    with open(path, "wb") as f:
        pickle.dump({"cfg": None}, f)
    with pytest.raises(MalformedStream):
        HierarchicalCompressor.load(path)


def test_atomic_write_failure_raises_after_retries(tmp_path):
    missing = str(tmp_path / "no" / "such" / "dir" / "f.rba")
    with pytest.raises(OSError):
        archive_io.atomic_write_bytes(missing, b"x", retries=1, backoff=0.001)
