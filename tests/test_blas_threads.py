"""The GAE correction product runs on one BLAS thread per process
(``exec.single_blas_thread``): the codec pool is the parallelism, and the
product's floats do not depend on the worker count or the host's cores."""
import sys

import numpy as np
import pytest

import jax

from repro.core import CompressorConfig, HierarchicalCompressor
from repro.core import bae as bae_mod
from repro.core import exec as exec_mod
from repro.core import gae
from repro.core import hbae as hbae_mod
from repro.core.options import CompressOptions
from repro.runtime import archive_io


def _require_blas():
    exec_mod.single_blas_thread()
    if exec_mod.blas_threads() == 0:
        pytest.skip("no BLAS library of numpy's was found to limit")


@pytest.fixture(scope="module")
def comp_hb():
    cfg = CompressorConfig(block_elems=40, k=2, emb=16, hidden=32, hb_latent=8,
                           bae_hidden=32, bae_latent=4, gae_block_elems=80,
                           hb_bin=0.01, bae_bin=0.01, gae_bin=0.02)
    comp = HierarchicalCompressor(cfg)
    khb, kb = jax.random.split(jax.random.PRNGKey(0))
    comp.hbae_params = hbae_mod.hbae_init(
        khb, in_dim=cfg.block_elems, k=cfg.k, emb=cfg.emb, hidden=cfg.hidden,
        latent=cfg.hb_latent, heads=cfg.heads)
    comp.bae_params = [bae_mod.bae_init(kb, in_dim=cfg.block_elems,
                                        hidden=cfg.bae_hidden,
                                        latent=cfg.bae_latent)]
    rng = np.random.default_rng(0)
    hb = 0.1 * rng.standard_normal((24, cfg.k, cfg.block_elems)
                                   ).astype(np.float32)
    comp.fit_basis(hb)
    return comp, hb


def _dense_codes(rng, n, d):
    """Every block keeps all ``d`` coefficients: the widest product."""
    ms = np.full(n, d, np.int64)
    cols = np.tile(np.arange(d, dtype=np.int32), n)
    qs = rng.integers(-200, 200, n * d).astype(np.int64)
    return ms, cols, qs, np.zeros(n, np.int64)


def test_compress_holds_one_blas_thread_at_any_worker_count(comp_hb,
                                                            monkeypatch):
    _require_blas()
    comp, hb = comp_hb
    blobs = []
    for workers in ("1", "4"):
        monkeypatch.setenv("REPRO_CODEC_WORKERS", workers)
        exec_mod.reset_pool()
        exec_mod.reset_stage_stats()
        try:
            archive = comp.compress(hb, options=CompressOptions(
                tau=0.5, chunk_hyperblocks=6))          # 4 stripes
        finally:
            exec_mod.reset_pool()
        assert exec_mod.blas_threads() == 1
        counts = exec_mod.counters()
        assert counts["gae.apply_calls"] >= 4
        assert counts["gae.apply_blas_threads"] == counts["gae.apply_calls"]
        blobs.append(archive_io.serialize_archive(archive))
    assert blobs[0] == blobs[1]
    exec_mod.reset_stage_stats()


def test_apply_codes_same_bits_on_the_main_thread_and_four_workers(
        monkeypatch):
    # XGC's GAE width: (512 x 1521) @ (1521 x 1521), the shape at which a
    # multi-threaded sgemm rounds differently from a single-threaded one
    _require_blas()
    n, d = 512, 1521
    rng = np.random.default_rng(1)
    u = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    x_r = rng.standard_normal((n, d)).astype(np.float32)
    codes = _dense_codes(rng, n, d)
    want = gae._apply_codes(x_r, u, *codes, 0.01).tobytes()
    monkeypatch.setenv("REPRO_CODEC_WORKERS", "4")
    exec_mod.reset_pool()
    try:
        got = exec_mod.map_parallel(
            lambda _: gae._apply_codes(x_r, u, *codes, 0.01).tobytes(),
            range(4))
    finally:
        exec_mod.reset_pool()
    assert got == [want] * 4
    assert exec_mod.blas_threads() == 1


@pytest.mark.parametrize("finder", ["threadpoolctl", "ctypes"])
def test_single_blas_thread_limits_numpy_blas(finder, monkeypatch):
    threadpoolctl = pytest.importorskip("threadpoolctl")
    if finder == "ctypes":
        if not sys.platform.startswith("linux"):
            pytest.skip("the ctypes finder reads Linux's /proc/self/maps")
        monkeypatch.setitem(sys.modules, "threadpoolctl", None)
    if not threadpoolctl.threadpool_info():
        pytest.skip("no BLAS library of numpy's was found to limit")
    # start from two threads, so that only the helper can bring it to one
    with threadpoolctl.threadpool_limits(2, user_api="blas"):
        monkeypatch.setattr(exec_mod, "_BLAS_READERS", None)
        exec_mod.single_blas_thread()
        assert exec_mod._BLAS_READERS
        assert exec_mod.blas_threads() == 1
        exec_mod.single_blas_thread()               # once per process
        assert exec_mod.blas_threads() == 1


def test_no_blas_library_found_is_a_noop_that_the_counters_show(monkeypatch):
    def not_found(*args, **kwargs):
        raise OSError("no such library")
    monkeypatch.setitem(sys.modules, "threadpoolctl", None)
    monkeypatch.setattr(exec_mod.ctypes, "CDLL", not_found)
    monkeypatch.setattr(exec_mod, "_BLAS_READERS", None)
    exec_mod.single_blas_thread()
    assert exec_mod._BLAS_READERS == []
    assert exec_mod.blas_threads() == 0

    rng = np.random.default_rng(2)
    n, d = 6, 16
    u = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    x_r = rng.standard_normal((n, d)).astype(np.float32)
    ms, cols, qs, bin_exps = _dense_codes(rng, n, d)
    exec_mod.reset_stage_stats()
    out = gae._apply_codes(x_r, u, ms, cols, qs, bin_exps, 0.01)
    coeffs = (qs.reshape(n, d) * np.float32(0.01)).astype(np.float32)
    np.testing.assert_allclose(out, x_r + coeffs @ u.T, rtol=1e-6, atol=1e-6)
    counts = exec_mod.counters()
    assert counts["gae.apply_calls"] == 1
    assert counts["gae.apply_blas_threads"] == 0
    exec_mod.reset_stage_stats()
