"""The program's spans and counters: ``exec.stage``'s on-CPU time and
profiler annotations, named codec threads and programs, the GAE and
host<->device transfer counters, and that none of it changes an archive."""
import glob
import os
import sys
import time

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


@pytest.fixture(scope="module")
def comp_hb():
    """An untrained compressor and 24 hyper-blocks: stripes of 10, 10, 4."""
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


OPTS = CompressOptions(tau=0.5, chunk_hyperblocks=10)


def _spin(seconds: float) -> None:
    end = time.perf_counter() + seconds
    while time.perf_counter() < end:
        pass


# ---------------------------------------------------------------------------
# exec.stage
# ---------------------------------------------------------------------------

def test_stage_records_cpu_seconds():
    exec_mod.reset_stage_stats()
    with exec_mod.stage("busy", 10):
        _spin(0.05)
    with exec_mod.stage("asleep", 10):
        time.sleep(0.05)
    busy = exec_mod.stage_stats()["busy"]
    asleep = exec_mod.stage_stats()["asleep"]
    assert 0.02 < busy.cpu_seconds <= busy.seconds + 1e-3
    assert asleep.cpu_seconds < 0.5 * asleep.seconds
    assert asleep.oncpu_share() < busy.oncpu_share()
    exec_mod.record_stage("folded", 2.0, 5, cpu_seconds=0.5)
    assert exec_mod.stage_stats()["folded"].oncpu_share() == 0.25
    assert "25% on CPU" in exec_mod.stats_summary()
    exec_mod.reset_stage_stats()


@pytest.mark.skipif(not sys.platform.startswith("linux"),
                    reason="threads get OS names through Linux's prctl")
def test_pool_stage_is_an_annotation_on_a_named_codec_thread(tmp_path,
                                                            monkeypatch):
    monkeypatch.setenv("REPRO_CODEC_WORKERS", "2")
    exec_mod.reset_pool()

    def work(i):
        with exec_mod.stage("probe_stage", 1):
            _spin(0.02)
        return i

    try:
        with jax.profiler.trace(str(tmp_path)):
            assert exec_mod.map_parallel(work, range(4)) == [0, 1, 2, 3]
    finally:
        exec_mod.reset_pool()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                n = sum(e.name == "repro/probe_stage" for e in line.events)
                if n:
                    lines[line.name] = lines.get(line.name, 0) + n
    assert sum(lines.values()) == 4
    assert all(name.startswith("repro-codec_") for name in lines), lines


def test_programs_are_named_after_their_cache_key():
    lowered = exec_mod.JitCache().get(
        "gae_select", gae.gae_select, static_argnames=("use_kernel",)).lower(
        np.zeros((8, 16), np.float32), np.eye(16, dtype=np.float32), 0.1,
        0.01)
    assert lowered.as_text().splitlines()[0].startswith(
        "module @jit_gae_select ")


@pytest.mark.parametrize("key, name", [
    ("encode_frontend", "encode_frontend"),
    ("decode_backend@hb4", "decode_backend_hb4"),
    ("a-b.c d", "a_b_c_d"),
])
def test_program_name_sanitizes_the_key(key, name):
    assert exec_mod.program_name(key) == name


# ---------------------------------------------------------------------------
# GAE spans and counters
# ---------------------------------------------------------------------------

def _gae_case(seed: int, n: int, d: int, noise: float):
    rng = np.random.default_rng(seed)
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    x = rng.standard_normal((n, d)).astype(np.float32)
    x_r = x + noise * rng.standard_normal((n, d)).astype(np.float32)
    return x, x_r, basis


@pytest.mark.parametrize("bin_size, repaired", [(0.5, True), (1e-4, False)])
def test_gae_counters_and_stages(bin_size, repaired):
    x, x_r, basis = _gae_case(1, 20, 32, 0.3)
    exec_mod.reset_stage_stats()
    _, codes = gae.gae_encode_blocks(x, x_r, basis, tau=0.05,
                                     bin_size=bin_size)
    counts = exec_mod.counters()
    stats = exec_mod.stage_stats()
    assert counts["gae.blocks"] == 20
    touched = sum(c.bin_exp > 0 for c in codes)
    if repaired:
        # a coarse bin sends blocks through the repair rounds
        assert touched and counts["gae.repaired_blocks"] >= touched
        assert counts["gae.repair_rounds"] >= 1
    else:
        assert counts["gae.repaired_blocks"] == 0 == touched
        assert counts["gae.repair_rounds"] == 0
    for name in ("gae_select", "gae_verify"):
        assert stats[name].calls == 1 and stats[name].values == x.size
    exec_mod.reset_stage_stats()


def test_gae_device_branch_counts_its_transfers(monkeypatch):
    x, x_r, basis = _gae_case(2, 12, 16, 0.05)
    monkeypatch.setattr(exec_mod, "_CACHE", exec_mod.JitCache())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    exec_mod.reset_stage_stats()
    gae.gae_encode_blocks(x, x_r, basis, tau=0.01, bin_size=0.01)
    counts = exec_mod.counters()
    n, d = x.shape
    assert counts["xfer.h2d_bytes"] == x.nbytes + basis.nbytes
    assert counts["xfer.d2h_bytes"] == 4 * n + 2 * 4 * n * d  # m, order, q
    exec_mod.reset_stage_stats()


# ---------------------------------------------------------------------------
# the compress and decompress paths
# ---------------------------------------------------------------------------

def _latent_bytes(comp, n_hb: int) -> int:
    cfg = comp.cfg
    return 4 * n_hb * (cfg.hb_latent + cfg.k * cfg.bae_latent)


def test_transfer_counters_equal_the_arrays_bytes(comp_hb):
    comp, hb = comp_hb
    exec_mod.reset_stage_stats()
    archive = comp.compress(hb, options=OPTS)
    counts = exec_mod.counters()
    # up: each stripe; down: its reconstruction and latents (the GAE
    # selection runs on the host on the CPU backend)
    assert counts["xfer.h2d_bytes"] == hb.nbytes
    assert counts["xfer.d2h_bytes"] == hb.nbytes + _latent_bytes(comp, 24)

    exec_mod.reset_stage_stats()
    comp.decompress(archive)
    counts = exec_mod.counters()
    assert counts["xfer.h2d_bytes"] == _latent_bytes(comp, 24)
    assert counts["xfer.d2h_bytes"] == hb.nbytes
    exec_mod.reset_stage_stats()


def test_codec_stages_count_each_stripe_once(comp_hb):
    comp, hb = comp_hb
    exec_mod.reset_stage_stats()
    archive = comp.compress(hb, options=OPTS)
    comp.decompress(archive)
    stats = exec_mod.stage_stats()
    for name in ("gae_encode", "gae_select", "gae_verify", "entropy_encode",
                 "huffman_encode", "index_encode", "entropy_decode",
                 "huffman_decode", "index_decode"):
        assert stats[name].values == hb.size, name
    assert stats["huffman_encode"].seconds <= stats["entropy_encode"].seconds
    assert stats["index_decode"].calls == 3
    assert stats["huffman_decode"].calls == 6      # latents, then coefficients
    exec_mod.reset_stage_stats()


def test_archives_identical_under_the_profiler(comp_hb, tmp_path):
    comp, hb = comp_hb
    plain = archive_io.serialize_archive(comp.compress(hb, options=OPTS))
    before = exec_mod.total_retraces()
    with jax.profiler.trace(str(tmp_path)):
        traced = comp.compress(hb, options=OPTS)
        recon = comp.decompress(traced)
    assert archive_io.serialize_archive(traced) == plain
    assert exec_mod.total_retraces() == before, exec_mod.retrace_counts()
    np.testing.assert_array_equal(recon, comp.decompress(traced))
