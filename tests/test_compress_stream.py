"""Streaming subsystem tests: scheduler semantics (backpressure, ordering,
deterministic errors), stream/batch byte-identity, the two-phase streaming
archive writer, and crash-mid-stream salvage via tolerant reads.

(Named ``test_compress_stream`` so it sorts before ``test_kernels`` — the
kernel sweep has a known pre-seed failure that stops ``pytest -x``.)
"""
import os
import threading
import time

import numpy as np
import pytest

import jax

from repro.core import CompressorConfig, HierarchicalCompressor
from repro.core import bae as bae_mod
from repro.core import exec as exec_mod
from repro.core import hbae as hbae_mod
from repro.core.errors import ChecksumMismatch
from repro.core.options import CompressOptions
from repro.runtime import archive_io, faultinject
from repro.runtime.stream_writer import StreamingArchiveWriter, \
    WriterStateError
from repro.stream import StageGraph, StageSpec, StreamScheduler, \
    stream_compress

#: the compress configuration every batch/stream parity test here runs
OPTS = CompressOptions(tau=0.5, chunk_hyperblocks=7)


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
    hb = rng.standard_normal((24, cfg.k, cfg.block_elems)).astype(np.float32)
    hb *= 0.1
    comp.fit_basis(hb)
    return comp, hb


# ---------------------------------------------------------------------------
# scheduler semantics
# ---------------------------------------------------------------------------

def test_scheduler_orders_results_despite_unordered_completion():
    # stage with several workers and index-dependent latency: completion
    # order scrambles, result order must not
    def jitter(i, x):
        time.sleep(0.002 * ((x * 7) % 5))
        return x * x
    graph = StageGraph([StageSpec("jitter", jitter, workers=4,
                                  queue_depth=4)])
    results, stats = StreamScheduler(graph).run(list(range(20)))
    assert results == [x * x for x in range(20)]
    assert stats.n_items == 20 and stats.wall_s > 0


def test_scheduler_backpressure_bounds_queues():
    def fast(i, x):
        return x + 1

    def slow(i, x):
        time.sleep(0.003)
        return x * 10
    graph = StageGraph([StageSpec("fast", fast, queue_depth=1),
                        StageSpec("slow", slow, queue_depth=2)])
    results, stats = StreamScheduler(graph).run(list(range(16)))
    assert results == [(x + 1) * 10 for x in range(16)]
    # the bounded queue in front of the slow stage can never exceed its depth
    assert stats.queue_high_water["slow"] <= 2
    assert stats.queue_high_water["fast"] <= 1


def test_scheduler_raises_lowest_index_error_deterministically():
    for _ in range(5):
        seen = []
        lock = threading.Lock()

        def fn(i, x):
            with lock:
                seen.append(x)
            if x in (2, 5):
                time.sleep(0.001 * (5 - x))   # let index 5 fail FIRST
                raise ValueError(f"boom-{x}")
            return x
        graph = StageGraph([StageSpec("fn", fn, workers=3, queue_depth=4)])
        with pytest.raises(ValueError, match="boom-2"):
            StreamScheduler(graph).run(list(range(8)))
        assert sorted(seen) == list(range(8))   # no short-circuit: all ran


def test_scheduler_multistage_error_drops_item_but_drains():
    done = []

    def explode(i, x):
        if x == 1:
            raise RuntimeError("stage1 fail")
        return x

    def collect(i, x):
        done.append(x)
        return x
    graph = StageGraph([StageSpec("explode", explode, queue_depth=2),
                        StageSpec("collect", collect, queue_depth=2)])
    with pytest.raises(RuntimeError, match="stage1 fail"):
        StreamScheduler(graph).run([0, 1, 2, 3])
    assert sorted(done) == [0, 2, 3]   # item 1 dropped, everything drained


def test_scheduler_validates_graph():
    with pytest.raises(ValueError):
        StageGraph([])
    with pytest.raises(ValueError):
        StageGraph([StageSpec("a", lambda i, x: x),
                    StageSpec("a", lambda i, x: x)])
    with pytest.raises(ValueError):
        StageSpec("w", lambda i, x: x, workers=0)


# ---------------------------------------------------------------------------
# stream/batch byte-identity
# ---------------------------------------------------------------------------

def test_stream_matches_batch_byte_for_byte(comp_hb, tmp_path):
    comp, hb = comp_hb
    out = str(tmp_path / "stream.rba")
    exec_mod.reset_stage_stats()
    batch = comp.compress(hb, options=OPTS)
    result = stream_compress(comp, hb, options=OPTS, out_path=out)
    blob = archive_io.serialize_archive(batch)
    assert archive_io.serialize_archive(result.archive) == blob
    with open(out, "rb") as f:
        assert f.read() == blob
    assert result.bytes_written == len(blob)
    assert result.archive.compressed_bytes() == batch.compressed_bytes()
    assert not os.path.exists(out + ".partial")   # finalize cleaned up
    # guarantee survives the streamed container round-trip
    recon = comp.decompress(archive_io.read_archive(out))
    errs = np.linalg.norm((hb - recon).reshape(-1, 80), axis=1)
    assert float(errs.max()) <= 0.5 * (1 + 1e-5)
    # pipeline behavior was measured and surfaced through exec counters
    counters = exec_mod.counters()
    assert counters["stream.overlap_s"] >= 0
    assert "stream.queue_high_water.host_encode" in counters
    assert any(k.startswith("stream.") for k in exec_mod.stage_stats())


def test_stream_without_gae_or_output(comp_hb):
    comp, hb = comp_hb
    batch = comp.compress(hb, options=CompressOptions(chunk_hyperblocks=5))
    result = stream_compress(
        comp, hb, options=CompressOptions(chunk_hyperblocks=5))
    assert result.bytes_written == 0
    assert archive_io.serialize_archive(result.archive) == \
        archive_io.serialize_archive(batch)


# ---------------------------------------------------------------------------
# streaming archive writer
# ---------------------------------------------------------------------------

def test_writer_out_of_order_appends_finalize_identical(comp_hb, tmp_path):
    comp, hb = comp_hb
    batch = comp.compress(hb, options=OPTS)
    spans = [(c.hb_start, c.n_hyperblocks) for c in batch.chunks]
    out = str(tmp_path / "ooo.rba")
    w = StreamingArchiveWriter(out, n_hyperblocks=batch.n_hyperblocks,
                               n_values=batch.n_values,
                               chunk_hyperblocks=batch.chunk_hyperblocks,
                               gae_dim=batch.gae_dim, spans=spans)
    for i in (2, 0, 3, 1):                    # scrambled arrival
        w.append(i, batch.chunks[i])
    assert w.appended() == 4
    nbytes = w.finalize()
    blob = archive_io.serialize_archive(batch)
    with open(out, "rb") as f:
        assert f.read() == blob
    assert nbytes == len(blob)


def test_writer_protocol_errors(comp_hb, tmp_path):
    comp, hb = comp_hb
    batch = comp.compress(hb, options=OPTS)
    spans = [(c.hb_start, c.n_hyperblocks) for c in batch.chunks]
    out = str(tmp_path / "proto.rba")
    w = StreamingArchiveWriter(out, n_hyperblocks=batch.n_hyperblocks,
                               n_values=batch.n_values,
                               chunk_hyperblocks=batch.chunk_hyperblocks,
                               gae_dim=batch.gae_dim, spans=spans)
    w.append(0, batch.chunks[0])
    # byte-identical re-append is a no-op (idempotent under sink retry) ...
    w.append(0, batch.chunks[0])
    assert w.appended() == 1
    # ... but different bytes for an already-seen slot is still a protocol
    # error (same span, verbatim re-encoding => different section bytes)
    tampered = comp.encode_stripe_verbatim(
        batch.chunks[0].hb_start, hb[:batch.chunks[0].n_hyperblocks])
    assert archive_io.pack_chunk_section(tampered) != \
        archive_io.pack_chunk_section(batch.chunks[0])
    with pytest.raises(WriterStateError, match="different bytes"):
        w.append(0, tampered)
    with pytest.raises(WriterStateError, match="span table"):
        w.append(1, batch.chunks[2])          # wrong hb range for slot 1
    with pytest.raises(WriterStateError, match="outside"):
        w.append(99, batch.chunks[0])
    with pytest.raises(WriterStateError, match="finalize"):
        w.finalize()                          # chunks missing
    w.abort()
    with pytest.raises(WriterStateError, match="aborted"):
        w.append(1, batch.chunks[1])
    assert os.path.exists(out + ".partial")   # abort preserves the partial


def test_partial_is_salvageable_after_every_append(comp_hb, tmp_path):
    comp, hb = comp_hb
    batch = comp.compress(hb, options=OPTS)
    spans = [(c.hb_start, c.n_hyperblocks) for c in batch.chunks]
    out = str(tmp_path / "salvage.rba")
    w = StreamingArchiveWriter(out, n_hyperblocks=batch.n_hyperblocks,
                               n_values=batch.n_values,
                               chunk_hyperblocks=batch.chunk_hyperblocks,
                               gae_dim=batch.gae_dim, spans=spans)
    for appended in range(len(spans)):
        with open(out + ".partial", "rb") as f:
            data = f.read()
        # strict read must refuse a partial (placeholder digests can't pass)
        with pytest.raises(ChecksumMismatch):
            archive_io.deserialize_archive(data, strict=True)
        salvaged = archive_io.deserialize_archive(data, strict=False)
        good = [i for i, c in enumerate(salvaged.chunks) if c is not None]
        assert good == list(range(appended))
        assert set(salvaged.chunk_errors) == \
            set(range(appended, len(spans)))
        w.append(appended, batch.chunks[appended])
    w.finalize()
    assert archive_io.read_archive(out, strict=True) is not None


# ---------------------------------------------------------------------------
# crash mid-stream: truncation on a partially finalized streaming archive
# ---------------------------------------------------------------------------

def test_crash_mid_stream_truncation_salvage(comp_hb, tmp_path):
    comp, hb = comp_hb
    batch = comp.compress(hb, options=OPTS)
    spans = [(c.hb_start, c.n_hyperblocks) for c in batch.chunks]
    assert spans == [(0, 7), (7, 7), (14, 7), (21, 3)]
    out = str(tmp_path / "crash.rba")
    w = StreamingArchiveWriter(out, n_hyperblocks=batch.n_hyperblocks,
                               n_values=batch.n_values,
                               chunk_hyperblocks=batch.chunk_hyperblocks,
                               gae_dim=batch.gae_dim, spans=spans)
    for i in range(3):                        # chunk 3 never lands
        w.append(i, batch.chunks[i])
    w.abort()
    with open(out + ".partial", "rb") as f:
        partial = f.read()

    # torn write: cut INSIDE chunk 2's section, so the disk holds the header,
    # meta, chunks 0-1 whole and chunk 2 half-written
    names = [archive_io._META_NAME] + [archive_io.chunk_section_name(i)
                                       for i in range(len(spans))]
    head = archive_io.head_size(names)
    meta = archive_io.build_meta_blob(
        n_hyperblocks=batch.n_hyperblocks, n_values=batch.n_values,
        chunk_hyperblocks=batch.chunk_hyperblocks, gae_dim=batch.gae_dim,
        spans=spans)
    cut = (head + len(meta)
           + archive_io.chunk_section_size(batch.chunks[0])
           + archive_io.chunk_section_size(batch.chunks[1])
           + archive_io.chunk_section_size(batch.chunks[2]) // 2)
    torn = partial[:cut]

    salvaged = archive_io.deserialize_archive(torn, strict=False)
    assert [c is not None for c in salvaged.chunks] == \
        [True, True, False, False]
    recon, report = comp.decompress(salvaged, strict=False)
    assert not report.ok
    assert [(d.chunk, d.hb_start, d.n_hyperblocks) for d in report.damaged] \
        == [(2, 14, 7), (3, 21, 3)]           # accurate damage accounting
    # every completed chunk still satisfies the per-block guarantee
    good = recon[:14]
    errs = np.linalg.norm((hb[:14] - good).reshape(-1, 80), axis=1)
    assert float(errs.max()) <= 0.5 * (1 + 1e-5)

    # and random truncations of the partial stay inside the typed-error
    # contract (detected or survived, never an escaped raw exception)
    rng = np.random.default_rng(7)
    for _ in range(24):
        bad = faultinject.corrupt(partial, "truncate", rng)
        try:
            arch = archive_io.deserialize_archive(bad, strict=False)
            comp.decompress(arch, strict=False)
        except archive_io.ArchiveError:
            pass


def test_stream_compress_failure_keeps_salvageable_partial(comp_hb, tmp_path,
                                                           monkeypatch):
    comp, hb = comp_hb
    out = str(tmp_path / "fail.rba")
    real = HierarchicalCompressor.encode_stripe_host

    def failing(self, hb_start, *args, **kwargs):
        if hb_start == 14:                    # chunk 2 of 4 dies
            raise RuntimeError("simulated encoder crash")
        return real(self, hb_start, *args, **kwargs)
    monkeypatch.setattr(HierarchicalCompressor, "encode_stripe_host", failing)
    with pytest.raises(RuntimeError, match="simulated encoder crash"):
        stream_compress(comp, hb, options=OPTS, out_path=out)
    monkeypatch.undo()
    assert not os.path.exists(out)            # never finalized
    with open(out + ".partial", "rb") as f:
        partial = f.read()
    salvaged = archive_io.deserialize_archive(partial, strict=False)
    good = [i for i, c in enumerate(salvaged.chunks) if c is not None]
    assert good == [0, 1]                     # chunks before the crash landed
    batch = comp.compress(hb, options=OPTS)
    for i in good:                            # and are byte-exact vs batch
        assert archive_io.pack_chunk_section(salvaged.chunks[i]) == \
            archive_io.pack_chunk_section(batch.chunks[i])
