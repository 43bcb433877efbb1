"""Fault-tolerance tests: retry ladder determinism, deadline watchdog,
poison-stripe quarantine with the lossless verbatim fallback, chaos-harness
invariants, and the streaming ``.partial`` fuzz contract.

(Named ``test_chaos`` so it sorts before ``test_kernels`` — the kernel sweep
has a known pre-seed failure that stops ``pytest -x``.)
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
from repro.core.errors import (ArchiveError, GuaranteeUnsatisfiable,
                               MalformedStream, StageDeadlineExceeded,
                               TransientStageError)
from repro.core.options import CompressOptions
from repro.runtime import archive_io, faultinject
from repro.runtime.chaosinject import (ChaosInjector, ChaosPermanentFault,
                                       ChaosSpec, run_chaos_check)
from repro.stream import (FaultTolerance, RetryPolicy, StageGraph, StageSpec,
                          StreamScheduler, stream_compress)

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
# retry policy & scheduler-level ladder
# ---------------------------------------------------------------------------

def test_retry_policy_delay_is_deterministic_and_bounded():
    p = RetryPolicy(max_retries=5, base_backoff_s=0.01, max_backoff_s=0.1,
                    jitter=0.25, seed=3)
    d1 = [p.delay("enc", 7, a) for a in range(6)]
    d2 = [p.delay("enc", 7, a) for a in range(6)]
    assert d1 == d2                                     # pure function
    assert d1 != [p.delay("enc", 8, a) for a in range(6)]   # item-dependent
    for a, d in enumerate(d1):
        base = min(0.1, 0.01 * 2 ** a)
        assert base <= d <= base * 1.25
    assert RetryPolicy(seed=3).delay("enc", 7, 0) != \
        RetryPolicy(seed=4).delay("enc", 7, 0)          # seed-dependent


def test_scheduler_retries_transient_then_succeeds():
    calls = {}
    lock = threading.Lock()

    def flaky(i, x):
        with lock:
            calls[i] = calls.get(i, 0) + 1
        if i == 2 and calls[i] < 3:
            raise TransientStageError("flaky")
        return x * 10

    policy = RetryPolicy(max_retries=3, base_backoff_s=0.001,
                         max_backoff_s=0.005, seed=1)
    graph = StageGraph([StageSpec("flaky", flaky, workers=2, queue_depth=2,
                                  retry=policy)])
    results, stats = StreamScheduler(graph).run(list(range(5)))
    assert results == [x * 10 for x in range(5)]
    assert stats.retries == {"flaky": 2}
    assert [(e[0], e[1], e[2]) for e in stats.retry_events] == \
        [("flaky", 2, 0), ("flaky", 2, 1)]
    assert stats.retry_events[0][3] == round(policy.delay("flaky", 2, 0), 9)


def test_scheduler_retry_timeline_is_reproducible():
    def flaky(i, x):
        raise TransientStageError("always")

    def fallback(i, payload, exc):
        return -1

    timelines = []
    for _ in range(2):
        graph = StageGraph([StageSpec(
            "f", flaky, workers=3, queue_depth=2,
            retry=RetryPolicy(max_retries=2, base_backoff_s=0.001,
                              max_backoff_s=0.004, seed=9),
            fallback=fallback)])
        results, stats = StreamScheduler(graph).run(list(range(6)))
        assert results == [-1] * 6
        assert stats.failovers == {"f": 6}
        timelines.append(list(stats.retry_events))
    assert timelines[0] == timelines[1]          # canonicalized & seeded


def test_scheduler_permanent_error_skips_retries():
    attempts = {"n": 0}

    def perm(i, x):
        attempts["n"] += 1
        if i == 1:
            raise ValueError("permanent")
        return x

    graph = StageGraph([StageSpec("perm", perm, queue_depth=2,
                                  retry=RetryPolicy(max_retries=3))])
    with pytest.raises(ValueError, match="permanent"):
        StreamScheduler(graph).run([0, 1, 2])
    assert attempts["n"] == 3                    # no retry on non-transient


def test_scheduler_custom_retryable_classifier():
    calls = {"n": 0}

    def fn(i, x):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("disk hiccup")
        return x

    policy = RetryPolicy(max_retries=2, base_backoff_s=0.001,
                         retryable=lambda e: isinstance(e, OSError))
    graph = StageGraph([StageSpec("io", fn, retry=policy)])
    results, stats = StreamScheduler(graph).run([5])
    assert results == [5] and stats.total_retries() == 1


def test_deadline_abandons_hung_attempt_without_deadlock():
    hung = threading.Event()

    def hang_once(i, x):
        if i == 1 and not hung.is_set():
            hung.set()
            time.sleep(5.0)                      # way past the deadline
        return x + 100

    graph = StageGraph([StageSpec(
        "hang", hang_once, workers=2, queue_depth=2, deadline_s=0.05,
        retry=RetryPolicy(max_retries=2, base_backoff_s=0.001,
                          max_backoff_s=0.002))])
    t0 = time.perf_counter()
    results, stats = StreamScheduler(graph).run(list(range(4)))
    assert time.perf_counter() - t0 < 4.0        # did NOT wait out the hang
    assert results == [x + 100 for x in range(4)]
    assert stats.deadline_hits == {"hang": 1}
    assert stats.total_retries() == 1            # deadline hit is retryable


def test_deadline_exhaustion_surfaces_typed_error():
    def always_hang(i, x):
        time.sleep(5.0)

    graph = StageGraph([StageSpec("h", always_hang, deadline_s=0.02,
                                  retry=RetryPolicy(
                                      max_retries=1, base_backoff_s=0.001,
                                      max_backoff_s=0.002))])
    with pytest.raises(StageDeadlineExceeded) as ei:
        StreamScheduler(graph).run([0])
    assert ei.value.stage == "h" and ei.value.deadline_s == 0.02


def test_scheduler_shutdown_with_inflight_retries_raises_lowest_index():
    # several items exhaust their retries concurrently; the drain must
    # complete (all sentinels propagate) and the LOWEST index error wins
    def bad(i, x):
        if i in (1, 3):
            raise TransientStageError(f"bad-{i}")
        return x

    graph = StageGraph([StageSpec(
        "bad", bad, workers=3, queue_depth=2,
        retry=RetryPolicy(max_retries=2, base_backoff_s=0.001,
                          max_backoff_s=0.002))])
    with pytest.raises(TransientStageError, match="bad-1"):
        StreamScheduler(graph).run(list(range(5)))
    # the scheduler's worker threads all exited (no deadlocked queues)
    assert not [t for t in threading.enumerate()
                if t.name.startswith("stream-bad")]


def test_failing_fallback_records_error():
    def boom(i, x):
        raise ValueError("boom")

    def bad_fallback(i, payload, exc):
        raise RuntimeError("fallback also died")

    graph = StageGraph([StageSpec("b", boom, fallback=bad_fallback)])
    with pytest.raises(RuntimeError, match="fallback also died"):
        StreamScheduler(graph).run([0])


# ---------------------------------------------------------------------------
# verbatim fallback chunks
# ---------------------------------------------------------------------------

def test_verbatim_chunk_roundtrip_and_flags(comp_hb):
    comp, hb = comp_hb
    chunk = comp.encode_stripe_verbatim(7, hb[7:14])
    assert chunk.verbatim_blob and chunk.hb_stream is None
    blob = archive_io.pack_chunk_section(chunk)
    assert len(blob) == archive_io.chunk_section_size(chunk)
    back = archive_io.unpack_chunk_section(blob)
    assert back.verbatim_blob == chunk.verbatim_blob
    assert (back.hb_start, back.n_hyperblocks) == (7, 7)
    assert np.array_equal(comp.decode_stripe_verbatim(back), hb[7:14])


def test_verbatim_chunk_malformed_payload_is_typed(comp_hb):
    comp, hb = comp_hb
    chunk = comp.encode_stripe_verbatim(0, hb[:7])
    blob = archive_io.pack_chunk_section(chunk)
    with pytest.raises(ArchiveError):
        archive_io.unpack_chunk_section(blob[:-3])       # truncated
    import dataclasses as dc
    import zlib
    short = dc.replace(chunk, verbatim_blob=zlib.compress(b"\x00" * 12))
    with pytest.raises(MalformedStream, match="verbatim"):
        comp.decode_stripe_verbatim(short)               # wrong payload size


def test_quarantine_on_permanent_encode_failure(comp_hb, tmp_path,
                                                monkeypatch):
    comp, hb = comp_hb
    out = str(tmp_path / "quarantine.rba")
    batch = comp.compress(hb, options=OPTS)
    real = HierarchicalCompressor.encode_stripe_host

    def poison(self, hb_start, *args, **kwargs):
        if hb_start == 14:                       # chunk 2 is poison
            raise RuntimeError("poison stripe")
        return real(self, hb_start, *args, **kwargs)

    monkeypatch.setattr(HierarchicalCompressor, "encode_stripe_host", poison)
    ft = FaultTolerance(retry=RetryPolicy(max_retries=1,
                                          base_backoff_s=0.001,
                                          max_backoff_s=0.002))
    result = stream_compress(comp, hb, options=OPTS,
                             out_path=out, fault_tolerance=ft)
    monkeypatch.undo()
    assert result.quarantined == [2]
    assert "poison stripe" in result.quarantine_reasons[2]
    assert result.stats.quarantined == [2]
    assert result.stats.failovers.get("host_encode") == 1
    # permanent error: the retry ladder must NOT have retried it
    assert result.stats.total_retries() == 0

    # finalized container: quarantined chunk flagged verbatim, rest
    # byte-identical to batch
    disk = archive_io.read_archive(out, strict=True)
    for i, chunk in enumerate(disk.chunks):
        if i == 2:
            assert chunk.verbatim_blob
            assert np.array_equal(comp.decode_stripe_verbatim(chunk),
                                  hb[14:21])    # lossless fallback
        else:
            assert archive_io.pack_chunk_section(chunk) == \
                archive_io.pack_chunk_section(batch.chunks[i])
    assert disk.verbatim_chunks() == [2]

    # end-to-end decode honors tau everywhere (verbatim stripe included)
    recon = comp.decompress(disk)
    errs = np.linalg.norm((hb - recon).reshape(-1, 80), axis=1)
    assert float(errs.max()) <= 0.5 * (1 + 1e-5)
    assert np.array_equal(recon[14:21], hb[14:21])


def test_quarantine_on_guarantee_unsatisfiable(comp_hb, tmp_path,
                                               monkeypatch):
    comp, hb = comp_hb
    real = HierarchicalCompressor.encode_stripe_host

    def unsatisfiable(self, hb_start, *args, **kwargs):
        if hb_start == 0:
            raise GuaranteeUnsatisfiable("bound not achievable")
        return real(self, hb_start, *args, **kwargs)

    monkeypatch.setattr(HierarchicalCompressor, "encode_stripe_host",
                        unsatisfiable)
    result = stream_compress(
        comp, hb, options=OPTS,
        fault_tolerance=FaultTolerance(retry=RetryPolicy(
            max_retries=2, base_backoff_s=0.001, max_backoff_s=0.002)))
    monkeypatch.undo()
    assert result.quarantined == [0]
    assert result.stats.total_retries() == 0     # not transient
    recon = comp.decompress(result.archive)
    assert np.array_equal(recon[0:7], hb[0:7])


def test_no_fault_tolerance_keeps_fail_fast_semantics(comp_hb, tmp_path,
                                                      monkeypatch):
    comp, hb = comp_hb
    out = str(tmp_path / "failfast.rba")
    real = HierarchicalCompressor.encode_stripe_host

    def failing(self, hb_start, *args, **kwargs):
        if hb_start == 14:
            raise RuntimeError("hard crash")
        return real(self, hb_start, *args, **kwargs)

    monkeypatch.setattr(HierarchicalCompressor, "encode_stripe_host", failing)
    with pytest.raises(RuntimeError, match="hard crash"):
        stream_compress(comp, hb, options=OPTS, out_path=out)
    monkeypatch.undo()
    assert not os.path.exists(out)
    assert os.path.exists(out + ".partial")


# ---------------------------------------------------------------------------
# live chaos: injector + harness invariants
# ---------------------------------------------------------------------------

def test_chaos_injector_decisions_are_seeded():
    spec = ChaosSpec(seed=5, transient_rate=0.4, permanent_rate=0.1)
    a, b = ChaosInjector(spec), ChaosInjector(spec)
    for inj in (a, b):
        for item in range(12):
            for attempt in range(3):
                try:
                    inj.before("host_encode", item, attempt)
                except (TransientStageError, ChaosPermanentFault):
                    pass
    assert a.injected == b.injected
    assert a.injected["transient"] > 0
    # permanent faults are keyed per (stage, item), NOT per attempt: a
    # poison item fails every attempt (retries can never dodge it)
    inj = ChaosInjector(spec)
    for item in range(12):
        hits = []
        for attempt in range(3):
            try:
                inj.before("host_encode", item, attempt)
                hits.append(False)
            except ChaosPermanentFault:
                hits.append(True)
            except TransientStageError:
                hits.append(False)
        assert all(hits) or not any(hits), \
            f"permanent fault flickered across attempts for item {item}"


def test_stream_compress_under_transient_chaos_is_deterministic(comp_hb,
                                                                tmp_path):
    comp, hb = comp_hb
    spec = ChaosSpec(seed=11, transient_rate=0.35)
    ft = FaultTolerance(retry=RetryPolicy(max_retries=4,
                                          base_backoff_s=0.002,
                                          max_backoff_s=0.01, seed=11))
    runs = []
    for r in range(2):
        out = str(tmp_path / f"chaos{r}.rba")
        result = stream_compress(comp, hb, options=OPTS,
                                 out_path=out, fault_tolerance=ft,
                                 chaos=ChaosInjector(spec))
        runs.append((tuple(result.stats.retry_events),
                     tuple(result.quarantined)))
        # transient-only chaos: retries absorb everything, no quarantine,
        # container byte-identical to batch
        assert result.quarantined == []
    assert runs[0] == runs[1]
    assert runs[0][0]                            # chaos actually injected
    batch = comp.compress(hb, options=OPTS)
    with open(str(tmp_path / "chaos0.rba"), "rb") as f:
        assert f.read() == archive_io.serialize_archive(batch)


def test_run_chaos_check_invariant_harness(comp_hb, tmp_path):
    comp, hb = comp_hb
    report = run_chaos_check(
        comp, hb, 0.5,
        ChaosSpec(seed=3, transient_rate=0.25, permanent_rate=0.2),
        str(tmp_path / "harness.rba"), scenario="test", budget_s=60.0)
    assert report.ok, report.summary()
    assert report.quarantined > 0                # permanent faults landed
    assert "OK" in report.summary()


# ---------------------------------------------------------------------------
# codec pool resilience & partial fuzz
# ---------------------------------------------------------------------------

def test_pool_submit_recovers_from_reset():
    exec_mod.reset_pool()
    assert exec_mod.pool_submit(lambda x: x + 1, 41).result() == 42
    exec_mod.reset_pool()                        # kill it again mid-flight
    assert exec_mod.pool_submit(lambda x: x * 2, 21).result() == 42


def test_partial_fuzz_containment(comp_hb, tmp_path, monkeypatch):
    comp, hb = comp_hb
    out = str(tmp_path / "fuzzme.rba")
    real = HierarchicalCompressor.encode_stripe_host

    def failing(self, hb_start, *args, **kwargs):
        if hb_start == 14:
            raise RuntimeError("crash")
        return real(self, hb_start, *args, **kwargs)

    monkeypatch.setattr(HierarchicalCompressor, "encode_stripe_host", failing)
    with pytest.raises(RuntimeError):
        stream_compress(comp, hb, options=OPTS, out_path=out)
    monkeypatch.undo()
    with open(out + ".partial", "rb") as f:
        partial = f.read()
    result = faultinject.check_partial_containment(
        partial, trials=24, seed=1,
        decode=lambda a: comp.decompress(a, strict=False))
    assert result.ok, result.summary()
    # trial 0 fuzzes nothing: the as-left partial must salvage cleanly
    assert result.trials[0].kind == "as_left_on_disk"
    assert result.trials[0].outcome == "survived"
