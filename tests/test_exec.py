"""Hot-path execution layer tests: persistent jit cache + retrace accounting,
fused stage programs, chunk-parallel codecs, and the guarantee/accounting
bugfix regressions (GuaranteeUnsatisfiable, model_bytes dtypes, cached
compressed_bytes, strict/tolerant decode parity)."""
import pathlib

import numpy as np
import pytest

import jax

from repro.core import CompressorConfig, HierarchicalCompressor
from repro.core import bae as bae_mod
from repro.core import entropy, gae
from repro.core import exec as exec_mod
from repro.core import hbae as hbae_mod
from repro.core.errors import GuaranteeUnsatisfiable, MalformedStream
from repro.core.options import CompressOptions
from repro.runtime import archive_io


# ---------------------------------------------------------------------------
# fixtures: an UNTRAINED compressor (random init) — the hot path, codecs and
# guarantees don't care whether the AE is good, and skipping fit() keeps the
# suite fast.
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def comp_hb():
    cfg = CompressorConfig(block_elems=40, k=2, emb=16, hidden=32, hb_latent=8,
                           bae_hidden=32, bae_latent=4, gae_block_elems=80,
                           hb_bin=0.01, bae_bin=0.01, gae_bin=0.02)
    comp = HierarchicalCompressor(cfg)
    key = jax.random.PRNGKey(0)
    khb, kb = jax.random.split(key)
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
# persistent jit cache
# ---------------------------------------------------------------------------

def test_jit_cache_returns_same_wrapper():
    c = exec_mod.JitCache()
    f = lambda x: x + 1
    w1 = c.get("inc", f)
    w2 = c.get("inc", f)
    assert w1 is w2
    # different statics => distinct compiled wrapper
    w3 = c.get("inc", f, static_argnums=(0,))
    assert w3 is not w1


def test_jit_cache_counts_retraces_not_calls():
    c = exec_mod.JitCache()
    sq = c.get("sq", lambda x: x * x)
    x4 = np.arange(4, dtype=np.float32)
    sq(x4)
    sq(x4 + 1)                      # same shape/dtype: cache hit
    sq(x4 + 2)
    assert c.retrace_counts() == {"sq": 1}
    sq(np.arange(5, dtype=np.float32))   # new shape: one more trace
    assert c.retrace_counts() == {"sq": 2}
    assert c.total_retraces() == 2


def test_roundtrip_retrace_stable_after_warmup(comp_hb):
    comp, hb = comp_hb
    # warmup traces every program for this shape
    a = comp.compress(hb, options=CompressOptions(tau=0.5))
    comp.decompress(a)
    before = exec_mod.total_retraces()
    for _ in range(2):
        a = comp.compress(hb, options=CompressOptions(tau=0.5))
        comp.decompress(a)
    assert exec_mod.total_retraces() == before, exec_mod.retrace_counts()


def test_decompress_decodes_at_the_stripe_shapes_compress_ran(comp_hb,
                                                              monkeypatch):
    # the GAE encoder verified tau against compress's per-stripe decode; a
    # program compiled for another batch shape may round differently on an
    # accelerator, so decompress must not compile one
    comp, hb = comp_hb
    monkeypatch.setattr(exec_mod, "_CACHE", exec_mod.JitCache())
    archive = comp.compress(hb, options=CompressOptions(
        tau=0.5, chunk_hyperblocks=10))              # stripes of 10, 10, 4
    assert exec_mod.retrace_counts()["decode_backend"] == 2
    recon = comp.decompress(archive)
    assert exec_mod.retrace_counts()["decode_backend"] == 2
    errs = np.linalg.norm((hb - recon).reshape(-1, 80), axis=1)
    assert errs.max() <= 0.5


def test_stage_stats_accumulate():
    exec_mod.reset_stage_stats()
    with exec_mod.stage("unit_test_stage", 100):
        pass
    with exec_mod.stage("unit_test_stage", 50):
        pass
    st = exec_mod.stage_stats()["unit_test_stage"]
    assert st.calls == 2 and st.values == 150 and st.seconds >= 0.0
    assert "unit_test_stage" in exec_mod.stats_summary()
    exec_mod.reset_stage_stats()
    assert "unit_test_stage" not in exec_mod.stage_stats()


def test_map_parallel_preserves_order(monkeypatch):
    items = list(range(37))
    assert exec_mod.map_parallel(lambda x: x * x, items) == \
        [x * x for x in items]
    # forced-serial configuration must agree bit-for-bit
    monkeypatch.setenv("REPRO_CODEC_WORKERS", "1")
    assert exec_mod.map_parallel(lambda x: x * x, items) == \
        [x * x for x in items]


def test_map_parallel_raises_lowest_index_error(monkeypatch):
    # several items fail, the later one FINISHES first — the propagated
    # exception must still be the lowest failing index's, exactly what the
    # serial loop would raise
    monkeypatch.setenv("REPRO_CODEC_WORKERS", "4")

    def fn(x):
        if x in (3, 9):
            import time
            time.sleep(0.002 if x == 3 else 0.0)
            raise ValueError(f"item-{x}")
        return x
    for _ in range(5):
        with pytest.raises(ValueError, match="item-3"):
            exec_mod.map_parallel(fn, range(12))
    # serial path agrees
    monkeypatch.setenv("REPRO_CODEC_WORKERS", "1")
    with pytest.raises(ValueError, match="item-3"):
        exec_mod.map_parallel(fn, range(12))


def test_stage_and_counter_accumulation_thread_safe():
    import threading
    exec_mod.reset_stage_stats()
    n_threads, n_iter = 8, 200

    def hammer():
        for _ in range(n_iter):
            exec_mod.record_stage("mt_stage", 0.001, n_values=10)
            exec_mod.counter_add("mt_counter", 1.0)
            exec_mod.counter_max("mt_gauge", 7.0)
    threads = [threading.Thread(target=hammer) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = exec_mod.stage_stats()["mt_stage"]
    # no lost updates: every read-modify-write landed
    assert st.calls == n_threads * n_iter
    assert st.values == n_threads * n_iter * 10
    assert st.seconds == pytest.approx(n_threads * n_iter * 0.001)
    counters = exec_mod.counters()
    assert counters["mt_counter"] == n_threads * n_iter
    assert counters["mt_gauge"] == 7.0
    assert "mt_counter: 1600" in exec_mod.stats_summary()
    exec_mod.reset_stage_stats()
    assert exec_mod.counters() == {}


# ---------------------------------------------------------------------------
# GAE guarantee regressions
# ---------------------------------------------------------------------------

def test_gae_unsatisfiable_raises_typed_error():
    # A zero basis can never span the residual: every refinement step keeps
    # err = ||x - x_r||.  The encoder previously emitted the violating block
    # silently; now it must raise with full diagnostics.
    d = 16
    x = np.ones((3, d), np.float32)
    x_r = np.zeros((3, d), np.float32)
    basis = np.zeros((d, d), np.float32)
    with pytest.raises(GuaranteeUnsatisfiable) as ei:
        gae.gae_encode_blocks(x, x_r, basis, tau=1e-4, bin_size=0.01,
                              max_refine=3)
    e = ei.value
    assert e.err > e.tau and e.tau == pytest.approx(1e-4)
    assert e.max_refine == 3 and 0 <= e.block < 3


def test_gae_encode_never_emits_violating_block():
    # Coarse bin vs tiny tau forces the per-block repair loop (bin_exp > 0);
    # every emitted block must still satisfy the bound.
    rng = np.random.default_rng(1)
    d = 32
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    x = rng.standard_normal((20, d)).astype(np.float32)
    x_r = x + 0.3 * rng.standard_normal((20, d)).astype(np.float32)
    tau = 0.05
    out, codes = gae.gae_encode_blocks(x, x_r, basis, tau=tau, bin_size=0.5)
    errs = np.linalg.norm(x - out, axis=1)
    assert np.all(errs <= tau * (1 + 1e-5)), errs.max()
    assert any(c.bin_exp > 0 for c in codes)   # the repair loop really ran
    # decode side reproduces the encoder's corrected output exactly
    dec = gae.gae_decode_blocks(x_r.copy(), basis, codes, bin_size=0.5)
    np.testing.assert_allclose(dec, out, atol=1e-5)


def test_gae_codes_are_ascending_index_order():
    rng = np.random.default_rng(2)
    d = 24
    basis = np.linalg.qr(rng.standard_normal((d, d)))[0].astype(np.float32)
    x = rng.standard_normal((8, d)).astype(np.float32)
    x_r = np.zeros_like(x)
    _, codes = gae.gae_encode_blocks(x, x_r, basis, tau=0.1, bin_size=0.01)
    assert any(c.m > 1 for c in codes)
    for c in codes:
        assert c.indices.size == c.m == c.qcoeffs.size
        assert np.all(np.diff(c.indices) > 0)   # strictly ascending


# ---------------------------------------------------------------------------
# accounting bugfixes
# ---------------------------------------------------------------------------

def test_model_bytes_uses_leaf_dtype_width():
    cfg = CompressorConfig(block_elems=8, k=2)
    comp = HierarchicalCompressor(cfg)
    comp.hbae_params = {"w": np.zeros((4, 4), np.float16)}
    comp.bae_params = [{"w": np.zeros(10, np.float64)}]
    comp.basis = np.zeros((3, 3), np.float32)
    assert comp.model_bytes() == 16 * 2 + 10 * 8 + 9 * 4


def test_compressed_bytes_matches_framing_and_caches(comp_hb):
    comp, hb = comp_hb
    archive = comp.compress(hb, options=CompressOptions(tau=0.5))
    blob = archive_io.serialize_archive(archive)
    assert archive_io.serialized_size(archive) == len(blob)
    assert archive.compressed_bytes() == len(blob)
    assert archive._size_cache == len(blob)          # cached after first query
    assert archive.compressed_bytes() == len(blob)   # stable on re-query
    archive.invalidate_size_cache()
    assert archive._size_cache is None
    assert archive.compressed_bytes() == len(blob)


# ---------------------------------------------------------------------------
# strict vs tolerant decode parity
# ---------------------------------------------------------------------------

def test_nonstrict_decode_bit_identical_on_undamaged_archive(comp_hb):
    comp, hb = comp_hb
    archive = comp.compress(
        hb, options=CompressOptions(tau=0.5, chunk_hyperblocks=8))
    strict = comp.decompress(archive)
    tolerant, report = comp.decompress(archive, strict=False)
    assert report.ok and not report.damaged
    assert np.array_equal(strict, tolerant)
    # and the same through a full container round-trip
    archive2 = archive_io.deserialize_archive(
        archive_io.serialize_archive(archive))
    tolerant2, report2 = comp.decompress(archive2, strict=False)
    assert report2.ok
    assert np.array_equal(strict, tolerant2)


# ---------------------------------------------------------------------------
# vectorized codec twins vs their scalar oracles
# ---------------------------------------------------------------------------

def _laplace(n, scale, seed):
    rng = np.random.default_rng(seed)
    return np.round(rng.laplace(0.0, scale, n)).astype(np.int64)


def _dyadic(n_lengths):
    """Counts 2^(n-1), ..., 2, 1, 1: a book with every length 1..n."""
    counts = [1 << (n_lengths - 1 - k) for k in range(n_lengths)] + [1]
    vals = np.repeat(np.arange(len(counts)), counts)
    return np.random.default_rng(9).permutation(vals).astype(np.int64)


# A power-of-two span of bits: streams made of whole spans, and bit flips
# on the spans' edges, where a window reads across a byte-triple boundary.
_SEG = 2048

HUFFMAN_STREAMS = {
    "laplace-256": _laplace(256, 2.0, 1),
    "geometric-300": np.random.default_rng(4).geometric(0.3, 300) - 3,
    "geometric-5000": np.random.default_rng(4).geometric(0.3, 5000) - 3,
    "laplace-wide-4096": _laplace(4096, 40.0, 2),
    "laplace-200k": _laplace(200_000, 3.0, 3),
    "lengths-1-to-16": _dyadic(16),
    "single-symbol": np.full(1000, 7, np.int64),
    # a fixed-length code whose length does not divide the span
    "fixed-3-bit": np.tile(np.arange(8), 1000),
    # 4-bit codes filling whole spans: the stream ends on a span's end
    "segments-exact": np.tile(np.arange(16), _SEG // 16),
}


@pytest.mark.parametrize("name", sorted(HUFFMAN_STREAMS))
def test_huffman_vector_decode_matches_scalar(name):
    vals = HUFFMAN_STREAMS[name]
    n = vals.size
    assert n >= entropy._VECTOR_DECODE_MIN
    book = entropy.build_huffman(vals)
    data = entropy.huffman_encode(vals, book)
    if name == "lengths-1-to-16":
        assert sorted(set(book.lengths.tolist())) == list(range(1, 17))
    if name == "fixed-3-bit":
        assert set(book.lengths.tolist()) == {3} and _SEG % 3
    if name == "segments-exact":
        assert len(data) * 8 == 4 * _SEG
    fast = entropy.huffman_decode(data, book, n)
    slow = entropy.huffman_decode_scalar(data, book, n)
    np.testing.assert_array_equal(fast, slow)
    np.testing.assert_array_equal(fast, vals)


def _decode_both(data, book, count):
    """(values or None, (error type, message) or None) of both decoders."""
    got = []
    for decode in (entropy.huffman_decode, entropy.huffman_decode_scalar):
        try:
            got.append((decode(bytes(data), book, count), None))
        except (MalformedStream, entropy.TruncatedArchive) as e:
            got.append((None, (type(e), str(e))))
    return got


def _flipped(data, bits):
    out = bytearray(data)
    for b in bits:
        out[b >> 3] ^= 0x80 >> (b & 7)
    return out


def _corruptions():
    rng = np.random.default_rng(5)
    n = 3 * _SEG // 2                  # about three spans of codes
    vals = rng.geometric(0.4, size=n).astype(np.int64)
    book = entropy.build_huffman(vals)
    data = entropy.huffman_encode(vals, book)
    assert len(data) * 8 > 2 * _SEG + 1
    cases = [(f"random-{i}", data, [int(rng.integers(len(data) * 8))], book,
              n) for i in range(20)]
    edges = [k * _SEG + d for k in (1, 2) for d in (-1, 0, 1)]
    cases += [(f"boundary-{b}", data, [b], book, n) for b in edges]
    cases.append(("boundary-all", data, edges, book, n))
    big = _laplace(20_000, 3.0, 6)
    big_book = entropy.build_huffman(big)
    big_data = entropy.huffman_encode(big, big_book)
    assert len(big_data) * 4 > _SEG
    cases.append(("truncated", big_data[:len(big_data) // 2], [], big_book,
                  big.size))
    cases.append(("truncated-empty", b"", [], big_book, big.size))
    # a one-symbol book leaves half the windows undecodable
    one = np.zeros(4 * _SEG, np.int64)
    one_book = entropy.build_huffman(one)
    one_data = entropy.huffman_encode(one, one_book)
    cases += [(f"undecodable-{b}", one_data, [b], one_book, one.size)
              for b in (5, _SEG - 1, _SEG, 3 * _SEG + 1)]
    # the payload holds more codes than are asked for: the chain is broken
    # long before its end
    cases.append(("undecodable-early", one_data, [7], one_book, 2 * _SEG))
    # the last code asked for runs past the payload's end
    tail = _dyadic(16)
    tail_book = entropy.build_huffman(tail)
    longest = tail_book.symbols[-1]         # a 16-bit code, moved to the end
    assert tail_book.lengths[-1] == 16
    tail = np.append(np.delete(tail, np.flatnonzero(tail == longest)[0]),
                     longest)
    cases.append(("truncated-last-code",
                  entropy.huffman_encode(tail, tail_book)[:-1], [], tail_book,
                  tail.size))
    return cases


CORRUPTIONS = {c[0]: c[1:] for c in _corruptions()}


@pytest.mark.parametrize("name", list(CORRUPTIONS))
def test_huffman_vector_decode_matches_scalar_on_corruption(name):
    data, bits, book, count = CORRUPTIONS[name]
    (fast, fast_err), (slow, slow_err) = _decode_both(
        _flipped(data, bits), book, count)
    assert fast_err == slow_err
    if fast_err is None:
        np.testing.assert_array_equal(fast, slow)
    if name.startswith("undecodable"):
        assert fast_err == (MalformedStream,
                            f"undecodable Huffman prefix at bit {bits[0]}")
    if name.startswith("truncated"):
        assert fast_err[0] is entropy.TruncatedArchive


def _table_by_code(book):
    """The decode table filled one code at a time: the oracle of
    ``entropy._decode_table``."""
    table_sym = np.zeros(1 << 16, np.int64)
    table_len = np.zeros(1 << 16, np.uint8)
    for sym, length, code in zip(book.symbols, book.lengths, book.codes):
        length = int(length)
        if not 1 <= length <= 16:
            raise MalformedStream(f"Huffman code length {length} out of range")
        base, span = int(code) << (16 - length), 1 << (16 - length)
        if base + span > 1 << 16:
            raise MalformedStream("Huffman code outside table range")
        table_sym[base:base + span] = sym
        table_len[base:base + span] = length
    return table_sym, table_len


def _book(symbols, lengths, codes):
    return entropy.HuffmanBook(np.array(symbols, np.int64),
                               np.array(lengths, np.uint8),
                               np.array(codes, np.uint32))


DECODE_TABLE_BOOKS = {
    "laplace-narrow": entropy.build_huffman(_laplace(5000, 1.0, 11)),
    "laplace-wide": entropy.build_huffman(_laplace(20000, 3000.0, 12)),
    "lengths-1-to-16": entropy.build_huffman(_dyadic(16)),
    "single-symbol": entropy.build_huffman(np.full(10, 3)),
    "incomplete": _book([5, 9], [2, 3], [0b01, 0b110]),
    "unsorted": _book([5, 9, 4], [3, 1, 2], [0b110, 0b0, 0b10]),
    "empty": _book([], [], []),
    "length-0": _book([1, 2], [1, 0], [0, 1]),
    "length-17": _book([1, 2], [1, 17], [0, 1]),
    "outside": _book([1, 2], [2, 1], [0b1001, 0]),
}


@pytest.mark.parametrize("name", sorted(DECODE_TABLE_BOOKS))
def test_decode_table_matches_code_by_code_fill(name):
    book = DECODE_TABLE_BOOKS[name]
    try:
        want = _table_by_code(book)
    except MalformedStream as e:
        with pytest.raises(MalformedStream, match=f"^{str(e)}$"):
            entropy._decode_table(book)
        return
    got = entropy._decode_table(book)
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[1], want[1])


def test_decode_table_rejects_codes_that_are_not_a_prefix_code():
    # "0" is a prefix of "01": one window would decode as both
    with pytest.raises(MalformedStream, match="not a prefix code"):
        entropy._decode_table(_book([1, 2], [1, 2], [0b0, 0b01]))


def test_index_set_codec_roundtrip_with_empty_sets():
    rng = np.random.default_rng(6)
    dim = 80
    sets = []
    for i in range(40):
        if i % 7 == 0:
            sets.append(np.zeros(0, np.int32))
        else:
            m = int(rng.integers(1, dim + 1))
            sets.append(np.sort(rng.choice(dim, size=m,
                                           replace=False)).astype(np.int32))
    blob = entropy.encode_index_sets(sets, dim)
    back = entropy.decode_index_sets(blob, expect_dim=dim)
    assert len(back) == len(sets)
    for a, b in zip(sets, back):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# persistent compilation cache placement
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env_set", [True, False])
def test_use_compile_cache_places_cache(monkeypatch, tmp_path, env_set):
    before = jax.config.jax_compilation_cache_dir
    try:
        if env_set:
            monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
            assert exec_mod.use_compile_cache() == str(tmp_path)
            # JAX reads the variable itself: no other directory is set
            assert jax.config.jax_compilation_cache_dir == before
        else:
            monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
            path = exec_mod.use_compile_cache()
            root = pathlib.Path(__file__).resolve().parents[1]
            assert path == str(root / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert exec_mod.use_compile_cache() == path   # fixed, not fresh
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
