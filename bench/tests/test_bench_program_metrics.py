"""The readers of the program's own spans and counters, on a hand-built
context: what each computes, 0.0 where its stage ran and counted nothing,
and None where the program has no such span or counter."""
from __future__ import annotations

from types import SimpleNamespace

import pytest

from bench.metrics import reader
from repro.core import exec as exec_mod
from repro.core.exec import StageStat

MB = 1_000_000 // 4                  # float32 values in one MB


@pytest.fixture
def counters():
    exec_mod.reset_stage_stats()
    yield exec_mod.counter_add
    exec_mod.reset_stage_stats()


def ctx(stages=None, window_bytes=8_000_000):
    return SimpleNamespace(stages=stages or {}, window_bytes=window_bytes)


@pytest.mark.parametrize("name", ["gae_select", "gae_verify",
                                  "huffman_encode", "index_encode",
                                  "huffman_decode", "index_decode"])
def test_span_readers(name):
    st = StageStat(calls=3, seconds=0.5, values=2 * MB, cpu_seconds=0.4)
    assert reader(f"{name}_ms_per_MB")(ctx({name: st})) == pytest.approx(250)
    assert reader(f"{name}_ms_per_MB")(ctx()) is None


def test_gae_repaired_pct(counters):
    read = reader("gae_repaired_pct")
    assert read(ctx()) is None
    counters("gae.blocks", 400)
    assert read(ctx()) == 0.0
    counters("gae.repaired_blocks", 10)
    assert read(ctx()) == pytest.approx(2.5)


def test_xfer_bytes_per_byte(counters):
    read = reader("xfer_bytes_per_byte.compress")
    assert read(ctx()) is None
    counters("xfer.h2d_bytes", 16_000_000)
    counters("xfer.d2h_bytes", 24_000_000)
    assert read(ctx()) == pytest.approx(5.0)


@pytest.mark.parametrize("mode, stages", [
    ("compress", ("entropy_encode", "gae_verify")),
    ("decompress", ("huffman_decode", "index_decode")),
])
def test_codec_oncpu_pct(mode, stages):
    read = reader(f"codec_oncpu_pct.{mode}")
    a, b = stages
    got = read(ctx({a: StageStat(1, 3.0, 10, cpu_seconds=1.0),
                    b: StageStat(1, 1.0, 10, cpu_seconds=1.0),
                    "ae_encode": StageStat(1, 9.0, 10, cpu_seconds=0.0)}))
    assert got == pytest.approx(50.0)
    assert read(ctx({a: StageStat(1, 3.0, 10)})) is None
    # a program whose stages keep no on-CPU time has nothing to read
    old = SimpleNamespace(calls=1, seconds=1.0, values=10)
    assert read(ctx({a: old, b: old})) is None
