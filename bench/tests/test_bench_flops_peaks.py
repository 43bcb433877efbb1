"""Model FLOP counts from the configuration's shapes, and the peak table."""
from __future__ import annotations

import json
from pathlib import Path

import pytest

from bench import flops, peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _compressor(name: str) -> dict:
    return json.loads((CONFIGS / f"{name}.json").read_text())["compressor"]


def test_e3sm_compress_count_by_hand():
    c = _compressor("e3sm")
    # HBAE, per block: (5*(1536*512 + 512*128) + 4*5*128^2 + 2*5^2*128
    # + 5*128*64) / 5 MACs each way
    assert 2 * flops.hbae_half_macs_per_block(c) == 1_853_952
    # BAE: 1536*512 + 512*16 each way
    assert 2 * flops.bae_half_macs_per_block(c) == 1_589_248
    ae = 2 * (1_853_952 + 1_589_248) / 1536
    assert ae == pytest.approx(4483.33, abs=0.01)
    assert flops.compress_per_value(c) == pytest.approx(ae + 512)


def test_decompress_counts_decode_and_coded_blocks():
    c = _compressor("xgc")
    none = flops.decompress_per_value(c, 0.0)
    assert none == pytest.approx(
        2 * (flops.hbae_half_macs_per_block(c)
             + flops.bae_half_macs_per_block(c)) / 1521)
    assert flops.decompress_per_value(c, 0.5) == pytest.approx(none + 1521)


def test_peaks_known_and_unknown_device():
    assert peaks.peak("TPU v5 lite")["bf16_flops"] == 197e12
    assert peaks.peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("cpu")
