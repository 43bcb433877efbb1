"""``correct`` comes out false for the control and for the timed path
broken underneath: the harness's look for a chip is skipped, the rest of a
run is driven at a quick size on the CPU."""
from __future__ import annotations

import numpy as np
import pytest

from bench.tests import quick
from repro.core import exec as exec_mod
from repro.core import gae
from repro.core.pipeline import HierarchicalCompressor


@pytest.mark.parametrize("name", sorted(quick.OVERRIDES))
def test_control_is_not_correct(name):
    result = quick.run(name, control=True)
    assert result["correct"] is True, result["checks"]
    assert result["control"]["correct"] is False, result["control"]


def _latent_altered(monkeypatch):
    """A latent altered where it is produced: every stripe's first HBAE
    code is off by 40 bins."""
    fetch = exec_mod.fetch_compress_stage

    def altered(handles):
        q_lh, q_lbs, recon = fetch(handles)
        q_lh = q_lh.copy()
        q_lh[:, 0] += 40
        return q_lh, q_lbs, recon
    monkeypatch.setattr(exec_mod, "fetch_compress_stage", altered)


def _half_gae_left_out(monkeypatch):
    """Half of the GAE blocks lose their coefficients after encoding."""
    encode = gae.gae_encode_blocks

    def halved(*args, **kwargs):
        out, codes = encode(*args, **kwargs)
        empty = np.zeros(0, np.int32)
        return out, [c if i % 2 else gae.GAEBlockCode(0, empty, empty.astype(
            np.int64), 0) for i, c in enumerate(codes)]
    monkeypatch.setattr(gae, "gae_encode_blocks", halved)


def _correction_skipped(monkeypatch):
    """The decoder returns its AE reconstruction unchanged."""
    monkeypatch.setattr(gae, "gae_decode_blocks",
                        lambda x_r, basis, codes, bin_size: np.array(x_r))


def _half_decode_left_out(monkeypatch):
    """The AE decode leaves out the second half of each stripe."""
    decode = HierarchicalCompressor._ae_decode

    def halved(self, q_lh, q_lbs, spans, mesh):
        recon = decode(self, q_lh, q_lbs, spans, mesh)
        for s, w in spans:
            recon[s + w // 2:s + w] = 0.0
        return recon
    monkeypatch.setattr(HierarchicalCompressor, "_ae_decode", halved)


@pytest.mark.parametrize("name,fault", [
    ("e3sm-compress", _latent_altered),
    ("e3sm-compress", _half_gae_left_out),
    ("xgc-compress", _latent_altered),
    ("e3sm-decompress", _correction_skipped),
    ("e3sm-decompress", _half_decode_left_out),
])
def test_broken_timed_path_is_not_correct(name, fault, monkeypatch):
    fault(monkeypatch)
    result = quick.run(name)
    assert result["correct"] is False, result["checks"]
