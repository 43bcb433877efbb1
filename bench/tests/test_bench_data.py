"""The device generators lay the data out as ``synthetic.make_dataset``
does: shapes, z-score, blocks and temporal-major hyper-block order."""
from __future__ import annotations

import json
from pathlib import Path

import jax
import numpy as np

from bench.data import e3sm, xgc
from bench.data import hyperblocks as make
from repro.data import blocks as blocks_mod
from repro.data import synthetic

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def _config(name: str, shape) -> dict:
    cfg = json.loads((CONFIGS / f"{name}.json").read_text())
    return dict(cfg, shape=list(shape))


def _repo_layout_e3sm(data: np.ndarray) -> np.ndarray:
    norm = blocks_mod.Normalizer.fit(data, mode="zscore")
    blocks, meta = blocks_mod.block_nd(norm.forward(data), (6, 16, 16))
    blocks = synthetic._temporal_major(blocks, meta.grid_shape, t_axis=0)
    return blocks_mod.group_hyperblocks(blocks, 5)


def test_e3sm_matches_make_dataset_layout():
    shape = synthetic._SIZES["e3sm"][1]
    dims = (shape["t"], shape["h"], shape["w"])
    hb = make(_config("e3sm", dims), 5)
    _, want = synthetic.make_dataset("e3sm", quick=True, seed=0)
    assert hb.shape == want.shape and hb.dtype == np.float32
    assert abs(float(hb.mean())) < 1e-4 and abs(float(hb.std()) - 1) < 1e-4
    # the same field through the repository's host blocking
    field = np.asarray(e3sm.field(jax.random.key(5), *dims))
    np.testing.assert_allclose(hb, _repo_layout_e3sm(field), rtol=1e-4,
                               atol=1e-4)


def test_xgc_matches_make_dataset_layout():
    hb = make(_config("xgc", (8, 128, 39, 39)), 9)
    assert hb.shape == (128, 8, 1521) and hb.dtype == np.float32
    assert abs(float(hb.mean())) < 1e-4 and abs(float(hb.std()) - 1) < 1e-4
    field = np.asarray(xgc.field(jax.random.key(9), 8, 128, 39, 39))
    norm = blocks_mod.Normalizer.fit(field, mode="zscore")
    data = norm.forward(field)
    # make_dataset's XGC branch: the planes at one node form a hyper-block
    want = blocks_mod.group_hyperblocks(
        data.transpose(1, 0, 2, 3).reshape(128 * 8, 1521), 8)
    np.testing.assert_allclose(hb, want, rtol=1e-4, atol=1e-4)
    # planes at one node are near-copies: the structure the HBAE exploits
    corr = np.corrcoef(hb[:, 0].ravel(), hb[:, 7].ravel())[0, 1]
    assert corr > 0.99


def test_same_seed_same_data_other_seed_other_data():
    cfg = _config("e3sm", (60, 48, 96))
    a, b, c = make(cfg, 2**31 - 1), make(cfg, 2**31 - 1), make(cfg, 17)
    assert np.array_equal(a, b) and not np.array_equal(a, c)
