"""The ``s3d-compress`` cell rehearsed on the CPU at a quick size (the
configuration's widths, a 58x50x8x8 field of 4 hyper-blocks, stripes of
4): ``correct`` true, the control and a planted fault false, every metric
the cell reports present; and the S3D device generator lays the field out
as ``synthetic.make_dataset("s3d")`` does."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace
from unittest import mock

import jax
import numpy as np
import pytest

from bench import run as bench_run
from bench.data import s3d
from bench.metrics import reader
from repro.core import exec as exec_mod
from repro.data import blocks as blocks_mod
from repro.data import synthetic

ROOT = Path(__file__).resolve().parents[2]
CELL = "s3d-compress"
S3D_HB_BYTES = 10 * 4640 * 4
OVERRIDES = {
    "config": {"shape": [58, 50, 8, 8]},
    "traffic": {"chunk_hyperblocks": 4, "slice_bytes": 4 * S3D_HB_BYTES,
                "check_chunks": 4}}


def _run(**kwargs) -> dict:
    """One run of the cell at the quick size; JAX's persistent compile
    cache is left as the test process has it."""
    with mock.patch.object(exec_mod, "use_compile_cache", lambda: None):
        return bench_run.run_cell(CELL, kwargs.pop("seed", 2**31 + 11),
                                  kwargs.pop("seconds", 0.5),
                                  kwargs.pop("trace", False),
                                  require_tpu=False, overrides=OVERRIDES,
                                  **kwargs)


def _config(shape) -> dict:
    cfg = json.loads((ROOT / "bench/configs/s3d.json").read_text())
    return dict(cfg, shape=list(shape))


def test_cell_is_correct_and_the_control_is_not():
    result = _run(control=True)
    assert result["correct"] is True, result["checks"]
    assert result["control"]["correct"] is False, result["control"]
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    want = {m["name"] for m in spec["end_to_end"]
            if CELL in m.get("workloads", [CELL])}
    assert want == {"compress_MBps", "compression_ratio", "setup_s"}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert set(result["checks"]) == {"latent_gap", "recon_gap", "tau_excess"}
    assert {"data", "fit", "basis", "warm-up"} <= set(result["setup_parts"])


def test_traced_run_reports_the_cells_per_layer_metrics():
    result = _run(trace=True)
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in spec["per_layer"]
              if CELL in m.get("workloads", [])}
    # the device trace and the share of a chip's peak need a chip
    on_cpu = listed - {"device_idle_pct.compress", "mfu.compress"}
    assert {"fit_ms_per_MB", "basis_ms_per_MB", "gae_coded_pct"} <= on_cpu
    assert on_cpu <= set(result["metrics"]), sorted(
        on_cpu - set(result["metrics"]))
    assert result["metrics"]["fit_ms_per_MB"]["value"] > 0


def test_latent_altered_in_the_timed_path_is_not_correct(monkeypatch):
    fetch = exec_mod.fetch_compress_stage

    def altered(handles):
        q_lh, q_lbs, recon = fetch(handles)
        q_lh = q_lh.copy()
        q_lh[:, 0] += 40
        return q_lh, q_lbs, recon
    monkeypatch.setattr(exec_mod, "fetch_compress_stage", altered)
    result = _run()
    assert result["correct"] is False, result["checks"]


@pytest.mark.parametrize("name, part", [("fit_ms_per_MB", "fit"),
                                        ("basis_ms_per_MB", "basis")])
def test_setup_part_readers(name, part):
    read = reader(name)
    hb = np.zeros((5, 2, 100_000), np.float32)          # 4 MB
    assert read(SimpleNamespace(setup_parts={part: 2.0}, hb=hb)) == \
        pytest.approx(500.0)
    assert read(SimpleNamespace(setup_parts={}, hb=hb)) is None
    assert read(SimpleNamespace(setup_parts={part: 2.0})) is None


def test_generator_matches_make_dataset_layout():
    shape = (58, 50, 16, 16)
    hb = s3d.hyperblocks(_config(shape), 2**31 + 5)
    # 4x4 spatial columns, each a hyper-block of its 10 temporal blocks
    assert hb.shape == (16, 10, 4640) and hb.dtype == np.float32
    field = np.asarray(s3d.field(jax.random.key(2**31 + 5), shape))
    norm = blocks_mod.Normalizer.fit(field, mode="range", axis=0)
    blocks, meta = blocks_mod.block_nd(norm.forward(field), (58, 5, 4, 4))
    blocks = synthetic._temporal_major(blocks, meta.grid_shape, t_axis=1)
    want = blocks_mod.group_hyperblocks(blocks, 10)
    np.testing.assert_allclose(hb, want, rtol=1e-4, atol=1e-4)
    # per species: mean 0, range 1
    per_species = hb.reshape(16, 10, 58, 80).transpose(2, 0, 1, 3).reshape(
        58, -1)
    np.testing.assert_allclose(per_species.mean(axis=1), 0, atol=1e-5)
    np.testing.assert_allclose(np.ptp(per_species, axis=1), 1, atol=1e-5)


def test_generator_orders_hyperblocks_temporal_fastest():
    """Hyper-block n holds the 50 time steps of spatial column n, and its
    block j the time steps 5j to 5j + 4, species first in each block."""
    shape = (58, 50, 8, 12)
    d = s3d.draws(jax.random.key(7), shape)
    g, tt, yy, xx = (np.asarray(a) for a in s3d.block_coords(
        shape, (58, 5, 4, 4), 10, 6 * 10, 58 * 80))
    hb = np.arange(60)[:, None] // 10
    j = np.arange(60)[:, None] % 10
    v = np.arange(58 * 80)[None, :] % 80
    assert np.array_equal(g, np.broadcast_to(np.arange(58 * 80) // 80,
                                             g.shape))
    assert np.array_equal(tt, j * 5 + v // 16)
    assert np.array_equal(yy, (hb // 3) * 4 + (v // 4) % 4)
    assert np.array_equal(xx, (hb % 3) * 4 + v % 4)
    assert d["mix"].shape == (58, s3d.RANK)


def test_same_seed_same_field_other_seed_other_field():
    cfg = _config((58, 50, 8, 8))
    a, b, c = (s3d.hyperblocks(cfg, s) for s in (2**31 - 1, 2**31 - 1, 17))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
