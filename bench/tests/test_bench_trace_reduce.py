"""The reduction from a profiler trace to busy time, idle share, top device
ops and labelled idle gaps."""
from __future__ import annotations

from pathlib import Path

import pytest

from bench import trace_reduce

DATA = Path(__file__).resolve().parent / "data"


def _small() -> dict:
    # window 0..100 ns; device ops 10-30, 20-40 (overlap), 60-70
    return {
        "devices": {"/device:TPU:0": [
            [10, 30, "%fusion.1 = f32[8]{0:T(8)} fusion(f32[8]{0} %p), "
                     "kind=kLoop, calls=%fused_computation"],
            [20, 40, "%dot = (f32[8]{0}, u32[]) dot(f32[8]{0} %a, f32[8]{0} %b)"],
            [60, 70, "%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"],
            [120, 130, "%late = f32[8]{0} copy(f32[8]{0} %p)"]]},
        "host": [[0, 100, "window", "main"],
                 [0, 60, "compress", "main"],
                 [40, 60, "ae_encode", "main"],
                 [60, 100, "compress", "main"],
                 [70, 100, "gae_encode", "pool-1"],
                 [70, 90, "entropy_encode", "pool-2"]],
    }


def test_busy_union_and_idle_share():
    red = trace_reduce.reduce(_small())
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(40e-9)     # 10-40 and 60-70
    assert red["idle_share"] == pytest.approx(0.6)
    assert red["device_ops"][0] == ["fusion:kLoop", pytest.approx(30e-9)]
    assert ["dot", pytest.approx(20e-9)] in red["device_ops"]
    assert all(name != "copy" for name, _ in red["device_ops"])


def test_gaps_labelled_by_innermost_span_per_thread():
    gaps = dict(trace_reduce.reduce(_small())["idle_gaps"])
    assert gaps["compress"] == pytest.approx(10e-9)          # 0-10
    assert gaps["ae_encode"] == pytest.approx(20e-9)         # 40-60
    assert gaps["compress+entropy_encode+gae_encode"] == pytest.approx(30e-9)


def test_no_device_plane_reads_nothing():
    assert trace_reduce.reduce({"devices": {}, "host": []}) is None


def test_recorded_chip_trace():
    """A traced e3sm-compress window on one TPU v5e (10 s, 3 slices)."""
    extracted = trace_reduce.load(str(DATA / "trace_e3sm_compress.json.gz"))
    red = trace_reduce.reduce(extracted)
    assert red["n_devices"] == 1
    assert red["window_s"] == pytest.approx(10.167573711)
    assert red["busy_s"] == pytest.approx(2.110276798)
    assert 0.0 < red["idle_share"] < 1.0
    gaps = dict(red["idle_gaps"])
    # every idle second of the window is labelled, by the program's stages
    assert sum(gaps.values()) == pytest.approx(red["window_s"] - red["busy_s"])
    assert set(gaps) == {"ae_encode", "gae_encode", "entropy_encode"}
    assert max(gaps, key=gaps.get) == "entropy_encode"
    # gae_select's row gathers are the device's work
    assert red["device_ops"][0][0] == "fusion:kCustom"
    assert sum(s for _, s in red["device_ops"]) <= red["busy_s"] * 1.001
