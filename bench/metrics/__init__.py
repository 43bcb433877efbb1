"""Per-layer metric readers, one module per metric, found by the metric's
name in BENCHMARK.json.  Each has ``read(ctx) -> float | None``; None means
the run holds nothing to read, and the harness leaves the metric out.

The helpers read the window's ``exec.stage`` spans (``ctx.stages``), the
trace reduction (``ctx.trace``) and the mode's facts (``ctx.facts``).
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Optional


def reader(name: str):
    path = Path(__file__).resolve().parent / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def stage_ms_per_MB(ctx, stage: str) -> Optional[float]:
    """Seconds inside one ``exec.stage`` span per MB of float32 values it
    processed.  Stages on the codec pool sum their threads' busy time."""
    st = ctx.stages.get(stage)
    if st is None or not st.values:
        return None
    return st.seconds * 1e3 / (st.values * 4 / 1e6)


def device_idle_pct(ctx) -> Optional[float]:
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])


def mfu_pct(ctx) -> Optional[float]:
    """Model FLOPs per value times values per second of the traced window,
    over the chips' bf16 peak."""
    from bench import peaks
    if ctx.trace is None:
        return None
    rate = ctx.window_bytes / 4 / ctx.window_seconds
    peak = peaks.peak(ctx.device_kind)["bf16_flops"] * ctx.chips
    return 100.0 * ctx.facts["flops_per_value"] * rate / peak
