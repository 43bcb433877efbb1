"""The ``fit`` driver's check at a quick size on the CPU, called directly
(no cell runs the driver yet: PERF.md, Open questions).  The recorded first steps match
the plain reference; the control and the planted faults do not."""
from __future__ import annotations

import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from bench import run as bench_run
from bench.modes import fit

ROOT = Path(__file__).resolve().parents[2]


def _ctx() -> SimpleNamespace:
    config = json.loads((ROOT / "bench/configs/e3sm.json").read_text())
    config["shape"] = [60, 48, 96]
    traffic = json.loads((ROOT / "bench/traffic/fit.json").read_text())
    traffic["epochs"] = 3       # 3 steps of each AE on the small field
    return SimpleNamespace(config=config, traffic=traffic, facts={},
                           setup_parts={},
                           **bench_run.derive_seeds(2**31 + 3))


@pytest.fixture(scope="module")
def fitted():
    ctx = _ctx()
    fit.setup(ctx)
    return ctx, [fit.unit(ctx, 0)]


def test_recorded_steps_match_the_reference(fitted):
    ctx, units = fitted
    numbers = fit.check(ctx, units)
    assert numbers["repeat_diff"] == 0.0
    sound = {k: v for k, v in numbers.items() if k != "repeat_diff"}
    assert max(sound.values()) < 1e-3, numbers
    assert fit.end_to_end(ctx, units, 1.0)["fit_MBps"] > 0


def test_control_reads_far_above_the_program(fitted):
    ctx, units = fitted
    sound = fit.check(ctx, units)
    control = fit.check(ctx, units, control=True)
    for name in ("hbae_loss_gap", "hbae_grad_gap", "bae_grad_gap"):
        assert control[name] > 30 * max(sound[name], 1e-6), (name, control)


def test_state_left_unchanged_reads_one(fitted):
    ctx, units = fitted
    rec = ctx.recs["hbae"]
    saved = rec.p_last
    rec.p_last = rec.p0
    try:
        assert fit.check(ctx, units)["hbae_update_gap"] == pytest.approx(1.0)
    finally:
        rec.p_last = saved


def test_half_batch_left_out_is_seen(monkeypatch):
    from repro.core import training
    step = training._hbae_step

    def half(params, opt_state, x, opt):
        return step(params, opt_state, x[: x.shape[0] // 2], opt)
    monkeypatch.setattr(training, "_hbae_step", half)
    ctx = _ctx()
    fit.setup(ctx)
    # the recorder saw the full batch; the step trained on half of it
    numbers = fit.check(ctx, [])
    assert numbers["hbae_loss_gap"] > 1e-3 or numbers["hbae_grad_gap"] > 1e-2

