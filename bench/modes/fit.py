"""Mode ``fit``: the window repeats ``HierarchicalCompressor.fit(field,
seed)`` at the traffic's epochs, the time to a fitted model on new data.

Set-up makes the field and runs one fit, which compiles every program the
window runs; it records that call's first HBAE and BAE steps (parameters
before, batches, losses, Adam state after the first, parameters after the
last) at the step function's boundary.  The check trains the plain
reference from the same parameters on the same batches and compares."""
from __future__ import annotations

import contextlib
import dataclasses

import jax
import numpy as np

from bench import checks, flops, reference
from bench.modes import compressor_config, timed


class StepRecorder:
    """Wraps a jitted training step; copies what the check needs of the
    first ``n`` calls to the host before the next call donates it."""

    def __init__(self, step, n: int):
        self.step, self.n, self.calls = step, n, 0
        self.p0 = self.state1 = self.p_last = None
        self.batches: list[np.ndarray] = []
        self.losses: list[float] = []

    def __call__(self, params, opt_state, x, opt):
        i = self.calls
        self.calls += 1
        if i >= self.n:
            return self.step(params, opt_state, x, opt)
        if i == 0:
            self.p0 = jax.device_get(params)
        self.batches.append(np.asarray(x))
        params, opt_state, loss = self.step(params, opt_state, x, opt)
        self.losses.append(float(loss))
        if i == 0:
            self.state1 = jax.device_get(opt_state)
        if i == self.n - 1:
            self.p_last = jax.device_get(params)
        return params, opt_state, loss


@contextlib.contextmanager
def recording(n: int):
    from repro.core import training
    recs = {"hbae": StepRecorder(training._hbae_step, n),
            "bae": StepRecorder(training._bae_step, n)}
    training._hbae_step, training._bae_step = recs["hbae"], recs["bae"]
    try:
        yield recs
    finally:
        training._hbae_step = recs["hbae"].step
        training._bae_step = recs["bae"].step


def _fit(ctx):
    from repro.core.pipeline import HierarchicalCompressor
    comp = HierarchicalCompressor(ctx.cfg).fit(ctx.hb,
                                               seed=ctx.config["fit_seed"])
    jax.block_until_ready((comp.hbae_params, comp.bae_params))
    return comp


def setup(ctx) -> None:
    from bench import data
    with timed(ctx, "data"):
        ctx.hb = data.hyperblocks(ctx.config, ctx.config["field_seed"])
    epochs = ctx.traffic["epochs"]
    ctx.cfg = dataclasses.replace(compressor_config(ctx.config),
                                  epochs_hbae=epochs, epochs_bae=epochs)
    with recording(ctx.traffic["steps_checked"]) as recs, \
            timed(ctx, "warm-up"):
        ctx.first = _fit(ctx)
    ctx.recs = recs


def unit(ctx, i: int) -> dict:
    comp = _fit(ctx)
    return {"params": (comp.hbae_params, comp.bae_params),
            "bytes": ctx.hb.nbytes}


def end_to_end(ctx, units: list[dict], seconds: float) -> dict[str, float]:
    ctx.facts["flops_per_value"] = flops.fit_per_value(
        ctx.config["compressor"], ctx.hb.shape[0], ctx.traffic["epochs"])
    return {"fit_MBps": sum(r["bytes"] for r in units) / seconds / 1e6}


def _leaves(tree) -> dict[str, np.ndarray]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(path): np.asarray(leaf, np.float64)
            for path, leaf in flat}


def _norm_gap(prog: dict, ref: dict, keep) -> float:
    """Worst leaf: | ||prog|| - ||ref|| | over the larger of the reference
    leaf's norm and the median leaf's."""
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items() if keep(k)}
    median = float(np.median(list(norms.values())))
    return max(abs(float(np.linalg.norm(prog[k])) - n) / max(n, median)
               for k, n in norms.items())


def _numbers(rec: StepRecorder, loss, lr: float, control: bool) -> dict:
    import jax.numpy as jnp
    if rec.calls < rec.n:
        raise ValueError(f"the fit ran {rec.calls} steps; the check follows "
                         f"{rec.n}")
    p0 = jax.tree.map(jnp.asarray, checks.plain(rec.p0, np.float32))
    batches = [jnp.asarray(b, jnp.float32) for b in rec.batches]
    with jax.default_matmul_precision("highest"):
        ref_losses, ref_g1, ref_p = reference.adam_train(
            loss("ref"), p0, batches, lr)
        if control:
            losses, g1, p_last = reference.adam_train(
                loss(checks.CONTROL_PRECISION), p0, batches, lr)
        else:
            losses = rec.losses
            mu = checks.plain(rec.state1.mu, np.float64)
            g1 = jax.tree.map(lambda m: m / (1 - reference.ADAM_B1), mu)
            p_last = checks.plain(rec.p_last, np.float64)
    g_ref, g_prog = _leaves(ref_g1), _leaves(g1)
    start = _leaves(p0)
    d_ref = {k: v - start[k] for k, v in _leaves(ref_p).items()}
    d_prog = {k: v - start[k] for k, v in _leaves(p_last).items()}
    g_norm = {k: float(np.linalg.norm(v)) for k, v in g_ref.items()}
    floor = 1e-3 * float(np.median(list(g_norm.values())))
    return {
        "loss_gap": max(abs(a - b) / b for a, b in zip(losses, ref_losses)),
        "grad_gap": _norm_gap(g_prog, g_ref, lambda k: True),
        # leaves whose reference gradient is nought to rounding move under
        # Adam by round-off alone: left out of the change
        "update_gap": _norm_gap(d_prog, d_ref, lambda k: g_norm[k] >= floor),
    }


def check(ctx, units: list[dict], control: bool = False) -> dict[str, float]:
    c = ctx.config["compressor"]
    lr = ctx.cfg.lr
    numbers = {}
    for name, loss in (
            ("hbae", lambda prec: lambda p, x: reference.hbae_loss(
                p, x, c["k"], c["heads"], prec)),
            ("bae", lambda prec: lambda p, r: reference.bae_loss(p, r, prec))):
        for key, value in _numbers(ctx.recs[name], loss, lr, control).items():
            numbers[f"{name}_{key}"] = value
    if not control:
        first = _leaves((ctx.first.hbae_params, ctx.first.bae_params))
        repeat = 0.0
        for rec in units:
            for k, v in _leaves(rec["params"]).items():
                repeat = max(repeat, float(np.abs(v - first[k]).max()))
        numbers["repeat_diff"] = repeat
    return numbers
