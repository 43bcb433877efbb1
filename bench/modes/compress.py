"""Mode ``compress``: the window cycles ``HierarchicalCompressor.compress``
over whole slices of the field, one slice per unit, with the traffic's tau
and stripe width.  Set-up fits the model and the basis on the whole field
and compresses the first slice once, which runs every program the window
runs at its shapes."""
from __future__ import annotations

import numpy as np

from bench import checks, flops
from bench.modes import (chunk_refs, coded_share, fit_model, options,
                         slices, timed)


def setup(ctx) -> None:
    fit_model(ctx)
    ctx.slices = slices(ctx)
    ctx.opts = options(ctx)
    with timed(ctx, "warm-up"):
        ctx.comp.compress(ctx.slices[0], options=ctx.opts)


def unit(ctx, i: int) -> dict:
    s = i % len(ctx.slices)
    archive = ctx.comp.compress(ctx.slices[s], options=ctx.opts)
    return {"slice": s, "archive": archive, "bytes": ctx.slices[s].nbytes}


def _distinct(units: list[dict]) -> dict[int, object]:
    """One archive per slice compressed in the window (compress is
    deterministic, so a repeat codes the same bytes)."""
    return {rec["slice"]: rec["archive"] for rec in units}


def end_to_end(ctx, units: list[dict], seconds: float) -> dict[str, float]:
    done = _distinct(units)
    raw = sum(ctx.slices[s].nbytes for s in done)
    coded = sum(a.compressed_bytes() for a in done.values())
    share = coded_share(done.values())
    ctx.facts["gae_coded_share"] = share
    ctx.facts["flops_per_value"] = flops.compress_per_value(
        ctx.config["compressor"])
    return {"compress_MBps": sum(r["bytes"] for r in units) / seconds / 1e6,
            "compression_ratio": raw / coded}


def check(ctx, units: list[dict], control: bool = False) -> dict[str, float]:
    refs = chunk_refs(units, lambda rec: rec["archive"])
    rng = np.random.default_rng(ctx.check_seed)
    items = []
    for j in checks.sample(rng, len(refs), ctx.traffic["check_chunks"]):
        ref = refs[j]
        rec = units[ref.unit]
        chunk = rec["archive"].chunks[ref.index]
        items.append(checks.Item(
            x=ctx.slices[rec["slice"]][ref.start:ref.stop], chunk=chunk,
            decoded=checks.decode_one_chunk(ctx.comp, rec["archive"], chunk)))
    return checks.compare(items, checks.model_arrays(ctx.comp),
                          ctx.config["compressor"], ctx.tau, latents=True,
                          control=control)
