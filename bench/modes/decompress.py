"""Mode ``decompress``: set-up fits the model and the basis, compresses the
first ``slices`` slices of the field, and decodes each archive once (the
decodes the window's are held to); the window cycles
``HierarchicalCompressor.decompress`` over those archives."""
from __future__ import annotations

import numpy as np

from bench import checks, flops
from bench.modes import (chunk_refs, coded_share, fit_model, options,
                         slices, timed)


def setup(ctx) -> None:
    fit_model(ctx)
    ctx.slices = slices(ctx)
    opts = options(ctx)
    with timed(ctx, "archives"):
        ctx.archives = [ctx.comp.compress(s, options=opts)
                        for s in ctx.slices]
    with timed(ctx, "warm-up"):
        ctx.first_decodes = [ctx.comp.decompress(a) for a in ctx.archives]


def unit(ctx, i: int) -> dict:
    j = i % len(ctx.archives)
    out = ctx.comp.decompress(ctx.archives[j])
    return {"archive": j, "out": out, "bytes": out.nbytes}


def end_to_end(ctx, units: list[dict], seconds: float) -> dict[str, float]:
    share = coded_share(ctx.archives)
    ctx.facts["gae_coded_share"] = share
    ctx.facts["flops_per_value"] = flops.decompress_per_value(
        ctx.config["compressor"], share)
    return {"decompress_MBps": sum(r["bytes"] for r in units) / seconds / 1e6}


def check(ctx, units: list[dict], control: bool = False) -> dict[str, float]:
    """Every output of the window against the set-up decode of the same
    archive (bit for bit), and a sample of their chunks against the
    reference decode and the original field."""
    repeat = 0.0
    for rec in units:
        diff = np.abs(rec["out"] - ctx.first_decodes[rec["archive"]])
        repeat = max(repeat, float(diff.max()) if diff.size else 0.0)
    refs = chunk_refs(units, lambda rec: ctx.archives[rec["archive"]])
    rng = np.random.default_rng(ctx.check_seed)
    items = []
    for j in checks.sample(rng, len(refs), ctx.traffic["check_chunks"]):
        ref = refs[j]
        rec = units[ref.unit]
        items.append(checks.Item(
            x=ctx.slices[rec["archive"]][ref.start:ref.stop],
            chunk=ctx.archives[rec["archive"]].chunks[ref.index],
            decoded=rec["out"][ref.start:ref.stop]))
    numbers = checks.compare(items, checks.model_arrays(ctx.comp),
                             ctx.config["compressor"], ctx.tau, latents=False,
                             control=control)
    if not control:
        numbers["repeat_diff"] = repeat
    return numbers
