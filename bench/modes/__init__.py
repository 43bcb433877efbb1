"""Drivers of the entry points a window drives, one module per mode.

A mode module has four functions, each taking the run's context:

* ``setup(ctx)``: build the state the window needs and run every program
  the window will run, at its shapes;
* ``unit(ctx, i)``: the ``i``-th unit of work of the window; returns a dict
  with the ``bytes`` of float32 values it processed;
* ``end_to_end(ctx, units, seconds)``: the end-to-end metrics it can give;
* ``check(ctx, units, control)``: the numbers that decide ``correct``.

The helpers below are shared by the compress and decompress drivers.
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import numpy as np


@contextlib.contextmanager
def timed(ctx, part: str):
    """Wall seconds of one part of the set-up, printed with the result."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        ctx.setup_parts[part] = time.perf_counter() - t0


def compressor_config(config: dict):
    from repro.core.pipeline import CompressorConfig
    return CompressorConfig(**config["compressor"],
                            epochs_hbae=config["epochs_hbae"],
                            epochs_bae=config["epochs_bae"])


def d_gae(config: dict) -> int:
    c = config["compressor"]
    return c.get("gae_block_elems") or c["block_elems"]


def fit_model(ctx) -> None:
    """Make the field on the device, fit the model on all of it, and fit the
    PCA basis: what a user does before the first compress.  The field and
    the fit follow the configuration's own seeds, so every run does the
    same work (PERF.md)."""
    import jax

    from bench import data
    from repro.core.pipeline import HierarchicalCompressor

    with timed(ctx, "data"):
        ctx.hb = data.hyperblocks(ctx.config, ctx.config["field_seed"])
    comp = HierarchicalCompressor(compressor_config(ctx.config))
    with timed(ctx, "fit"):
        comp.fit(ctx.hb, seed=ctx.config["fit_seed"])
        jax.block_until_ready((comp.hbae_params, comp.bae_params))
    with timed(ctx, "basis"):
        comp.fit_basis(ctx.hb)
    ctx.comp = comp
    ctx.tau = ctx.traffic["tau_rms"] * math.sqrt(d_gae(ctx.config))


def slices(ctx) -> list[np.ndarray]:
    """``slices`` slices of at most ``slice_bytes`` each, made of whole
    stripes of ``chunk_hyperblocks`` spread evenly over the field and dealt
    out at random from the run's seed, so that every slice samples the
    whole field alike and together they hold the same stripes on every
    seed."""
    n, k, d = ctx.hb.shape
    chunk = ctx.traffic["chunk_hyperblocks"]
    per = ctx.traffic["slice_bytes"] // (k * d * 4 * chunk)
    n_stripes = n // chunk
    count = min(ctx.traffic["slices"], n_stripes // max(per, 1))
    if per < 1 or count < 1:
        raise ValueError(f"{n} hyper-blocks hold no slice of "
                         f"{ctx.traffic['slice_bytes']} bytes in stripes of "
                         f"{chunk}")
    stripes = ctx.hb[:n_stripes * chunk].reshape(n_stripes, chunk, k, d)
    picks = (np.arange(count * per) * n_stripes) // (count * per)
    picks = np.random.default_rng(ctx.deal_seed).permutation(picks)
    return [stripes[np.sort(picks[s::count])].reshape(-1, k, d)
            for s in range(count)]


def options(ctx):
    from repro.core.options import CompressOptions
    return CompressOptions(tau=ctx.tau,
                           chunk_hyperblocks=ctx.traffic["chunk_hyperblocks"])


def coded_share(archives) -> float:
    """Share of GAE blocks that keep at least one coefficient: a block's
    index set is empty exactly when its bitmask prefix has length 0."""
    import struct
    import zlib
    coded = total = 0
    for archive in archives:
        for chunk in archive.chunks:
            raw = zlib.decompress(chunk.gae_index_blob)
            n, _ = struct.unpack("<II", raw[:8])
            lens = np.frombuffer(raw[8:8 + 4 * n], np.uint32)
            coded += int(np.count_nonzero(lens))
            total += n
    return coded / total if total else 0.0


@dataclasses.dataclass
class ChunkRef:
    """Where one sampled chunk lies: unit, chunk index, hyper-block range."""
    unit: int
    index: int
    start: int
    stop: int


def chunk_refs(units: list[dict], archive_of) -> list[ChunkRef]:
    refs = []
    for u, rec in enumerate(units):
        for ci, chunk in enumerate(archive_of(rec).chunks):
            refs.append(ChunkRef(u, ci, chunk.hb_start,
                                 chunk.hb_start + chunk.n_hyperblocks))
    return refs
