"""The numbers that decide ``correct``: what the timed path produced,
compared with the plain reference (``bench/reference.py``), over a sample of
archive chunks drawn from the seed.

The autoencoders' arithmetic is judged against what a lower-precision path
would give on the same chunks: the reference recomputed with fp8 operands
for the autoencoders and bf16 for the GAE correction (the control).  On a
TPU the program's default-precision matmuls deviate from float64 by an
amount that follows the trained model's scale, from seed to seed, by
several times; the fp8 deviation follows it alike, so the share is steady:

* ``latent_gap``: the widest gap, in bins, between an archived latent and
  the reference encoder's latent, less the half bin of rounding, over the
  same for the fp8 encoder's latents (AE front end and entropy coding);
* ``recon_gap``: the widest per-GAE-block l2 distance between the program's
  decode and the reference decode of the same bytes, over the widest of the
  fp8 decode (entropy decode, AE decode, GAE correction);
* ``tau_excess``: per GAE block, ||x - decode|| / tau - 1 for the program's
  decode (the guarantee; the compression CLI allows 1e-5 of slack).

The control itself, put in the program's place, reads 1 on both shares.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np

from bench import reference

CONTROL_PRECISION = "fp8"
CONTROL_GAE_PRECISION = "bf16"


def model_arrays(comp) -> tuple[dict, list, np.ndarray]:
    """The decoder-side model as float64 numpy trees."""
    hbae, baes = jax.device_get((comp.hbae_params, comp.bae_params))
    return plain(hbae), [plain(p) for p in baes], np.asarray(comp.basis,
                                                             np.float64)


def plain(tree, dtype=np.float64):
    """A parameter tree as nested dicts of arrays, static leaves dropped."""
    if isinstance(tree, dict):
        return {k: plain(v, dtype) for k, v in tree.items()
                if isinstance(v, dict) or hasattr(v, "shape")}
    return np.asarray(tree, dtype)


def sample(rng: np.random.Generator, n: int, want: int) -> list[int]:
    return sorted(rng.choice(n, size=min(n, want), replace=False).tolist())


def decode_one_chunk(comp, archive, chunk) -> np.ndarray:
    """The program's decode of one chunk on its own, at the stripe shape it
    was coded at (a one-chunk archive that starts at hyper-block 0)."""
    from repro.core.pipeline import Archive
    cfg = comp.cfg
    n_hb = chunk.n_hyperblocks
    sub = Archive(n_hyperblocks=n_hb, n_values=n_hb * cfg.k * cfg.block_elems,
                  chunk_hyperblocks=archive.chunk_hyperblocks,
                  gae_dim=archive.gae_dim,
                  chunks=[dataclasses.replace(chunk, hb_start=0)])
    return comp.decompress(sub)


def _widest(current: float, new: float) -> float:
    """max() that keeps a NaN: a decode that made one is never a pass."""
    return new if (new != new or new > current) else current


def _block_norms(a: np.ndarray, b: np.ndarray, d_gae: int) -> np.ndarray:
    diff = (np.asarray(a, np.float64) - np.asarray(b, np.float64))
    return np.linalg.norm(diff.reshape(-1, d_gae), axis=1)


@dataclasses.dataclass
class Item:
    """One sampled chunk: the original hyper-blocks, the archive chunk, and
    the program's decode of it."""
    x: np.ndarray
    chunk: object
    decoded: np.ndarray


def compare(items: list[Item], model, cfg: dict, tau: float, *,
            latents: bool, control: bool = False) -> dict[str, float]:
    """The numbers over ``items``; with ``control`` the lower-precision
    reference stands in for the program."""
    hbae, baes, basis = model
    d_gae = basis.shape[0]
    widest = {"prog": 0.0, "low": 0.0, "prog_lat": 0.0, "low_lat": 0.0}
    excess = -1.0
    for it in items:
        x_ref, codes = reference.decode_chunk(it.chunk, hbae, baes, basis, cfg)
        low, _ = reference.decode_chunk(it.chunk, hbae, baes, basis, cfg,
                                        CONTROL_PRECISION, CONTROL_GAE_PRECISION)
        decoded = low if control else it.decoded
        widest["prog"] = _widest(widest["prog"], float(
            _block_norms(decoded, x_ref, d_gae).max()))
        widest["low"] = _widest(widest["low"], float(
            _block_norms(low, x_ref, d_gae).max()))
        excess = _widest(excess, float(
            _block_norms(it.x, decoded, d_gae).max() / tau - 1.0))
        if latents:
            low_codes = reference.control_codes(it.x, hbae, baes, cfg,
                                                CONTROL_PRECISION)
            low_gap = reference.latent_gap(it.x, low_codes, hbae, baes, cfg)
            widest["low_lat"] = _widest(widest["low_lat"], low_gap)
            widest["prog_lat"] = _widest(widest["prog_lat"], low_gap if control
                                         else reference.latent_gap(
                                             it.x, codes, hbae, baes, cfg))
    if not widest["low"] > 0 or (latents and not widest["low_lat"] > 0.5):
        raise ValueError(f"the fp8 control matched the reference exactly or "
                         f"made no number: {widest}")
    out = {"recon_gap": widest["prog"] / widest["low"], "tau_excess": excess}
    if latents:
        # a latent rounded exactly is off by up to half a bin
        out["latent_gap"] = ((widest["prog_lat"] - 0.5)
                             / (widest["low_lat"] - 0.5))
    return out


def judge(numbers: dict[str, float], limits: dict[str, float]) -> tuple[
        bool, dict[str, dict[str, float]]]:
    """Each number beside its limit; correct when none is over (a missing
    limit is a fault of the cell's files, not a pass)."""
    table = {name: {"value": value, "limit": float(limits[name])}
             for name, value in numbers.items()}
    ok = all(np.isfinite(v["value"]) and v["value"] <= v["limit"]
             for v in table.values())
    return ok, table
