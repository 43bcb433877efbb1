"""XGC-like velocity histograms, made on the device.

The formulas of ``repro.data.synthetic.xgc_like``: per node a drifting
anisotropic Maxwellian whose density, temperatures and drift are smooth
profiles along the nodes (Gaussian-smoothed noise, sigma 15 nodes, scaled to
fixed ranges); the 8 toroidal planes are near-copies (2% per-node and
per-plane jitter), plus noise at 1e-3 of the spread.  Then the paper's
z-score and one hyper-block per node: its 8 planes' (39, 39) histograms.
The random draws come from ``jax.random``; sizes, normalization and order
are those of ``synthetic.make_dataset``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

PLANE_JITTER = 0.02
NOISE = 1e-3


def _smooth_profile(key, nodes: int, lo: float, hi: float):
    raw = jax.random.normal(key, (nodes,))
    taps = jnp.arange(-50, 51, dtype=jnp.float32)
    kernel = jnp.exp(-0.5 * (taps / 15.0) ** 2)
    kernel = kernel / kernel.sum()
    sm = jnp.convolve(raw, kernel, mode="same",
                      precision=jax.lax.Precision.HIGHEST)
    sm = (sm - sm.min()) / jnp.maximum(jnp.ptp(sm), 1e-9)
    return lo + (hi - lo) * sm


def field(key, planes: int, nodes: int, v: int, v2: int):
    """(planes, nodes, v, v) histograms, before normalization."""
    ks = jax.random.split(key, 7)
    grid = jnp.linspace(-3.0, 3.0, v)
    vpar, vperp = jnp.meshgrid(grid, grid, indexing="ij")
    temp_par = _smooth_profile(ks[0], nodes, 0.6, 1.6)[:, None, None]
    temp_perp = _smooth_profile(ks[1], nodes, 0.6, 1.6)[:, None, None]
    drift = _smooth_profile(ks[2], nodes, -0.8, 0.8)[:, None, None]
    dens = _smooth_profile(ks[3], nodes, 0.5, 2.0)[:, None, None]
    base = dens * jnp.exp(-((vpar[None] - drift) ** 2) / (2 * temp_par)
                          - (vperp[None] ** 2) / (2 * temp_perp))
    pert = 1.0 + PLANE_JITTER * jax.random.normal(ks[4], (planes, nodes, 1, 1))
    shift = 1.0 + PLANE_JITTER * jax.random.normal(ks[5], (planes, 1, 1, 1))
    out = base[None] * pert * shift
    return out + NOISE * jax.random.normal(ks[6], out.shape) * jnp.std(out)


def block(data, k: int):
    """(planes, nodes, v, v) -> (nodes, planes, v*v): the planes at one node
    form a hyper-block."""
    p, n, v, v2 = data.shape
    assert p == k, (p, k)
    return data.transpose(1, 0, 2, 3).reshape(n, p, v * v2)


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, shape, k):
    data = field(key, *shape)
    data = (data - jnp.mean(data)) / jnp.maximum(jnp.std(data), 1e-12)
    return block(data, k)


def hyperblocks(config: dict, seed: int) -> np.ndarray:
    hb = _make(jax.random.key(seed), tuple(config["shape"]),
               config["compressor"]["k"])
    out = np.asarray(jax.device_get(hb))
    hb.delete()
    return out
