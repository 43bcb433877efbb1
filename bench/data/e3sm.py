"""E3SM PSL-like field, made on the device.

The formulas of ``repro.data.synthetic.e3sm_like`` and its
``_fourier_field``: zonal banding, 20 advecting Fourier eddies with 1/k
amplitudes and a per-mode time warp, a diurnal cycle, and noise at 5e-4 of
the field's spread; then the paper's z-score, blocks of (6, 16, 16), the
temporal grid axis fastest and 5 consecutive temporal blocks per
hyper-block.  The random draws come from ``jax.random`` (the noise from a
hash of each value's (t, y, x) position) and the arithmetic is float32, so
the values differ from the host generator's; sizes, normalization, blocking
and order are the same.

The field is evaluated straight at the hyper-block layout's coordinates:
a (t, h, w) array reshaped into (.., 6, 16, 16) blocks on a TPU pads its
16-wide minor axis to 128 lanes, and the paper-size field then needs more
than a chip's memory.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

N_MODES = 20
T_SPEED = 0.2
WARP = 0.6
NOISE = 5e-4


def _fmix32(h):
    """MurmurHash3's 32-bit finalizer."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def _hashed_normal(seeds, index):
    """A standard normal per position (Box-Muller on two hashed uniforms)."""
    index = index.astype(jnp.uint32)
    u1 = ((_fmix32(index ^ seeds[0]) >> 8) + 1).astype(jnp.float32) / 2**24
    u2 = (_fmix32(index ^ seeds[1]) >> 8).astype(jnp.float32) / 2**24
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos(2 * math.pi * u2)


def values(key, tt, yy, xx, shape):
    """The field, before normalization, at integer coordinates (t, y, x)."""
    t, h, w = shape
    ks = jax.random.split(key, 7)
    kx = jax.random.randint(ks[0], (N_MODES,), 1, max(2, w // 8))
    ky = jax.random.randint(ks[1], (N_MODES,), 1, max(2, h // 8))
    phase = jax.random.uniform(ks[2], (N_MODES,), maxval=2 * math.pi)
    omega = T_SPEED * jax.random.uniform(ks[3], (N_MODES,), minval=-1.0)
    aw = WARP * jax.random.uniform(ks[4], (N_MODES,))
    phi = jax.random.uniform(ks[5], (N_MODES,), maxval=2 * math.pi)
    k_noise = ks[6]
    tf, yf, xf = (a.astype(jnp.float32) for a in (tt, yy, xx))

    def add(i, acc):
        amp = 1.0 / jnp.hypot(kx[i], ky[i]).astype(jnp.float32)
        spatial = 2 * math.pi * (kx[i] * xf / w + ky[i] * yf / h)
        tw = tf + aw[i] * t / (2 * math.pi) * jnp.sin(
            2 * math.pi * tf / t + phi[i])
        return acc + amp * jnp.cos(spatial + omega[i] * tw + phase[i])

    eddies = jax.lax.fori_loop(0, N_MODES, add, jnp.zeros(tf.shape,
                                                          jnp.float32))
    lat = -math.pi / 2 + math.pi * yf / max(h - 1, 1)
    zonal = 1013.0 + 8.0 * jnp.cos(2 * lat) - 3.0 * jnp.cos(4 * lat)
    diurnal = 1.5 * jnp.sin(2 * math.pi * tf / 24.0)
    out = zonal + 6.0 * eddies + diurnal
    seeds = jax.random.bits(k_noise, (2,), jnp.uint32)
    return out + NOISE * _hashed_normal(seeds, (tt * h + yy) * w + xx) \
        * jnp.std(out)


def field(key, t: int, h: int, w: int):
    """(t, h, w) field in grid order, before normalization."""
    tt, yy, xx = jnp.meshgrid(jnp.arange(t), jnp.arange(h), jnp.arange(w),
                              indexing="ij")
    return values(key, tt, yy, xx, (t, h, w))


def block_coords(shape, block_shape, k: int):
    """(t, y, x) of every value of the (N, k, bt*bh*bw) hyper-block array:
    blocks ordered with the temporal grid axis fastest, k consecutive
    temporal blocks per hyper-block, values in (bt, bh, bw) order."""
    t, h, w = shape
    bt, bh, bw = block_shape
    gt, gx = t // bt, w // bw
    n = (h // bh) * gx * gt // k
    dims = (n, k, bt * bh * bw)
    hb = jax.lax.broadcasted_iota(jnp.int32, dims, 0)
    j = jax.lax.broadcasted_iota(jnp.int32, dims, 1)
    v = jax.lax.broadcasted_iota(jnp.int32, dims, 2)
    per_column = gt // k
    ht, column = hb % per_column, hb // per_column
    tt = (ht * k + j) * bt + v // (bh * bw)
    yy = (column // gx) * bh + (v // bw) % bh
    xx = (column % gx) * bw + v % bw
    return tt, yy, xx


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, shape, block_shape, k):
    data = values(key, *block_coords(shape, block_shape, k), shape)
    return (data - jnp.mean(data)) / jnp.maximum(jnp.std(data), 1e-12)


def hyperblocks(config: dict, seed: int) -> np.ndarray:
    hb = _make(jax.random.key(seed), tuple(config["shape"]),
               tuple(config["block_shape"]), config["compressor"]["k"])
    out = np.asarray(jax.device_get(hb))
    hb.delete()
    return out
