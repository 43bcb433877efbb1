"""S3D-like multi-species field, made on the device.

The formulas of ``repro.data.synthetic.s3d_like`` and its
``_fourier_field``: 8 latent fields, each 12 advecting Fourier modes with
1/k amplitudes and a per-mode time warp; each of the 58 species mixes the
latents with a unit-norm row of normal weights, then takes
``scale * exp(gain * tanh(.))`` with gain ~ U(0.5, 2) and scale ~
exp(U(-3, 3)); noise at 1e-3 of the field's spread.  Then the paper's
per-species normalization (mean 0, range 1), blocks of (58, 5, 4, 4) with
the temporal grid axis fastest, and 10 consecutive temporal blocks per
hyper-block, as ``synthetic.make_dataset("s3d")`` orders them.  The random
draws come from ``jax.random`` (the noise from a hash of each value's
(species, t, y, x) position) and the arithmetic is float32, so the values
differ from the host generator's; sizes, normalization, blocking and order
are the same.

The field is evaluated straight at the hyper-block layout (N, k, 4640): the
latents at (N * k, 8 * 80), each row one temporal block of one (y, x)
column, then one matmul with the (8 * 80, 58 * 80) mixing matrix gives
every species of every block in the order of the block's 4640 values.  A
(.., 4, 4)-minor layout would pad to 128 lanes on a TPU, 32 times the bytes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

RANK = 8
N_MODES = 12
T_SPEED = 0.35
WARP = 0.6
NOISE = 1e-3


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


def draws(key, shape) -> dict:
    """Every random number of the field: the latents' modes (RANK,
    N_MODES), the species' mixing rows, gains and scales, and the seeds of
    the noise's hash."""
    s, t, h, w = shape
    ks = jax.random.split(key, 10)
    modes = (RANK, N_MODES)
    mix = jax.random.normal(ks[6], (s, RANK))
    return {
        "kx": jax.random.randint(ks[0], modes, 1, max(2, w // 8)),
        "ky": jax.random.randint(ks[1], modes, 1, max(2, h // 8)),
        "phase": jax.random.uniform(ks[2], modes, maxval=2 * math.pi),
        "omega": T_SPEED * jax.random.uniform(ks[3], modes, minval=-1.0),
        "aw": WARP * jax.random.uniform(ks[4], modes),
        "phi": jax.random.uniform(ks[5], modes, maxval=2 * math.pi),
        "mix": mix / jnp.linalg.norm(mix, axis=1, keepdims=True),
        "gain": jax.random.uniform(ks[7], (s,), minval=0.5, maxval=2.0),
        "scale": jnp.exp(jax.random.uniform(ks[8], (s,), minval=-3.0,
                                            maxval=3.0)),
        "noise": jax.random.bits(ks[9], (2,), jnp.uint32),
    }


def latent(d: dict, coords, shape):
    """The latent fields at the positions ``coords()`` gives as
    ``(r, t, y, x)`` integer arrays, broadcasting together: latent ``r`` at
    (t, y, x).  ``coords`` is called inside the loop over modes, so that on
    a device its index arithmetic fuses into each mode's and no coordinate
    array is kept."""
    _, t, h, w = shape

    def add(m, acc):
        r, tt, yy, xx = coords()
        tf, yf, xf = (a.astype(jnp.float32) for a in (tt, yy, xx))
        kx = d["kx"][r, m].astype(jnp.float32)
        ky = d["ky"][r, m].astype(jnp.float32)
        amp = 1.0 / jnp.hypot(kx, ky)
        spatial = 2 * math.pi * (kx * xf / w + ky * yf / h)
        tw = tf + d["aw"][r, m] * t / (2 * math.pi) * jnp.sin(
            2 * math.pi * tf / t + d["phi"][r, m])
        return acc + amp * jnp.cos(spatial + d["omega"][r, m] * tw
                                   + d["phase"][r, m])

    out_shape = jnp.broadcast_shapes(*(jnp.shape(a) for a in coords()))
    return jax.lax.fori_loop(0, N_MODES, add,
                             jnp.zeros(out_shape, jnp.float32))


def species(d: dict, s, base):
    """Species ``s``'s monotone nonlinearity of its latent mixture."""
    return d["scale"][s] * jnp.exp(d["gain"][s] * jnp.tanh(base))


def noise(d: dict, index, spread):
    """Noise at ``NOISE`` of the field's ``spread``, hashed by position."""
    return NOISE * _hashed_normal(d["noise"], index) * spread


def field(key, shape):
    """(species, t, h, w) field in grid order, before normalization."""
    s, t, h, w = shape
    d = draws(key, shape)

    def coords():
        r = jnp.arange(RANK)[:, None, None, None]
        tt, yy, xx = jnp.meshgrid(jnp.arange(t), jnp.arange(h),
                                  jnp.arange(w), indexing="ij")
        return r, tt, yy, xx

    _, tt, yy, xx = coords()
    base = jnp.einsum("sr,rthw->sthw", d["mix"], latent(d, coords, shape),
                      precision=jax.lax.Precision.HIGHEST)
    sp = jnp.arange(s)[:, None, None, None]
    out = species(d, sp, base)
    return out + noise(d, ((sp * t + tt) * h + yy) * w + xx, jnp.std(out))


def block_coords(shape, block_shape, k: int, rows: int, cols: int, row0=0):
    """``(g, t, y, x)``, broadcasting to (rows, cols), of every entry of rows
    ``row0 + [0, rows)`` of a (N * k, cols) array whose row is one temporal block of one (y, x)
    column and whose column ``c`` is value ``c % e`` of a block's
    ``e = bt*bh*bw`` values of one species, in (bt, bh, bw) order, for
    group ``g = c // e`` (a latent or a species).  Hyper-blocks are ordered
    with the temporal grid axis fastest, k consecutive temporal blocks
    each."""
    _, t, h, w = shape
    _, bt, bh, bw = block_shape
    per_column, gx = t // bt // k, w // bw
    e = bt * bh * bw
    q = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) + row0
    c = jax.lax.broadcasted_iota(jnp.int32, (1, cols), 1)
    hb, j = q // k, q % k
    ht, column = hb % per_column, hb // per_column
    v = c % e
    tt = (ht * k + j) * bt + v // (bh * bw)
    yy = (column // gx) * bh + (v // bw) % bh
    xx = (column % gx) * bw + v % bw
    # the group of a column is a (1, cols) row, so that what is looked up
    # by group (a latent's modes, a species' gain) is gathered once per
    # column and not once per value
    return c // e, tt, yy, xx


#: hyper-blocks evaluated at once, at most: a few hundred MB of a chunk's
#: temporaries beside the field
CHUNK_HYPERBLOCKS = 1024


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _make(key, shape, block_shape, k):
    """The normalized field as (N * k, s * e) rows, in chunks of whole
    hyper-blocks, each pass evaluating the chunks anew: one pass for the
    field's spread, one for each species' mean and range with the noise,
    and one that writes the normalized values.  The field is the one array
    of its size."""
    s, t, h, w = shape
    if block_shape[0] != s:
        raise ValueError(f"blocks {block_shape} must hold all {s} species")
    d = draws(key, shape)
    e = int(np.prod(block_shape[1:]))
    n = (h // block_shape[2]) * (w // block_shape[3]) * (
        t // block_shape[1] // k)
    per = max(c for c in range(1, min(n, CHUNK_HYPERBLOCKS) + 1)
              if n % c == 0)
    rows, n_chunks = per * k, n // per
    # column r * e + u of the mixing matrix feeds value u of every species
    mixing = jnp.einsum("sr,uv->rusv", d["mix"], jnp.eye(e)).reshape(
        RANK * e, s * e)

    def clean(i):
        """A chunk's values without noise, and their positions."""
        row0 = i * rows
        lat = latent(d, lambda: block_coords(shape, block_shape, k, rows,
                                             RANK * e, row0), shape)
        base = jnp.dot(lat, mixing, precision=jax.lax.Precision.HIGHEST)
        sp, tt, yy, xx = block_coords(shape, block_shape, k, rows, s * e,
                                      row0)
        return species(d, sp, base), ((sp * t + tt) * h + yy) * w + xx

    def moments(i, acc):
        """Count, mean and summed squared deviation, chunk by chunk
        (Chan et al.'s pairwise update)."""
        count, mean, m2 = acc
        x, _ = clean(i)
        mean_c = jnp.mean(x)
        m2_c = jnp.sum(jnp.square(x - mean_c))
        total = count + x.size
        delta = mean_c - mean
        return (total, mean + delta * x.size / total,
                m2 + m2_c + delta * delta * count * x.size / total)

    zero = jnp.float32(0)
    _, _, m2 = jax.lax.fori_loop(0, n_chunks, moments, (zero, zero, zero))
    spread = jnp.sqrt(m2 / (n * k * s * e))

    def noisy(i):
        x, index = clean(i)
        return x + noise(d, index, spread)

    def extremes(i, acc):
        total, high, low = acc
        x = noisy(i)
        return (total + x.sum(axis=0), jnp.maximum(high, x.max(axis=0)),
                jnp.minimum(low, x.min(axis=0)))

    cols = jnp.zeros(s * e, jnp.float32)
    total, high, low = jax.lax.fori_loop(
        0, n_chunks, extremes, (cols, cols - jnp.inf, cols + jnp.inf))
    # per species mean 0, range 1, from the rows' sums and extremes
    mean = jnp.repeat(total.reshape(s, e).sum(axis=1) / (n * k * e), e)
    width = jnp.repeat(jnp.maximum(high.reshape(s, e).max(axis=1)
                                   - low.reshape(s, e).min(axis=1), 1e-12), e)

    def write(i, out):
        return jax.lax.dynamic_update_slice(
            out, (noisy(i) - mean) / width, (i * rows, 0))

    return jax.lax.fori_loop(0, n_chunks, write,
                             jnp.zeros((n * k, s * e), jnp.float32))


def hyperblocks(config: dict, seed: int) -> np.ndarray:
    from repro.core.pipeline import HierarchicalCompressor
    if not hasattr(HierarchicalCompressor, "residual_covariance"):
        # a program without the striped basis fit pushes the whole field
        # through one program, which does not fit one chip at this size:
        # say so now, not after minutes of set-up
        raise RuntimeError("this program fits its PCA basis on the whole "
                           "field at once; the S3D configuration needs the "
                           "striped fit (HierarchicalCompressor."
                           "residual_covariance)")
    k = config["compressor"]["k"]
    rows = _make(jax.random.key(seed), tuple(config["shape"]),
                 tuple(config["block_shape"]), k)
    out = np.asarray(jax.device_get(rows))
    rows.delete()
    # (N * k, D) on the device: an (N, k, D) array would pad k to 8s
    return out.reshape(-1, k, out.shape[1])
