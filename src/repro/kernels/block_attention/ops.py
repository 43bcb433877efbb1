"""Jit'd public wrapper around the hyper-block attention kernel.

Handles arbitrary leading batch shape, folds the head axis into the batch
axis (the kernel is single-head, so each in-kernel matmul has one batch
dimension), pads the batch to the tile size (padded rows compute garbage that
is sliced away — softmax over real columns only, since padding is along
batch, never along n), and interprets off-TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.block_attention.kernel import block_attention_fwd

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("heads", "tile_b", "interpret"))
def block_attention(q: Array, k: Array, v: Array, *, heads: int = 1,
                    tile_b: int = 128, interpret: bool | None = None) -> Array:
    """q/k/v: (..., n, d) -> (..., n, d_v)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    *lead, n, dk = q.shape
    dv = v.shape[-1]
    b = 1
    for x in lead:
        b *= x

    def fold(t: Array) -> Array:      # (..., n, h*d) -> (b*h, n, d)
        t = t.reshape(b, n, heads, t.shape[-1] // heads)
        return t.transpose(0, 2, 1, 3).reshape(b * heads, n, -1)

    qf, kf, vf = fold(q), fold(k), fold(v)
    bh = b * heads
    tb = min(tile_b, bh)
    pad = -bh % tb
    if pad:
        qf, kf, vf = (jnp.pad(t, ((0, pad), (0, 0), (0, 0)))
                      for t in (qf, kf, vf))
    out = block_attention_fwd(qf, kf, vf, tile_b=tb, interpret=interpret)
    out = out[:bh].reshape(b, heads, n, dv // heads).transpose(0, 2, 1, 3)
    return out.reshape(*lead, n, dv)
