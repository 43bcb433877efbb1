"""Jit'd public wrapper around the fused quantize kernel: any input shape,
padded to whole 2-D tiles underneath, interpret off-TPU."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.quantize.kernel import quantize_fused_fwd

Array = jax.Array


@functools.partial(jax.jit, static_argnames=("bin_size", "interpret"))
def quantize_fused(x: Array, bin_size: float,
                   interpret: bool | None = None) -> tuple[Array, Array, Array]:
    """x: any shape -> (q int32, deq x.dtype, err2 fp32), all shaped like x."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    shape = x.shape
    flat = x.reshape(-1)
    c = min(512, flat.size)
    rows = -(-flat.size // c)
    tr = min(256, rows)                       # the kernel's row tile
    pad = -(-rows // tr) * tr * c - flat.size
    if pad:
        flat = jnp.pad(flat, (0, pad))
    x2 = flat.reshape(-1, c)
    q, deq, err2 = quantize_fused_fwd(x2, bin_size=float(bin_size),
                                      interpret=interpret)
    q, deq, err2 = (t.reshape(-1)[:x.size].reshape(shape) for t in (q, deq, err2))
    return q, deq, err2
