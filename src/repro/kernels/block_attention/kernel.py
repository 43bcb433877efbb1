"""Fused hyper-block attention Pallas kernel (HBAE, paper Eq. 6 core).

TPU adaptation (DESIGN.md §4): the HBAE attends over only k <= 16 block
embeddings of d = 128 per hyper-block — a *tiny-n, batch-huge* attention.
FlashAttention-style KV streaming is pointless at n = 10; the win is batching
``tb`` whole hyper-blocks into one VMEM tile of shape (tb, n, d) and fusing
QK^T -> softmax -> PV for the whole tile so the intermediates (tb, n, n) never
round-trip to HBM.  Softmax numerics are fp32 on-chip; I/O keeps the input
dtype.  The grid is 1-D over hyper-block tiles — every cell independent
("parallel" semantics).

Each in-kernel matmul has one batch dimension, which is all the TPU's Mosaic
compiler accepts: the wrapper (``ops.block_attention``) folds the head axis
into the batch axis, so the kernel always sees single-head rows.

VMEM budget: 4 tensors x tb*n_pad*d*4 B, double-buffered, + scores
tb*n*n*4 B, where n_pad is n rounded up to the 8-row sublane tile; at tb=128,
n=10 (n_pad=16), d=128 that's 2 x 4 MB + 0.05 MB, under the 16 MB scoped
VMEM limit of v5e (tb=256 at n=16 is refused for VMEM when compiled for
v5e).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _block_attn_kernel(q_ref, k_ref, v_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)            # (tb, n, d)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    scores = jnp.einsum("bqd,bkd->bqk", q, k,
                        preferred_element_type=jnp.float32)
    scores = scores / jnp.sqrt(jnp.asarray(q.shape[-1], jnp.float32))
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    w = jnp.exp(scores)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    ctx = jnp.einsum("bqk,bkd->bqd", w, v, preferred_element_type=jnp.float32)
    o_ref[...] = ctx.astype(o_ref.dtype)


def block_attention_fwd(q: Array, k: Array, v: Array, *, tile_b: int = 128,
                        interpret: bool = False) -> Array:
    """Single-head q/k/v: (B, n, d) with B a multiple of tile_b (wrapper
    pads and folds heads into B)."""
    b, n, dk = q.shape
    dv = v.shape[-1]
    tile_b = min(tile_b, b)
    assert b % tile_b == 0, (b, tile_b)
    grid = (b // tile_b,)
    return pl.pallas_call(
        _block_attn_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((tile_b, n, dk), lambda i: (i, 0, 0)),
                  pl.BlockSpec((tile_b, n, dk), lambda i: (i, 0, 0)),
                  pl.BlockSpec((tile_b, n, dv), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((tile_b, n, dv), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, n, dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(q, k, v)
