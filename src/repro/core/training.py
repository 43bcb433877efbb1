"""Training loops for the compressor (paper Sec. III-C).

The HBAE is trained first, then the BAE on the HBAE residuals (stacked BAE
stages for the StackAE ablation).  MSE loss, Adam lr=1e-3 as in the paper.
Data-parallel training over hyper-blocks is expressed with
``jax.jit(in_shardings=...)`` in ``repro.launch.train``; the loops here are
mesh-agnostic (they jit plain update steps and stream minibatches).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bae as bae_mod
from repro.core import hbae as hbae_mod
from repro.train import optim as optim_mod

Array = jax.Array


def _minibatches(rng: np.random.Generator, n: int, batch: int, epochs: int):
    for _ in range(epochs):
        order = rng.permutation(n)
        for i in range(0, n - batch + 1, batch):
            yield order[i:i + batch]


#: a TPU vector register's lanes: rows padded to a multiple of this are
#: gathered row by row
LANES = 128


def _device_rows(data: np.ndarray, width: int) -> Array:
    """The training data on the device as rows of ``width`` values, each
    padded with zeros to whole 128-lane tiles.  A TPU gathers the rows of
    such an array by moving just those rows; from an (N, k, D) array, or
    rows of a width that is not a multiple of 128, it copies the whole array
    for every gather (a 4.75 GB field, 4,200 times in S3D's fit)."""
    rows = jnp.asarray(np.asarray(data).reshape(-1, width))
    pad = -width % LANES
    return jnp.pad(rows, ((0, 0), (0, pad))) if pad else rows


@functools.partial(jax.jit, static_argnames=("shape",))
def _batch(rows: Array, idx: Array, shape: tuple) -> Array:
    """Items ``idx`` of the data ``_device_rows`` holds, each
    ``prod(shape[:-1])`` consecutive rows cut back to ``shape[-1]`` values:
    a (len(idx),) + shape batch, the same values as indexing the data."""
    per = math.prod(shape[:-1])
    take = (idx[:, None] * per + jnp.arange(per)).reshape(-1)
    return rows[take, :shape[-1]].reshape((idx.shape[0],) + shape)


# ---------------------------------------------------------------------------
# HBAE
# ---------------------------------------------------------------------------

def hbae_loss(params: dict, x: Array) -> Array:
    y, _ = hbae_mod.hbae_apply(params, x)
    return jnp.mean(jnp.square(y - x))


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 1))
def _hbae_step(params, opt_state, x, opt):
    loss, grads = jax.value_and_grad(hbae_loss)(params, x)
    params, opt_state, _ = opt.update(grads, opt_state, params)
    return params, opt_state, loss


def train_hbae(key: Array, hyperblocks: np.ndarray, *, emb: int = 128,
               hidden: int = 256, latent: int = 128, heads: int = 1,
               use_attention: bool = True, epochs: int = 30, batch: int = 64,
               lr: float = 1e-3, seed: int = 0,
               log: Optional[Callable[[int, float], None]] = None) -> dict:
    n, k, d = hyperblocks.shape
    params = hbae_mod.hbae_init(key, in_dim=d, k=k, emb=emb, hidden=hidden,
                                latent=latent, heads=heads,
                                use_attention=use_attention)
    opt = optim_mod.adam(lr=lr)
    opt_state = opt.init(params)
    rng = np.random.default_rng(seed)
    batch = min(batch, n)
    data = _device_rows(hyperblocks, d)
    for step, idx in enumerate(_minibatches(rng, n, batch, epochs)):
        params, opt_state, loss = _hbae_step(
            params, opt_state, _batch(data, idx, (k, d)), opt)
        if log is not None and step % 50 == 0:
            log(step, float(loss))
    return params


# ---------------------------------------------------------------------------
# BAE
# ---------------------------------------------------------------------------

def bae_loss(params: dict, residual: Array) -> Array:
    r_hat, _ = bae_mod.bae_apply(params, residual)
    return jnp.mean(jnp.square(r_hat - residual))


@functools.partial(jax.jit, static_argnames=("opt",), donate_argnums=(0, 1))
def _bae_step(params, opt_state, r, opt):
    loss, grads = jax.value_and_grad(bae_loss)(params, r)
    params, opt_state, _ = opt.update(grads, opt_state, params)
    return params, opt_state, loss


def train_bae(key: Array, residuals: np.ndarray, *, hidden: int = 256,
              latent: int = 16, epochs: int = 30, batch: int = 256,
              lr: float = 1e-3, seed: int = 0,
              log: Optional[Callable[[int, float], None]] = None) -> dict:
    n, d = residuals.shape
    params = bae_mod.bae_init(key, in_dim=d, hidden=hidden, latent=latent)
    opt = optim_mod.adam(lr=lr)
    opt_state = opt.init(params)
    rng = np.random.default_rng(seed)
    batch = min(batch, n)
    data = _device_rows(residuals, d)
    for step, idx in enumerate(_minibatches(rng, n, batch, epochs)):
        params, opt_state, loss = _bae_step(
            params, opt_state, _batch(data, idx, (d,)), opt)
        if log is not None and step % 100 == 0:
            log(step, float(loss))
    return params
