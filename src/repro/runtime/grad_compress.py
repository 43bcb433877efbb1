"""Gradient compression for data-parallel all-reduce — the paper's GAE insight
applied to the DP collective (DESIGN.md §2).

The GAE mechanism (project a residual onto a shared orthonormal basis, keep
the leading coefficients, quantize, and error-feed the tail) is *linear*, so
coefficients aggregate exactly across data-parallel workers:

    mean_i(U^T g_i) = U^T mean_i(g_i).

Every worker therefore all-reduces only a rank-M coefficient tensor instead of
the full gradient — a PowerSGD-class scheme, but with the paper's machinery:
a fixed shared orthonormal basis (deterministic QR of a seeded Gaussian, so
all workers build an identical U with zero communication), per-block leading-M
projection, optional uniform quantization of the coefficients, and per-worker
**error feedback** that re-injects the discarded tail into the next step's
gradient (keeping the compressed SGD unbiased in the long run).

Shapes: every float leaf of the gradient pytree is flattened and blocked into
``block``-length vectors (zero-padded); the coefficient tensor per leaf is
(n_blocks, rank).  Compression payload ratio ~ rank/block (plus 4-byte scale).

Two modes:
  * ``pca_ef``  — rank-M, quantized, error feedback (DP-aggregatable), with
    **adaptive basis refresh**: a fixed basis never transmits the gradient
    component orthogonal to its span, so the error-feedback buffer grows
    linearly (a real failure mode — property-tested).  Every
    ``refresh_every`` steps the basis is recomputed as the top-``rank``
    eigenvectors of the block covariance of (grad + error) — the paper's own
    distributed-PCA machinery (Sec. II-D adapted): the covariance is a single
    (block x block) psum across workers, so every worker derives an IDENTICAL
    basis and coefficients stay exactly aggregatable.
  * ``gae``     — tau-driven per-block M via the one-shot GAE selection; the
    realized per-block l2 distortion of the *local* gradient is <= tau.  The
    variable-length index sets make it a storage/offload format (checkpoint
    deltas, gradient logging) rather than an all-reduce payload; aggregation
    support is the fixed-rank mode above.

Everything is jit-compatible; ``axis_name`` switches the same code between
single-process and shard_map'd multi-worker execution.
"""
from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.quantization import dequantize, quantize

PyTree = Any
Array = jax.Array


class GradCompressionState(NamedTuple):
    basis: Array          # (block, rank) shared orthonormal basis
    error: PyTree         # per-leaf error-feedback buffers (leaf-shaped, f32)
    step: Array


def make_basis(block: int, rank: int, seed: int = 17) -> Array:
    """Deterministic orthonormal (block, rank) basis — identical on every
    worker from the seed alone (no broadcast needed)."""
    g = jax.random.normal(jax.random.PRNGKey(seed), (block, max(rank, 1)),
                          jnp.float32)
    q, _ = jnp.linalg.qr(g)
    return q[:, :rank]


def _is_float(x) -> bool:
    return jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating)


def init_state(params: PyTree, *, block: int = 256, rank: int = 32,
               seed: int = 17) -> GradCompressionState:
    error = jax.tree.map(
        lambda p: jnp.zeros(p.shape, jnp.float32) if _is_float(p) else None,
        params)
    return GradCompressionState(basis=make_basis(block, rank, seed),
                                error=error, step=jnp.zeros((), jnp.int32))


def _blocked(x: Array, block: int) -> Array:
    flat = x.astype(jnp.float32).reshape(-1)
    pad = -flat.size % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, block)


def _unblocked(blocks: Array, shape: tuple, dtype) -> Array:
    n = math.prod(shape)          # python-level: shape is static under jit
    return blocks.reshape(-1)[:n].reshape(shape).astype(dtype)


def compress_update(grads: PyTree, state: GradCompressionState, *,
                    bin_size: float = 0.0,
                    axis_name: Optional[str] = None,
                    refresh_every: int = 50
                    ) -> tuple[PyTree, GradCompressionState, dict]:
    """Rank-M compressed-aggregate gradients with error feedback and adaptive
    basis refresh (module docstring).

    Returns (approx mean gradient, new state, stats).  Under ``axis_name``
    (shard_map / pmap axis) only the rank-M coefficients (and, on refresh
    steps, one block x block covariance) are ``pmean``ed — that reduction IS
    the compressed all-reduce; without it the function is the single-worker
    reference semantics.
    """
    block = state.basis.shape[0]      # block length is defined by the basis
    rank = state.basis.shape[1]

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = treedef.flatten_up_to(state.error)

    # ---- adaptive basis refresh (the paper's distributed PCA on gradients)
    if refresh_every:
        cov = jnp.zeros((block, block), jnp.float32)
        for g, e in zip(flat_g, flat_e):
            if g is None or not _is_float(g):
                continue
            hb = _blocked(g.astype(jnp.float32) +
                          (e if e is not None else 0.0), block)
            cov = cov + hb.T @ hb
        if axis_name is not None:
            cov = jax.lax.pmean(cov, axis_name)

        def refreshed(cov):
            _, vecs = jnp.linalg.eigh(cov)
            return vecs[:, ::-1][:, :rank]

        basis = jax.lax.cond(state.step % refresh_every == 0,
                             refreshed, lambda _: state.basis, cov)
    else:
        basis = state.basis

    comm_elems = jnp.zeros((), jnp.float32)
    raw_elems = jnp.zeros((), jnp.float32)

    def leaf(g, e):
        nonlocal comm_elems, raw_elems
        if g is None or not _is_float(g):
            return g, e
        h = g.astype(jnp.float32) + (e if e is not None else 0.0)
        hb = _blocked(h, block)                           # (nb, block)
        c = hb @ basis                                     # (nb, rank)
        if bin_size > 0:
            cq = dequantize(quantize(c, bin_size), bin_size)
        else:
            cq = c
        # local decompression (for error feedback) BEFORE aggregation
        recon_local = cq @ basis.T
        e_new = _unblocked(hb - recon_local, h.shape, jnp.float32)
        if axis_name is not None:
            cq = jax.lax.pmean(cq, axis_name)              # the compressed AR
        recon = cq @ basis.T
        ghat = _unblocked(recon, g.shape, g.dtype)
        comm_elems += cq.size
        raw_elems += g.size
        return ghat, e_new

    out = [leaf(g, e) for g, e in zip(flat_g, flat_e)]
    new_g = treedef.unflatten([o[0] for o in out])
    new_e = treedef.unflatten([o[1] for o in out])
    if refresh_every:   # amortized covariance-psum cost of the basis refresh
        comm_elems += block * block / refresh_every
    stats = {"comm_elems": comm_elems, "raw_elems": raw_elems,
             "compression": raw_elems.astype(jnp.float32) /
             jnp.maximum(comm_elems.astype(jnp.float32), 1.0)}
    return new_g, GradCompressionState(basis=basis, error=new_e,
                                       step=state.step + 1), stats


# ---------------------------------------------------------------------------
# tau-driven GAE mode (bounded per-block distortion; storage/offload format)
# ---------------------------------------------------------------------------

def gae_compress_grads(grads: PyTree, *, tau: float, bin_size: float = 1e-4,
                       block: int = 256) -> tuple[PyTree, dict]:
    """Per-block guaranteed ||g - g^G||_2 <= tau using the paper's one-shot
    selection (Algorithm 1, batched form).  Returns (bounded grads, stats)."""
    from repro.core.gae import fit_pca_basis, gae_correct, gae_select

    kept = jnp.zeros((), jnp.float32)
    total = jnp.zeros((), jnp.float32)

    def leaf(g):
        nonlocal kept, total
        if g is None or not _is_float(g):
            return g
        gb = _blocked(g, block)
        basis = fit_pca_basis(gb)
        sel = gae_select(gb, basis, tau, bin_size)
        kept += jnp.sum(sel.m)
        total += gb.size
        corrected, _ = gae_correct(gb, basis, sel, bin_size)
        return _unblocked(corrected, g.shape, g.dtype)

    out = jax.tree.map(leaf, grads)
    return out, {"kept_coeffs": kept, "total_elems": total,
                 "keep_frac": kept / jnp.maximum(total, 1.0)}
