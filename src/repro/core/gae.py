"""GAE — Guaranteed-error-bound post-processing (paper Sec. II-D, Algorithm 1).

Given original blocks x, autoencoder reconstructions x^R and a user bound tau,
GAE projects each block residual onto a PCA basis U (fit on the residuals of
the whole dataset), keeps the top-M *quantized* coefficients per block with M
minimal such that ||x - x^G||_2 <= tau, and corrects x^G = x^R + U_s c_q.

One selection, checked against an oracle by tests:

* ``gae_reference_loop`` — a literal per-block port of the paper's Algorithm 1
  (serial ``while delta > tau: M += 1`` loop).  The oracle.
* ``gae_select`` — the selection the encoder runs, jitted, on every
  backend.  Because U is orthonormal, the post-correction error decomposes
  exactly in coefficient space as

      err^2(M) = sum_{k>M} c_(k)^2  +  sum_{k<=M} (c_(k) - q(c_(k)))^2

  over magnitude-sorted coefficients, so minimal M for EVERY block in a batch
  falls out of one projection (MXU matmul), one sort, two cumulative sums and
  one comparison — branch-free and batched.  This replaces the paper's serial
  re-quantize/re-reconstruct loop (GPU/CPU-style) with a one-shot form.

Scale: the basis comes from the D x D residual covariance
(``residual_covariance``), which adds up over disjoint sets of blocks, so the
compressor sums it stripe by stripe on the device (``psum``-ed over the data
axis of a mesh, O(D^2) communication independent of dataset size) and runs one
``eigh`` on the sum (``pca_basis``).
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.errors import GuaranteeUnsatisfiable
from repro.core.quantization import dequantize, quantize

Array = jax.Array


# ---------------------------------------------------------------------------
# PCA basis
# ---------------------------------------------------------------------------

def residual_covariance(residuals: Array,
                        axis_name: Optional[str] = None) -> Array:
    """``r.T @ r`` (D, D) of (N, D) block residuals in float32; with
    ``axis_name``, ``psum``-ed over that axis.  Covariances of disjoint sets
    of blocks add up to the covariance of their union, so a basis can be
    fitted on a field stripe by stripe."""
    r = residuals.astype(jnp.float32)
    cov = r.T @ r
    if axis_name is not None:
        cov = jax.lax.psum(cov, axis_name)
    return cov


def pca_basis(cov: Array) -> Array:
    """PCA basis of a residual covariance: U (D, D) with eigenvectors as
    COLUMNS, sorted by descending eigenvalue; coefficients are c = U^T r
    (paper Eq. 9)."""
    # eigh returns ascending eigenvalues; flip to descending.
    _, vecs = jnp.linalg.eigh(cov)
    return vecs[:, ::-1]


def fit_pca_basis(residuals: Array, axis_name: Optional[str] = None) -> Array:
    """PCA basis of (N, D) block residuals held in one array."""
    return pca_basis(residual_covariance(residuals, axis_name))


# ---------------------------------------------------------------------------
# one-shot batched selection (TPU adaptation)
# ---------------------------------------------------------------------------

class GAESelection(NamedTuple):
    m: Array            # (N,)   minimal M per block (0 = block already within tau)
    order: Array        # (N, D) basis indices sorted by coefficient magnitude desc
    q_sorted: Array     # (N, D) quantized (int) coefficients in sorted order
    corrected: Array    # (N, D) corrected residual reconstruction  U_s c_q
    err: Array          # (N,)   actual l2 error after correction
    ok: Array           # (N,)   bool, err <= tau achievable with this bin size


def gae_select(residuals: Array, basis: Array, tau: float, bin_size: float,
               *, use_kernel: bool = False) -> GAESelection:
    """Batched minimal-M selection. residuals: (N, D); basis: (D, D)."""
    r = residuals.astype(jnp.float32)
    if use_kernel:
        from repro.kernels.gae_project import ops as gp_ops
        c, c2 = gp_ops.gae_project(r, basis)
    else:
        c = r @ basis                                  # (N, D) coefficients
        c2 = jnp.square(c)

    order = jnp.argsort(-c2, axis=-1)                  # descending magnitude
    c_sorted = jnp.take_along_axis(c, order, axis=-1)
    c2_sorted = jnp.take_along_axis(c2, order, axis=-1)

    q_sorted = quantize(c_sorted, bin_size)
    deq = dequantize(q_sorted, bin_size)
    qerr2 = jnp.square(c_sorted - deq)

    total = jnp.sum(c2_sorted, axis=-1, keepdims=True)         # err^2(0) = ||r||^2
    tail2 = total - jnp.cumsum(c2_sorted, axis=-1)              # err tail for M=1..D
    kept2 = jnp.cumsum(qerr2, axis=-1)                          # quant err for M=1..D
    err2 = jnp.concatenate([total, tail2 + kept2], axis=-1)     # index M = 0..D

    ok_any = err2 <= tau * tau
    m = jnp.argmax(ok_any, axis=-1)                             # first M satisfying
    ok = jnp.any(ok_any, axis=-1)
    m = jnp.where(ok, m, residuals.shape[-1])                   # fall back to full-D

    # corrected residual: U @ (masked quantized coeffs un-permuted).  The
    # un-permute is a row-local GATHER via the inverse permutation — a row
    # scatter (.at[].set) here makes GSPMD replicate the whole coefficient
    # matrix across the mesh (§Perf gae_select iteration 2).
    keep = jnp.arange(residuals.shape[-1])[None, :] < m[:, None]
    deq_masked = jnp.where(keep, deq, 0.0)
    inv_order = jnp.argsort(order, axis=-1)
    c_hat = jnp.take_along_axis(deq_masked, inv_order, axis=-1)
    corrected = c_hat @ basis.T
    err = jnp.linalg.norm(r - corrected, axis=-1)
    return GAESelection(m=m, order=order, q_sorted=q_sorted, corrected=corrected,
                        err=err, ok=ok)


# ---------------------------------------------------------------------------
# literal Algorithm 1 (oracle; host-side, per block)
# ---------------------------------------------------------------------------

def gae_reference_loop(x: np.ndarray, x_r: np.ndarray, basis: np.ndarray,
                       tau: float, bin_size: float) -> tuple[np.ndarray, list[int]]:
    """Direct port of paper Algorithm 1. x, x_r: (N, D); returns (x^G, M list)."""
    x = np.asarray(x, np.float32)
    x_r = np.asarray(x_r, np.float32)
    u = np.asarray(basis, np.float32)
    out = x_r.copy()
    ms = []
    for i in range(x.shape[0]):
        xi, xr = x[i], x_r[i]
        delta = float(np.linalg.norm(xi - xr))
        if delta <= tau:
            ms.append(0)
            continue
        c = u.T @ (xi - xr)                            # line 6
        order = np.argsort(-np.square(c))              # sort c_k^2 desc
        m = 1
        while True:                                    # lines 8-14
            sel = order[:m]
            cq = np.round(c[sel] / bin_size) * bin_size
            xg = xr + u[:, sel] @ cq
            delta = float(np.linalg.norm(xi - xg))
            if delta <= tau or m >= x.shape[1]:
                break
            m += 1
        out[i] = xg
        ms.append(m)
    return out, ms


# ---------------------------------------------------------------------------
# host-side encoder with HARD guarantee (per-block bin fallback)
# ---------------------------------------------------------------------------

class GAEBlockCode(NamedTuple):
    m: int                  # number of kept coefficients
    indices: np.ndarray     # (m,) basis indices (int32), ASCENDING index order
    qcoeffs: np.ndarray     # (m,) quantized ints at bin_size / 2**bin_exp
    bin_exp: int            # per-block bin refinement exponent (usually 0)


def gae_encode_blocks(x: np.ndarray, x_r: np.ndarray, basis: np.ndarray,
                      tau: float, bin_size: float,
                      max_refine: int = 20) -> tuple[np.ndarray, list[GAEBlockCode]]:
    """Encode every block with a HARD ||x - x^G||_2 <= tau guarantee.

    Uses the one-shot vectorized selection, then verifies each block against
    the reconstruction the DECODER will compute from the emitted codes
    (``gae_decode_blocks``' host float32 arithmetic, run here on the same
    codes) — not against the selection's own ``corrected``, which on an
    accelerator may come from a lower-precision matmul.  Blocks over ``tau``
    are repaired in rounds: more coefficients first, then a halved bin (per-
    block ``bin_exp``), each round re-decoding and re-verifying the whole
    batch.  With a full-rank basis the quantization error goes to 0 under
    refinement; if the budget is exhausted with ``err > tau`` (rank-deficient
    basis, ``max_refine`` too small), raises ``GuaranteeUnsatisfiable``
    instead of emitting a block that violates the bound the caller would then
    claim.

    Code construction is vectorized (membership masks and the ascending-index
    extraction are whole-batch numpy passes); the per-block Python work is
    only the namedtuple per code, and the repair rounds touch only blocks
    whose verified error still exceeds ``tau``.

    Stages: ``gae_select`` (upload, selection, fetch of the codes) and
    ``gae_verify`` (the rest); counters ``gae.blocks``,
    ``gae.repaired_blocks`` (blocks the repair touched) and
    ``gae.repair_rounds``.
    """
    from repro.core import exec as exec_mod

    x = np.asarray(x, np.float32)
    x_r = np.asarray(x_r, np.float32)
    u = np.asarray(basis, np.float32)
    n, d = x.shape

    with exec_mod.stage("gae_select", x.size):
        select = exec_mod.cache().get("gae_select", gae_select,
                                      static_argnames=("use_kernel",))
        sel = select(exec_mod.to_device(x - x_r), exec_mod.to_device(u),
                     tau, bin_size)
        # only the codes leave the device: the encoder never trusts the
        # selection's own ``corrected``/``err``
        m_sel, order_sel, q_sorted = exec_mod.to_host(
            (sel.m, sel.order, sel.q_sorted))
    with exec_mod.stage("gae_verify", x.size):
        out, codes, repaired, rounds = _verify_codes(
            x, x_r, u, m_sel, order_sel, q_sorted, tau, bin_size, max_refine)
    exec_mod.counter_add("gae.blocks", n)
    exec_mod.counter_add("gae.repaired_blocks", repaired)
    exec_mod.counter_add("gae.repair_rounds", rounds)
    return out, codes


def _verify_codes(x: np.ndarray, x_r: np.ndarray, u: np.ndarray,
                  m_sel, order_sel, q_sorted, tau: float, bin_size: float,
                  max_refine: int
                  ) -> tuple[np.ndarray, list[GAEBlockCode], int, int]:
    """The host half of ``gae_encode_blocks``: codes from the selection,
    verified with the decoder's arithmetic and repaired.  Also returns how
    many blocks the repair touched and in how many rounds."""
    n, d = x.shape
    # batch extraction in ascending index order: scatter the kept-coefficient
    # membership and quantized values from sorted-magnitude space back to
    # index space, then one np.nonzero walks every block's set in index order.
    ms = np.asarray(m_sel, np.int64)
    order64 = np.asarray(order_sel, np.int64)
    keep = np.arange(d)[None, :] < ms[:, None]            # sorted-mag space
    mask = np.zeros((n, d), bool)
    np.put_along_axis(mask, order64, keep, axis=1)
    q_idx_space = np.zeros((n, d), np.int32)
    np.put_along_axis(q_idx_space, order64,
                      np.asarray(q_sorted, np.int32), axis=1)
    rows, cols = np.nonzero(mask)                          # row-major: ascending
    idx_all = cols.astype(np.int32)
    q_all = q_idx_space[rows, cols].astype(np.int64)
    bounds = np.zeros(n + 1, np.int64)
    np.cumsum(ms, out=bounds[1:])
    idx_list = [idx_all[bounds[i]:bounds[i + 1]] for i in range(n)]
    q_list = [q_all[bounds[i]:bounds[i + 1]] for i in range(n)]
    bin_exps = np.zeros(n, np.int64)

    # verify against the decoder's arithmetic & repair (numerical safety +
    # coarse-bin fallback), in rounds over the blocks still above tau
    out = _apply_codes(x_r, u, ms, idx_all, q_all, bin_exps, bin_size)
    bad = np.flatnonzero(np.linalg.norm(x - out, axis=1) > tau)
    repaired = np.zeros(n, bool)
    rounds = 0
    while bad.size:
        repaired[bad] = True
        rounds += 1
        stuck = bad[bin_exps[bad] >= max_refine]
        if stuck.size:
            i = int(stuck[0])
            raise GuaranteeUnsatisfiable(
                block=i, err=float(np.linalg.norm(x[i] - out[i])), tau=tau,
                max_refine=max_refine)
        c = (x[bad] - x_r[bad]) @ u
        order = np.argsort(-np.square(c), axis=1)
        for j, i in enumerate(bad.tolist()):
            if ms[i] < d:
                ms[i] = min(d, ms[i] + max(1, d // 32))
            else:
                bin_exps[i] += 1
            idx = np.sort(order[j, :ms[i]]).astype(np.int32)
            idx_list[i] = idx
            q_list[i] = np.round(
                c[j, idx] / (bin_size / 2 ** bin_exps[i])).astype(np.int64)
        out = _apply_codes(x_r, u, ms, np.concatenate(idx_list),
                           np.concatenate(q_list), bin_exps, bin_size)
        bad = np.flatnonzero(np.linalg.norm(x - out, axis=1) > tau)

    ms_list = ms.tolist()
    be_list = bin_exps.tolist()
    codes = [GAEBlockCode(ms_list[i], idx_list[i], q_list[i], be_list[i])
             for i in range(n)]
    return out, codes, int(repaired.sum()), rounds


def _apply_codes(x_r: np.ndarray, u: np.ndarray, ms: np.ndarray,
                 cols: np.ndarray, qs: np.ndarray, bin_exps: np.ndarray,
                 bin_size: float) -> np.ndarray:
    """x^R + U c for flat codes: block i owns the next ``ms[i]`` entries of
    ``cols``/``qs``.  The one place the GAE correction is computed, so the
    encoder verifies exactly the floats the decoder will produce.

    The product runs on one BLAS thread (``exec.single_blas_thread``), so
    no worker count or core count changes its rounding; the counters
    ``gae.apply_calls`` and ``gae.apply_blas_threads`` (the thread count in
    force, summed) are equal while that holds."""
    from repro.core import exec as exec_mod

    exec_mod.single_blas_thread()
    exec_mod.counter_add("gae.apply_calls")
    exec_mod.counter_add("gae.apply_blas_threads", exec_mod.blas_threads())
    out = np.asarray(x_r, np.float32).copy()
    if not ms.sum():
        return out
    rows = np.repeat(np.arange(len(ms)), ms)
    b_vals = (bin_size / np.exp2(bin_exps.astype(np.float64)))[rows]
    coeffs = np.zeros(out.shape, np.float32)
    coeffs[rows, np.asarray(cols, np.int64)] = \
        np.asarray(qs).astype(np.float32) * b_vals.astype(np.float32)
    out += coeffs @ u.T
    return out


def gae_decode_blocks(x_r: np.ndarray, basis: np.ndarray, codes: list[GAEBlockCode],
                      bin_size: float) -> np.ndarray:
    """Inverse of gae_encode_blocks given the AE reconstruction x^R.

    Vectorized: all blocks' dequantized coefficients scatter into one dense
    (N, D) matrix (index sets are unique per block, so plain fancy-index
    assignment is exact) and the correction is a single ``@ basis.T`` matmul
    instead of a per-block Python loop.
    """
    u = np.asarray(basis, np.float32)
    if not codes:
        return np.asarray(x_r, np.float32).copy()
    ms = np.fromiter((c.m for c in codes), np.int64, len(codes))
    binexps = np.fromiter((c.bin_exp for c in codes), np.int64, len(codes))
    cols = np.concatenate([c.indices for c in codes])
    qs = np.concatenate([c.qcoeffs for c in codes])
    return _apply_codes(x_r, u, ms, cols, qs, binexps, bin_size)
