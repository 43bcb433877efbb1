"""Plain reference of the compressor's decoder-side arithmetic.

Everything here is plain numpy or jax.numpy, and imports nothing of the
program.  It reads what the program produced: the
archive's chunk fields (Huffman payloads and code lengths, index bitmasks,
bin exponents) and the decoder-side model (autoencoder weights and PCA
basis), which the paper counts as part of the compressed representation.

The autoencoders take numpy arrays (float64, the reference of the decoder
checks) or JAX arrays (float32 under ``jax.default_matmul_precision
("highest")``, which the training reference differentiates); they compute
in the dtype they are given.  ``precision`` selects the operands of every
matrix product:

* ``"ref"``: as given;
* ``"bf16"`` / ``"fp8"``: rounded to bfloat16 / float8 e4m3 first (fp8
  with one scale per operand, its largest magnitude at e4m3's largest
  finite value, as fp8 paths scale), the controls that a lower-precision
  path would give.
"""
from __future__ import annotations

import struct
import zlib

import ml_dtypes
import numpy as np

_LOW = {"bf16": ml_dtypes.bfloat16, "fp8": ml_dtypes.float8_e4m3fn}


def _xp(a):
    """numpy for numpy arrays, jax.numpy for JAX arrays and tracers."""
    if isinstance(a, np.ndarray):
        return np
    import jax.numpy as jnp
    return jnp


_FP8_MAX = float(ml_dtypes.finfo(ml_dtypes.float8_e4m3fn).max)


def _round(a, precision: str):
    if precision == "ref":
        return a
    xp = _xp(a)
    scale = 1.0
    if precision == "fp8":
        peak = xp.max(xp.abs(a))
        scale = xp.where(peak > 0, peak / _FP8_MAX, 1.0)
    low = (a / scale).astype(np.float32).astype(_LOW[precision])
    return low.astype(a.dtype) * scale


def matmul(x, w, precision: str = "ref"):
    return _round(x, precision) @ _round(w, precision)


# ---------------------------------------------------------------------------
# entropy layer
# ---------------------------------------------------------------------------

def huffman_decode(payload: bytes, symbols: np.ndarray, lengths: np.ndarray,
                   count: int) -> np.ndarray:
    """Canonical Huffman decode: codes are assigned in the book's
    (length, symbol) order, each one the previous plus one, shifted left
    when the length grows."""
    if count == 0:
        return np.zeros(0, np.int64)
    lengths = [int(v) for v in lengths]
    width = max(lengths)
    # every width-bit window that starts with a code maps to that code
    table_sym = np.zeros(1 << width, np.int64)
    table_len = np.zeros(1 << width, np.int64)
    code, prev = 0, lengths[0]
    for sym, n in zip(symbols.tolist(), lengths):
        code <<= n - prev
        lo, hi = code << (width - n), (code + 1) << (width - n)
        table_sym[lo:hi] = sym
        table_len[lo:hi] = n
        prev = n
        code += 1
    bits = np.unpackbits(np.frombuffer(payload, np.uint8)).astype(np.int64)
    bits = np.concatenate([bits, np.zeros(width, np.int64)])
    windows = np.zeros(bits.size - width, np.int64)
    for j in range(width):
        windows = (windows << 1) | bits[j:j + windows.size]
    window_list, len_list = windows.tolist(), table_len.tolist()
    sym_list = table_sym.tolist()
    out = np.empty(count, np.int64)
    pos = 0
    for i in range(count):
        w = window_list[pos]
        n = len_list[w]
        if n == 0:
            raise ValueError("no Huffman code matches the payload")
        out[i] = sym_list[w]
        pos += n
    return out


def index_sets(blob: bytes) -> tuple[int, list[np.ndarray]]:
    """Prefix bitmask per block: header (n, dim), n prefix lengths, then the
    concatenated prefixes; a set bit marks a kept basis vector."""
    raw = zlib.decompress(blob)
    n, dim = struct.unpack("<II", raw[:8])
    lens = np.frombuffer(raw[8:8 + 4 * n], np.uint32)
    bits = np.unpackbits(np.frombuffer(raw[8 + 4 * n:], np.uint8))
    sets, pos = [], 0
    for length in lens.tolist():
        sets.append(np.flatnonzero(bits[pos:pos + length]))
        pos += length
    return dim, sets


# ---------------------------------------------------------------------------
# autoencoders (paper Sec. II-B, II-C)
# ---------------------------------------------------------------------------

def _linear(p: dict, x, precision: str):
    y = matmul(x, p["w"], precision)
    return y + p["b"] if "b" in p else y


def _mlp2(p: dict, x, precision: str):
    return _linear(p["fc2"], _xp(x).maximum(_linear(p["fc1"], x, precision),
                                            0.0), precision)


def _layernorm(p: dict, x, eps: float = 1e-5):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / _xp(x).sqrt(var + eps) * p["scale"] + p["bias"]


def _attention_block(p: dict, e, heads: int, precision: str):
    """e + Atten(LayerNorm(e)) over the k blocks of each hyper-block."""
    x = _layernorm(p["ln"], e)
    a = p["attn"]
    q = _linear(a["wq"], x, precision)
    k = _linear(a["wk"], x, precision)
    v = _linear(a["wv"], x, precision)
    b, n, d = q.shape
    dh = d // heads
    q = q.reshape(b, n, heads, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, n, heads, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, n, heads, dh).transpose(0, 2, 1, 3)
    s = matmul(q, k.transpose(0, 1, 3, 2), precision) / np.sqrt(dh)
    s = _xp(s).exp(s - s.max(axis=-1, keepdims=True))
    w = s / s.sum(axis=-1, keepdims=True)
    ctx = matmul(w, v, precision).transpose(0, 2, 1, 3).reshape(b, n, d)
    return _linear(a["wo"], ctx, precision) + e


def hbae_encode(p: dict, x, heads: int, precision: str = "ref"):
    """(B, k, D) -> (B, latent)."""
    e = _mlp2(p["enc"], x, precision)
    if "enc_attn" in p:
        e = _attention_block(p["enc_attn"], e, heads, precision)
    return _linear(p["to_latent"], e.reshape(e.shape[0], -1), precision)


def hbae_decode(p: dict, latent, k: int, heads: int, precision: str = "ref"):
    """(B, latent) -> (B, k, D)."""
    e = _linear(p["from_latent"], latent, precision)
    e = e.reshape(e.shape[0], k, -1)
    if "dec_attn" in p:
        e = _attention_block(p["dec_attn"], e, heads, precision)
    return _mlp2(p["dec"], e, precision)


def bae_encode(p: dict, resid, precision: str = "ref"):
    return _mlp2(p["enc"], _layernorm(p["ln"], resid), precision)


def bae_decode(p: dict, latent, precision: str = "ref"):
    return _mlp2(p["dec"], latent, precision)


# ---------------------------------------------------------------------------
# one archive chunk, decoded
# ---------------------------------------------------------------------------

def decode_codes(chunk, cfg: dict) -> dict:
    """The chunk's quantized latents and GAE codes, decoded from its bytes."""
    n_hb, k = chunk.n_hyperblocks, cfg["k"]

    def huff(stream):
        return huffman_decode(stream.payload, stream.book.symbols,
                              stream.book.lengths, stream.count)

    out = {"q_hb": huff(chunk.hb_stream).reshape(n_hb, cfg["hb_latent"]),
           "q_bae": [huff(s).reshape(n_hb * k, cfg["bae_latent"])
                     for s in chunk.bae_streams]}
    dim, sets = index_sets(chunk.gae_index_blob)
    coeffs = (huff(chunk.gae_coeff_stream) if chunk.gae_coeff_stream is not None
              else np.zeros(0, np.int64))
    out["gae_dim"] = dim
    out["index_sets"] = sets
    out["coeffs"] = coeffs
    out["bin_exps"] = np.frombuffer(zlib.decompress(chunk.gae_binexp_blob),
                                    np.uint8).astype(np.int64)
    return out


def ae_decode(codes: dict, hbae: dict, baes: list, cfg: dict,
              precision: str = "ref") -> np.ndarray:
    """Dequantize the latents and decode: HBAE, plus each BAE stage's
    residual."""
    k = cfg["k"]
    recon = hbae_decode(hbae, codes["q_hb"] * cfg["hb_bin"], k, cfg["heads"],
                        precision)
    for p, q in zip(baes, codes["q_bae"]):
        recon = recon + bae_decode(p, q * cfg["bae_bin"],
                                   precision).reshape(recon.shape)
    return recon


def gae_correct(x_r: np.ndarray, codes: dict, basis: np.ndarray,
                gae_bin: float, precision: str = "ref") -> np.ndarray:
    """x^G = x^R + U_s c for every GAE block (paper Eq. 10): the kept
    coefficients of block i are the next |S_i| values, in ascending index
    order, each at the block's bin gae_bin / 2**bin_exp."""
    d = basis.shape[0]
    flat = np.asarray(x_r, np.float64).reshape(-1, d)
    coeff = np.zeros_like(flat)
    pos = 0
    for i, idx in enumerate(codes["index_sets"]):
        m = idx.size
        if m:
            step = gae_bin / 2.0 ** int(codes["bin_exps"][i])
            coeff[i, idx] = codes["coeffs"][pos:pos + m] * step
            pos += m
    if pos != codes["coeffs"].size:
        raise ValueError(f"{codes['coeffs'].size} coefficients for {pos} "
                         f"kept basis vectors")
    return (flat + matmul(coeff, np.asarray(basis, np.float64).T, precision)
            ).reshape(np.shape(x_r))


def decode_chunk(chunk, hbae: dict, baes: list, basis: np.ndarray, cfg: dict,
                 precision: str = "ref", gae_precision: str = "ref"
                 ) -> tuple[np.ndarray, dict]:
    """Reconstruct one chunk's hyper-blocks from its bytes and the model."""
    codes = decode_codes(chunk, cfg)
    x_r = ae_decode(codes, hbae, baes, cfg, precision)
    return gae_correct(x_r, codes, basis, cfg["gae_bin"], gae_precision), codes


def latent_gap(x: np.ndarray, codes: dict, hbae: dict, baes: list, cfg: dict,
               precision: str = "ref") -> float:
    """Widest gap, in bins, between the archive's quantized latents and the
    encoder's latents computed here from the original hyper-blocks.  Each
    BAE stage is encoded from the residual of the archive's own earlier
    stages, as the program chains them."""
    k = cfg["k"]
    x = np.asarray(x, np.float64)
    z = hbae_encode(hbae, x, cfg["heads"], precision)
    gap = float(np.max(np.abs(codes["q_hb"] - z / cfg["hb_bin"])))
    recon = hbae_decode(hbae, codes["q_hb"] * cfg["hb_bin"], k, cfg["heads"],
                        precision)
    resid = (x - recon).reshape(-1, x.shape[-1])
    for p, q in zip(baes, codes["q_bae"]):
        zb = bae_encode(p, resid, precision)
        gap = max(gap, float(np.max(np.abs(q - zb / cfg["bae_bin"]))))
        resid = resid - bae_decode(p, q * cfg["bae_bin"], precision)
    return gap


def control_codes(x: np.ndarray, hbae: dict, baes: list, cfg: dict,
                  precision: str) -> dict:
    """The latents a lower-precision encoder would have written: the
    reference encoder at ``precision``, quantized at the configured bins."""
    k = cfg["k"]
    x = np.asarray(x, np.float64)
    q_hb = np.round(hbae_encode(hbae, x, cfg["heads"], precision)
                    / cfg["hb_bin"])
    recon = hbae_decode(hbae, q_hb * cfg["hb_bin"], k, cfg["heads"], precision)
    resid = (x - recon).reshape(-1, x.shape[-1])
    q_bae = []
    for p in baes:
        q = np.round(bae_encode(p, resid, precision) / cfg["bae_bin"])
        q_bae.append(q)
        resid = resid - bae_decode(p, q * cfg["bae_bin"], precision)
    return {"q_hb": q_hb, "q_bae": q_bae}


# ---------------------------------------------------------------------------
# training (paper Sec. III-C: MSE loss, Adam at lr 1e-3)
# ---------------------------------------------------------------------------

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def hbae_loss(p: dict, x, k: int, heads: int, precision: str = "ref"):
    y = hbae_decode(p, hbae_encode(p, x, heads, precision), k, heads,
                    precision)
    return ((y - x) ** 2).mean()


def bae_loss(p: dict, r, precision: str = "ref"):
    return ((bae_decode(p, bae_encode(p, r, precision), precision) - r)
            ** 2).mean()


def adam_train(loss, params: dict, batches: list, lr: float):
    """Adam from zero moments (Kingma and Ba, Algorithm 1) over ``batches``
    with JAX's autodiff; returns the losses, the first gradient and the
    parameters after the last step."""
    import jax
    import jax.numpy as jnp

    step_grad = jax.jit(jax.value_and_grad(loss))
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first = [], None
    for t, x in enumerate(batches, start=1):
        value, g = step_grad(params, x)
        first = g if first is None else first
        m = jax.tree.map(lambda a, b: ADAM_B1 * a + (1 - ADAM_B1) * b, m, g)
        v = jax.tree.map(lambda a, b: ADAM_B2 * a + (1 - ADAM_B2) * b * b, v, g)
        c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
        params = jax.tree.map(
            lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + ADAM_EPS),
            params, m, v)
        losses.append(float(value))
    return losses, first, params
