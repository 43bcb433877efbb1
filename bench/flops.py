"""Model FLOPs per value of the compressor, from the configuration's shapes.

A MAC is two FLOPs.  Per hyper-block of k blocks of D values (HBAE, paper
Sec. II-B) and per block (BAE, Sec. II-C):

* HBAE encode: block MLP k(D*hidden + hidden*emb), attention over the k
  embeddings 4k*emb^2 + 2k^2*emb, latent projection k*emb*latent;
* HBAE decode: the mirror image, the same count;
* BAE encode and decode: D*bae_hidden + bae_hidden*bae_latent each;
* GAE: the projection c = r U onto the D_gae x D_gae basis is D_gae MACs
  per value, and so is the reconstruction U c of a block that keeps
  coefficients.

Recomputation is not counted: compress runs the AE decode twice (in the
front end, for the BAE residual, and again for the GAE input), and fit's
backward pass is counted as twice the forward.
"""
from __future__ import annotations


def hbae_half_macs_per_block(c: dict) -> float:
    """MACs of the HBAE encoder (or, equally, the decoder) per block."""
    k, d, hid, emb, lat = c["k"], c["block_elems"], c["hidden"], c["emb"], \
        c["hb_latent"]
    mlp = k * (d * hid + hid * emb)
    attn = (4 * k * emb * emb + 2 * k * k * emb) if c["use_attention"] else 0
    latent = k * emb * lat
    return (mlp + attn + latent) / k


def bae_half_macs_per_block(c: dict) -> float:
    """MACs of one BAE stage's encoder (or decoder) per block."""
    if not c["use_bae"]:
        return 0.0
    return c["n_bae_stages"] * (c["block_elems"] * c["bae_hidden"]
                                + c["bae_hidden"] * c["bae_latent"])


def d_gae(c: dict) -> int:
    return c.get("gae_block_elems") or c["block_elems"]


def compress_per_value(c: dict) -> float:
    """HBAE and BAE forward once, plus the GAE projection."""
    ae = 2 * (hbae_half_macs_per_block(c) + bae_half_macs_per_block(c))
    return 2 * ae / c["block_elems"] + 2 * d_gae(c)


def decompress_per_value(c: dict, coded_share: float) -> float:
    """HBAE and BAE decode, plus the GAE reconstruction of the blocks that
    keep coefficients (``coded_share`` of them)."""
    ae = hbae_half_macs_per_block(c) + bae_half_macs_per_block(c)
    return 2 * ae / c["block_elems"] + 2 * d_gae(c) * coded_share


def fit_per_value(c: dict, n_hyperblocks: int, epochs: int) -> float:
    """One ``fit``: HBAE steps of ``batch`` hyper-blocks, the whole-field
    HBAE forward, BAE steps of ``max(4 batch, 256)`` blocks and the
    whole-field BAE forward.  A training step counts three forwards (the
    backward pass as two)."""
    k, d = c["k"], c["block_elems"]
    blocks = n_hyperblocks * k
    hb_batch = min(c["batch"], n_hyperblocks)
    bae_batch = min(max(4 * c["batch"], 256), blocks)
    hbae = 2 * hbae_half_macs_per_block(c)
    bae = 2 * bae_half_macs_per_block(c)
    steps_hb = epochs * (n_hyperblocks // hb_batch)
    steps_bae = epochs * (blocks // bae_batch)
    macs = (3 * steps_hb * hb_batch * k * hbae + blocks * hbae
            + 3 * steps_bae * bae_batch * bae + blocks * bae)
    return 2 * macs / (blocks * d)
