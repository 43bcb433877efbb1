"""Before/after throughput benchmark for the compression hot path.

Measures the LEGACY hot path — a faithful re-implementation of the
pre-exec-layer pipeline: fresh inline ``jax.jit(fn)(...)`` wrappers per call
(so every call retraces + recompiles), per-stage host<->device ``np.asarray``
bounces, serial chunk entropy loops, and the per-symbol scalar Huffman decode
— against the current pipeline (persistent jit cache, fused device-resident
stage programs, chunk-parallel vectorized entropy coding), on a synthetic
S3D-shaped workload.  Results (values/s per phase, speedup, retrace counts)
are written to ``BENCH_pipeline.json``.

    PYTHONPATH=src python benchmarks/bench_pipeline_throughput.py            # full
    PYTHONPATH=src python benchmarks/bench_pipeline_throughput.py --smoke    # CI gate

``--smoke`` runs a small workload and FAILS (exit 1) if a repeated
``compress``/``decompress`` call retraces after warmup — the regression gate
wired into ``scripts/smoke.sh``.  See docs/PERF.md for how to read the output.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bae as bae_mod
from repro.core import entropy, gae
from repro.core import exec as exec_mod
from repro.core import hbae as hbae_mod
from repro.core.options import CompressOptions
from repro.core.pipeline import Archive, ArchiveChunk, HierarchicalCompressor
from repro.core.quantization import dequantize, quantize
from repro.data import blocks as blocks_mod
from repro.data import synthetic


# ---------------------------------------------------------------------------
# legacy (pre-PR) hot path, kept verbatim as the measured baseline
# ---------------------------------------------------------------------------

def legacy_gae_encode_blocks(x, x_r, basis, tau, bin_size, max_refine=20):
    """Pre-PR encoder: unjitted selection + one Python iteration per block."""
    x = np.asarray(x, np.float32)
    x_r = np.asarray(x_r, np.float32)
    u = np.asarray(basis, np.float32)
    n, d = x.shape
    r = jnp.asarray(x - x_r)
    sel = gae.gae_select(r, jnp.asarray(u), tau, bin_size)
    corrected, _ = gae.gae_correct(r, jnp.asarray(u), sel, bin_size)
    sel = jax.device_get(sel)
    out = x_r + np.asarray(corrected)
    codes = []
    for i in range(n):
        m = int(sel.m[i])
        bin_exp = 0
        b = bin_size
        idx = np.asarray(sel.order[i][:m], np.int32)
        q = np.asarray(sel.q_sorted[i][:m], np.int64)
        err = float(np.linalg.norm(x[i] - out[i]))
        while err > tau and bin_exp < max_refine:
            if m < d:
                m = min(d, m + max(1, d // 32))
            else:
                bin_exp += 1
                b = bin_size / (2 ** bin_exp)
            c = u.T @ (x[i] - x_r[i])
            order = np.argsort(-np.square(c))
            idx = order[:m].astype(np.int32)
            q = np.round(c[idx] / b).astype(np.int64)
            rec = x_r[i] + u[:, idx] @ (q.astype(np.float32) * b)
            err = float(np.linalg.norm(x[i] - rec))
            out[i] = rec
        codes.append(gae.GAEBlockCode(m=m, indices=idx, qcoeffs=q,
                                      bin_exp=bin_exp))
    return out, codes


def legacy_gae_decode_blocks(x_r, basis, codes, bin_size):
    """Pre-PR decoder: one gather-matvec per block."""
    u = np.asarray(basis, np.float32)
    out = np.asarray(x_r, np.float32).copy()
    for i, code in enumerate(codes):
        if code.m == 0:
            continue
        b = bin_size / (2 ** code.bin_exp)
        out[i] = out[i] + u[:, code.indices] @ (code.qcoeffs.astype(np.float32)
                                                * b)
    return out


def legacy_encode_index_sets(index_sets, dim):
    """Pre-PR bitmask encoder: one mask allocation per index set."""
    import struct
    import zlib
    lengths = []
    all_bits = []
    for idx in index_sets:
        mask = np.zeros(dim, np.uint8)
        if idx.size:
            mask[idx] = 1
            plen = int(idx.max()) + 1
        else:
            plen = 0
        lengths.append(plen)
        all_bits.append(mask[:plen])
    bits = np.concatenate(all_bits) if all_bits else np.zeros(0, np.uint8)
    header = struct.pack("<II", len(index_sets), dim)
    lens_b = np.asarray(lengths, np.uint32).tobytes()
    payload = np.packbits(bits).tobytes() if bits.size else b""
    return zlib.compress(header + lens_b + payload, level=9)


def legacy_decode_index_sets(blob, expect_dim=None, expect_sets=None):
    """Pre-PR bitmask decoder: per-set slice + nonzero loop (validation
    identical to the current implementation)."""
    import struct
    import zlib
    from repro.core.errors import MalformedStream, TruncatedArchive
    try:
        raw = zlib.decompress(blob)
    except zlib.error as e:
        raise MalformedStream(f"index blob DEFLATE error: {e}") from e
    if len(raw) < 8:
        raise TruncatedArchive("index blob shorter than its header")
    n, dim = struct.unpack("<II", raw[:8])
    if expect_dim is not None and dim != expect_dim:
        raise MalformedStream(
            f"index blob dimension {dim} != basis dimension {expect_dim}")
    if expect_sets is not None and n != expect_sets:
        raise MalformedStream(f"index blob has {n} sets, expected {expect_sets}")
    if len(raw) < 8 + 4 * n:
        raise TruncatedArchive("index blob length table truncated")
    lens = np.frombuffer(raw[8:8 + 4 * n], np.uint32).astype(np.int64)
    if lens.size and lens.max() > dim:
        raise MalformedStream(
            f"index prefix length {int(lens.max())} exceeds dimension {dim}")
    bits = np.unpackbits(np.frombuffer(raw[8 + 4 * n:], np.uint8))
    if int(lens.sum()) > bits.size:
        raise TruncatedArchive("index bitmask payload truncated")
    out = []
    pos = 0
    for plen in lens:
        mask = bits[pos:pos + plen]
        out.append(np.nonzero(mask)[0].astype(np.int32))
        pos += int(plen)
    return out


def legacy_compress(comp: HierarchicalCompressor, hyperblocks: np.ndarray,
                    tau: float, chunk_hyperblocks: int = 64) -> Archive:
    """The old ``compress``: inline jit per call site, one host<->device
    round-trip per stage, serial chunk striping."""
    cfg = comp.cfg
    n, k, d = hyperblocks.shape
    latent = np.asarray(jax.jit(hbae_mod.hbae_encode)(comp.hbae_params,
                                                      jnp.asarray(hyperblocks)))
    q_lh = np.asarray(quantize(jnp.asarray(latent), cfg.hb_bin))
    lat_deq = np.asarray(dequantize(jnp.asarray(q_lh), cfg.hb_bin))
    y = np.asarray(jax.jit(hbae_mod.hbae_decode)(comp.hbae_params,
                                                 jnp.asarray(lat_deq)))
    recon = y
    q_lbs: list[np.ndarray] = []
    if cfg.use_bae:
        resid = (hyperblocks - recon).reshape(n * k, d)
        for p in comp.bae_params:
            lb = np.asarray(jax.jit(bae_mod.bae_encode)(p, jnp.asarray(resid)))
            q_lb = np.asarray(quantize(jnp.asarray(lb), cfg.bae_bin))
            q_lbs.append(q_lb)
            lb_deq = np.asarray(dequantize(jnp.asarray(q_lb), cfg.bae_bin))
            r_hat = np.asarray(jax.jit(bae_mod.bae_decode)(p, jnp.asarray(lb_deq)))
            recon = recon + r_hat.reshape(n, k, d)
            resid = resid - r_hat

    codes: list[gae.GAEBlockCode] = []
    gae_dim = 0
    if tau is not None:
        x_gae = comp._gae_view(hyperblocks)
        r_gae = comp._gae_view(recon)
        _, codes = legacy_gae_encode_blocks(x_gae, r_gae, comp.basis, tau,
                                            cfg.gae_bin)
        gae_dim = int(comp.basis.shape[0])

    width = comp._chunk_width(chunk_hyperblocks, with_gae=tau is not None)
    d_gae = cfg.gae_block_elems or cfg.block_elems
    gae_per_hb = (k * d) // d_gae if tau is not None else 0
    chunks = []
    for start in range(0, n, width):
        n_hb = min(width, n - start)
        hb_stream = entropy.huffman_compress(q_lh[start:start + n_hb])
        bae_streams = [entropy.huffman_compress(
            q_lb[start * k:(start + n_hb) * k]) for q_lb in q_lbs]
        coeff_stream = None
        index_blob = binexp_blob = b""
        if tau is not None:
            cchunk = codes[start * gae_per_hb:(start + n_hb) * gae_per_hb]
            all_coeffs, index_sets, binexps = [], [], []
            for c in cchunk:
                asc = np.argsort(c.indices)
                index_sets.append(np.sort(c.indices))
                all_coeffs.append(c.qcoeffs[asc])
                binexps.append(c.bin_exp)
            coeffs = (np.concatenate(all_coeffs) if all_coeffs else
                      np.zeros(0, np.int64))
            if coeffs.size:
                coeff_stream = entropy.huffman_compress(coeffs)
            index_blob = legacy_encode_index_sets(index_sets, gae_dim)
            binexp_blob = entropy.zlib_pack(
                np.asarray(binexps, np.uint8).tobytes())
        chunks.append(ArchiveChunk(
            hb_start=start, n_hyperblocks=n_hb, hb_stream=hb_stream,
            bae_streams=bae_streams, gae_coeff_stream=coeff_stream,
            gae_index_blob=index_blob, gae_binexp_blob=binexp_blob))
    return Archive(n_hyperblocks=n, n_values=hyperblocks.size,
                   chunk_hyperblocks=width, gae_dim=gae_dim, chunks=chunks)


def legacy_decompress(comp: HierarchicalCompressor, archive: Archive
                      ) -> np.ndarray:
    """The old strict ``decompress``: serial chunk loop, inline jit decode."""
    cfg = comp.cfg
    n, k, d = archive.n_hyperblocks, cfg.k, cfg.block_elems
    q_lh = np.zeros((n, cfg.hb_latent), np.int64)
    q_lbs = [np.zeros((n * k, cfg.bae_latent), np.int64)
             for _ in comp.bae_params]
    gae_codes: dict[int, gae.GAEBlockCode] = {}
    d_gae = cfg.gae_block_elems or d
    gae_per_hb = (k * d) // d_gae if archive.gae_dim else 0
    for chunk in archive.chunks:
        c_lh, c_lbs, c_codes = comp._decode_chunk(chunk, archive)
        s, e = chunk.hb_start, chunk.hb_start + chunk.n_hyperblocks
        q_lh[s:e] = c_lh
        for stage, c_lb in enumerate(c_lbs):
            q_lbs[stage][s * k:e * k] = c_lb
        for j, code in enumerate(c_codes):
            gae_codes[s * gae_per_hb + j] = code
    lat = np.asarray(dequantize(jnp.asarray(q_lh), cfg.hb_bin))
    recon = np.asarray(jax.jit(hbae_mod.hbae_decode)(comp.hbae_params,
                                                     jnp.asarray(lat)))
    for p, q_lb in zip(comp.bae_params, q_lbs):
        lb = np.asarray(dequantize(jnp.asarray(q_lb), cfg.bae_bin))
        r_hat = np.asarray(jax.jit(bae_mod.bae_decode)(p, jnp.asarray(lb)))
        recon = recon + r_hat.reshape(n, k, d)
    if archive.gae_dim and gae_codes:
        r_gae = comp._gae_view(recon)
        idxs = sorted(gae_codes)
        sub = legacy_gae_decode_blocks(r_gae[idxs], comp.basis,
                                       [gae_codes[i] for i in idxs],
                                       cfg.gae_bin)
        r_gae[idxs] = sub
        recon = comp._gae_unview(r_gae, recon.shape)
    return recon


@contextlib.contextmanager
def legacy_entropy():
    """Route the entropy codecs through their pre-PR implementations (scalar
    per-symbol Huffman decode, per-set index bitmask loops) for the duration
    of the legacy measurement — including inside ``comp._decode_chunk``."""
    saved = (entropy.huffman_decode, entropy.decode_index_sets)
    entropy.huffman_decode = entropy.huffman_decode_scalar
    entropy.decode_index_sets = legacy_decode_index_sets
    try:
        yield
    finally:
        entropy.huffman_decode, entropy.decode_index_sets = saved


# ---------------------------------------------------------------------------
# workloads
# ---------------------------------------------------------------------------

def s3d_workload(smoke: bool, seed: int, epochs_scale: float):
    """S3D-shaped hyper-blocks: paper block geometry (58,5,4,4), k=10."""
    if not smoke:
        cfg, hb = synthetic.make_dataset("s3d", quick=True, seed=seed,
                                         epochs_scale=epochs_scale)
        return cfg, hb
    # smoke: same geometry, smaller spatial grid (t stays 50 so t_grid >= k)
    import dataclasses
    from repro.configs import get_compressor_config
    data = synthetic.s3d_like(n_species=58, t=50, h=16, w=16, seed=seed)
    norm = blocks_mod.Normalizer.fit(data, mode="range", axis=0)
    blocks, meta = blocks_mod.block_nd(norm.forward(data),
                                       (data.shape[0], 5, 4, 4))
    blocks = synthetic._temporal_major(blocks, meta.grid_shape, t_axis=1)
    hb = blocks_mod.group_hyperblocks(blocks, 10)
    cfg = dataclasses.replace(get_compressor_config("s3d"), hidden=128,
                              bae_hidden=128, epochs_hbae=2, epochs_bae=2)
    return cfg, hb.astype(np.float32)


def timed(fn, repeats: int) -> float:
    t0 = time.perf_counter()
    for _ in range(repeats):
        fn()
    return (time.perf_counter() - t0) / repeats


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload + retrace-regression gate (exit 1)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--legacy-repeats", type=int, default=2)
    ap.add_argument("--tau", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--epochs-scale", type=float, default=0.1)
    ap.add_argument("--out", default="BENCH_pipeline.json")
    args = ap.parse_args(argv)
    if args.smoke:
        args.repeats = min(args.repeats, 2)
        args.legacy_repeats = 1

    cfg, hb = s3d_workload(args.smoke, args.seed, args.epochs_scale)
    print(f"workload: {hb.shape[0]} hyper-blocks of (k={hb.shape[1]}, "
          f"D={hb.shape[2]}) = {hb.size:,} values", file=sys.stderr)
    t0 = time.perf_counter()
    comp = HierarchicalCompressor(cfg).fit(hb, seed=args.seed)
    comp.fit_basis(hb)
    print(f"fit in {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    # -- current path: warmup, then assert zero retraces across repeats -----
    exec_mod.reset_stage_stats()
    opts = CompressOptions(tau=args.tau)
    archive = comp.compress(hb, options=opts)
    recon = comp.decompress(archive)
    traces_warm = exec_mod.total_retraces()
    cur_comp = timed(lambda: comp.compress(hb, options=opts), args.repeats)
    cur_dec = timed(lambda: comp.decompress(archive), args.repeats)
    retrace_delta = exec_mod.total_retraces() - traces_warm

    # -- legacy path --------------------------------------------------------
    with legacy_entropy():
        leg_arch = legacy_compress(comp, hb, args.tau)
        leg_recon = legacy_decompress(comp, leg_arch)
        leg_comp = timed(lambda: legacy_compress(comp, hb, args.tau),
                         args.legacy_repeats)
        leg_dec = timed(lambda: legacy_decompress(comp, leg_arch),
                        args.legacy_repeats)
    # Selection ties may resolve differently between the two implementations,
    # so compare them on the contract: every block meets the l2 bound.
    for label, r in (("legacy", leg_recon), ("current", recon)):
        gview = (hb - r).reshape(-1, cfg.gae_block_elems or cfg.block_elems)
        worst = float(np.linalg.norm(gview, axis=1).max())
        if worst > args.tau * (1 + 1e-5):
            print(f"ERROR: {label} reconstruction violates tau: "
                  f"{worst} > {args.tau}", file=sys.stderr)
            return 1

    speedup = (leg_comp + leg_dec) / (cur_comp + cur_dec)
    result = {
        "workload": {"dataset": "s3d", "smoke": args.smoke,
                     "hyperblocks": int(hb.shape[0]), "k": int(hb.shape[1]),
                     "block_elems": int(hb.shape[2]),
                     "n_values": int(hb.size), "tau": args.tau,
                     "repeats": args.repeats,
                     "legacy_repeats": args.legacy_repeats},
        "baseline": {
            "compress_s": leg_comp, "decompress_s": leg_dec,
            "compress_values_per_s": hb.size / leg_comp,
            "decompress_values_per_s": hb.size / leg_dec,
        },
        "current": {
            "compress_s": cur_comp, "decompress_s": cur_dec,
            "compress_values_per_s": hb.size / cur_comp,
            "decompress_values_per_s": hb.size / cur_dec,
            "stage_stats": {
                name: {"calls": st.calls, "seconds": round(st.seconds, 4),
                       "values_per_s": round(st.values_per_s(), 1)}
                for name, st in sorted(exec_mod.stage_stats().items())},
            "retraces": exec_mod.retrace_counts(),
        },
        "speedup_compress_plus_decompress": round(speedup, 2),
        "retraces_after_warmup": int(retrace_delta),
    }
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"legacy:  compress {leg_comp:.3f}s  decompress {leg_dec:.3f}s")
    print(f"current: compress {cur_comp:.3f}s  decompress {cur_dec:.3f}s")
    print(f"speedup (compress+decompress): {speedup:.2f}x")
    print(f"retraces after warmup: {retrace_delta}")
    print(f"written: {args.out}")

    if args.smoke and retrace_delta != 0:
        print(f"FAIL: {retrace_delta} retraces across repeated "
              f"compress/decompress calls after warmup (expected 0) — a hot-"
              f"path call site is creating fresh jit wrappers", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
