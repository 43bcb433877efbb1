"""Retrace-budget sweep: trace count must equal distinct-shape count.

The ROADMAP's "retrace budget in CI" item: sweep the compress path over
(n_hyperblocks, n_bae_stages) combinations and assert that the persistent jit
cache (``core/exec.py``) traces each fused program EXACTLY once per distinct
(bae-stage structure, stripe shape) key — no retraces for repeated shapes, no
hidden fresh-wrapper call sites.

Both the batch and streaming compress paths run per-stripe programs on the
same ``stripe_spans`` tiling, so the expected trace count is computable in
closed form: for each of ``encode_frontend`` / ``decode_backend``, the number
of distinct ``(n_bae_stages, stripe_width)`` pairs the sweep produces.  The
sweep runs batch AND streaming compress on every combination — streaming must
add ZERO traces on top of batch (it reuses the same cached programs; that is
what makes its chunks byte-identical).

    PYTHONPATH=src python benchmarks/bench_retrace_sweep.py
    PYTHONPATH=src python benchmarks/bench_retrace_sweep.py --out BENCH_retrace.json
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import numpy as np

from repro.core import bae as bae_mod
from repro.core import exec as exec_mod
from repro.core import hbae as hbae_mod
from repro.core.options import CompressOptions
from repro.core.pipeline import CompressorConfig, HierarchicalCompressor
from repro.stream import stream_compress


def make_compressor(n_stages: int, seed: int) -> HierarchicalCompressor:
    """Random-init compressor (no training — the sweep measures tracing, not
    reconstruction quality)."""
    cfg = CompressorConfig(block_elems=40, k=2, emb=16, hidden=32, hb_latent=8,
                           bae_hidden=32, bae_latent=4, use_bae=n_stages > 0,
                           n_bae_stages=max(n_stages, 1), hb_bin=0.01,
                           bae_bin=0.01)
    comp = HierarchicalCompressor(cfg)
    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + max(n_stages, 1))
    comp.hbae_params = hbae_mod.hbae_init(
        keys[0], in_dim=cfg.block_elems, k=cfg.k, emb=cfg.emb,
        hidden=cfg.hidden, latent=cfg.hb_latent, heads=cfg.heads)
    comp.bae_params = [
        bae_mod.bae_init(keys[1 + s], in_dim=cfg.block_elems,
                         hidden=cfg.bae_hidden, latent=cfg.bae_latent)
        for s in range(n_stages)]
    return comp


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--hyperblocks", type=int, nargs="+", default=[12, 24])
    ap.add_argument("--bae-stages", type=int, nargs="+", default=[0, 1, 2])
    ap.add_argument("--chunk-hyperblocks", type=int, default=7)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    rng = np.random.default_rng(args.seed)
    combos = [(n_hb, stages) for stages in args.bae_stages
              for n_hb in args.hyperblocks]

    # closed-form expectation: one trace per distinct (structure, shape) key
    distinct: set[tuple[int, int]] = set()
    per_combo_spans = {}
    for n_hb, stages in combos:
        comp = make_compressor(stages, args.seed)
        spans = comp.stripe_spans(n_hb, args.chunk_hyperblocks, with_gae=False)
        per_combo_spans[(n_hb, stages)] = spans
        for _, width in spans:
            distinct.add((stages, width))
    expected = 2 * len(distinct)        # encode_frontend + decode_backend

    opts = CompressOptions(chunk_hyperblocks=args.chunk_hyperblocks)
    base = exec_mod.total_retraces()
    for n_hb, stages in combos:
        comp = make_compressor(stages, rng.integers(1 << 30))
        x = rng.normal(size=(n_hb, 2, 40)).astype(np.float32)
        comp.compress(x, options=opts)
        batch_traces = exec_mod.total_retraces()
        stream_compress(comp, x, options=opts)
        stream_delta = exec_mod.total_retraces() - batch_traces
        if stream_delta:
            print(f"FAIL: streaming compress added {stream_delta} traces on "
                  f"(n_hb={n_hb}, stages={stages}) — it must hit the batch "
                  f"path's cache", file=sys.stderr)
            return 1
    got = exec_mod.total_retraces() - base

    report = {
        "combos": [{"n_hyperblocks": n, "bae_stages": s,
                    "stripe_widths": sorted({w for _, w in
                                             per_combo_spans[(n, s)]})}
                   for n, s in combos],
        "distinct_shape_keys": sorted(distinct),
        "expected_traces": expected,
        "observed_traces": got,
        "retrace_counts": exec_mod.retrace_counts(),
    }
    print(f"distinct (bae_stages, stripe_width) keys: {len(distinct)} -> "
          f"expected {expected} traces (encode+decode), observed {got}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
            f.write("\n")
        print(f"written: {args.out}")
    if got != expected:
        print(f"FAIL: trace count {got} != distinct-shape count {expected}: "
              f"{exec_mod.retrace_counts()}", file=sys.stderr)
        return 1
    print("OK: trace count equals distinct-shape count")
    return 0


if __name__ == "__main__":
    sys.exit(main())
