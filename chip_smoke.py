#!/usr/bin/env python3
"""Run the paper's compressor end to end on a TPU and check what comes out.

    python chip_smoke.py            # one chip: E3SM at paper size, full width
    python chip_smoke.py --mesh 4   # four chips: sharded compress/decompress
                                    # against the single-device compress

The one-chip phase generates E3SM at its paper size (720x240x1440 values,
32,400 hyper-blocks of 5 x 1536), fits the full-width ``configs/e3sm.py``
model for a few epochs, and drives the public API the compression CLI
drives: ``compress``, ``stream_compress`` into an ``.rba``, ``decompress``,
``read_archive`` of that file and a second ``decompress``.  It fails unless
every GAE block is within tau on both decodes, the streamed container is
byte-identical to the batch archive, the disk round trip decodes bit-exactly,
and no stripe was quarantined, retried or failed over.

``--mesh 4`` fits the same model, compresses once on one device and once
with ``CompressOptions(mesh=4)``, and decodes each; it fails unless the two
archives are byte-identical, the sharded decode is bit-identical to the
single-device decode, and tau holds on every block.

Each phase ends with ``exec.stats_summary()``: the program's own stage
spans and counters, from a bring-up run, not speed measurements.  The last
line of stdout is ``{"ok": true, "device": {...}}`` and appears
only if every check passed; without a TPU the script exits nonzero before
doing any work.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DATASET = "e3sm"
#: below the CLI default of 0.5, which the full-width E3SM model meets with
#: the AE alone on all but a handful of blocks (max l2 0.484 after 3 epochs,
#: one TPU v5e): at 0.2 GAE codes a clear share of the blocks
TAU = 0.2
#: the one cut: 30 -> 3 epochs for both HBAE and BAE (widths unchanged)
EPOCHS_SCALE = 0.1
OUT_DIR = ROOT / ".chip_smoke"


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


def _say(msg: str) -> None:
    print(msg, flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    _say(f"check ok: {what}")


def _block_errs(hyperblocks, recon, d_gae: int):
    import numpy as np
    return np.linalg.norm(
        hyperblocks.reshape(-1, d_gae) - recon.reshape(-1, d_gae), axis=1)


def _check_tau(errs, tau: float, label: str) -> None:
    # the exact comparison the compression CLI makes (launch/compress.py)
    worst = float(errs.max())
    over = int((errs > tau * (1 + 1e-5)).sum())
    _say(f"{label}: max per-block l2 {worst!r} vs tau {tau} over "
         f"{errs.size} GAE blocks ({over} over tau)")
    _check(not over,
           f"{label}: all {errs.size} GAE blocks within tau")


def _coded_share(comp, archive) -> float:
    """Share of GAE blocks that keep at least one coefficient."""
    from repro.core import entropy
    from repro.core import exec as exec_mod

    def nonempty(chunk):
        sets = entropy.decode_index_sets(chunk.gae_index_blob,
                                         expect_dim=archive.gae_dim)
        return sum(s.size > 0 for s in sets), len(sets)

    counts = exec_mod.map_parallel(nonempty, archive.chunks)
    return sum(c for c, _ in counts) / max(1, sum(n for _, n in counts))


def _fit(dataset: str, quick: bool, seed: int, epochs_scale: float):
    import jax

    from repro.core.pipeline import HierarchicalCompressor
    from repro.data import synthetic

    cfg, hb = synthetic.make_dataset(dataset, quick=quick, seed=seed,
                                     epochs_scale=epochs_scale)
    d_gae = cfg.gae_block_elems or cfg.block_elems
    _say(f"dataset {dataset}: shape {hb.shape} ({hb.size} values, "
         f"{hb.size // d_gae} GAE blocks of {d_gae})")
    _say(f"config: emb={cfg.emb} hidden={cfg.hidden} hb_latent="
         f"{cfg.hb_latent} bae_hidden={cfg.bae_hidden} bae_latent="
         f"{cfg.bae_latent} epochs_hbae={cfg.epochs_hbae} "
         f"epochs_bae={cfg.epochs_bae}")

    comp = HierarchicalCompressor(cfg).fit(hb, seed=seed)
    jax.block_until_ready((comp.hbae_params, comp.bae_params))
    return comp, hb, d_gae


def run_single_chip(dataset: str = DATASET, *, quick: bool = False,
                    seed: int = 0, tau: float = TAU,
                    epochs_scale: float = EPOCHS_SCALE,
                    chunk_hyperblocks: int = 64,
                    out_dir: Path = OUT_DIR) -> dict:
    """Fit, compress (batch and streamed), decompress and re-read one
    dataset on the default device; raises ``SmokeFailure`` on any failed
    check and returns the facts it printed."""
    import numpy as np

    from repro.core import exec as exec_mod
    from repro.core.options import CompressOptions
    from repro.data.blocks import nrmse
    from repro.runtime import archive_io
    from repro.stream import stream_compress

    exec_mod.reset_stage_stats()          # the summary covers this phase
    comp, hb, d_gae = _fit(dataset, quick, seed, epochs_scale)
    opts = CompressOptions(tau=tau, chunk_hyperblocks=chunk_hyperblocks)
    archive = comp.compress(hb, options=opts)
    _check(not archive.verbatim_chunks(), "batch compress: no stripe "
           "quarantined")

    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{dataset}.rba"
    result = stream_compress(comp, hb, options=opts, out_path=str(path))
    s = result.stats
    _say(f"stream: {s.n_items} items, retries {dict(s.retries)}, deadline "
         f"hits {dict(s.deadline_hits)}, failovers {dict(s.failovers)}, "
         f"quarantined {result.quarantined}")
    _check(not result.quarantined and not s.total_retries()
           and not any(s.failovers.values())
           and not any(s.deadline_hits.values()),
           "stream compress: no quarantine, retry, deadline hit or failover")
    batch_bytes = archive_io.serialize_archive(archive)
    _check(path.read_bytes() == batch_bytes,
           f"streamed .rba is byte-identical to the batch archive "
           f"({len(batch_bytes)} bytes)")

    recon = comp.decompress(archive)
    errs = _block_errs(hb, recon, d_gae)
    ratio = archive.compression_ratio()
    err = nrmse(hb, recon)
    share = _coded_share(comp, archive)
    _say(f"compression ratio {ratio!r}, NRMSE {err!r}, share of GAE "
         f"blocks with m > 0: {share!r}")
    _check_tau(errs, tau, "in-memory decode")

    disk = archive_io.read_archive(str(path))
    recon2 = comp.decompress(disk)
    _check_tau(_block_errs(hb, recon2, d_gae), tau, "disk round-trip decode")
    _check(np.array_equal(recon2, recon),
           "disk round trip decodes bit-exactly")
    path.unlink()
    _say(exec_mod.stats_summary())
    return {"shape": hb.shape, "ratio": ratio, "nrmse": err,
            "max_l2": float(errs.max()), "coded_share": share,
            "archive_bytes": len(batch_bytes)}


def run_mesh(n_shards: int, dataset: str = DATASET, *, quick: bool = False,
             seed: int = 0, tau: float = TAU,
             epochs_scale: float = EPOCHS_SCALE,
             chunk_hyperblocks: int = 64) -> dict:
    """Compress with ``CompressOptions(mesh=n_shards)`` and decode over the
    mesh, against one device's compress and decode of the same model."""
    import numpy as np

    from repro.core import exec as exec_mod
    from repro.core.options import CompressOptions
    from repro.runtime import archive_io

    exec_mod.reset_stage_stats()          # the summary covers this phase
    comp, hb, d_gae = _fit(dataset, quick, seed, epochs_scale)
    opts = CompressOptions(tau=tau, chunk_hyperblocks=chunk_hyperblocks)
    single = comp.compress(hb, options=opts)
    sharded = comp.compress(hb, options=opts.replace(mesh=n_shards))
    same = (archive_io.serialize_archive(sharded)
            == archive_io.serialize_archive(single))
    _say(f"sharded archive byte-identical to single-device: {same}")
    recon1 = comp.decompress(single)
    recon_n = comp.decompress(sharded, mesh=n_shards)
    diff = float(np.abs(recon_n - recon1).max())
    _say(f"mesh={n_shards} decode bit-identical to single-device: "
         f"{bool(np.array_equal(recon_n, recon1))}, max |diff| {diff!r}")
    _say(exec_mod.stats_summary())

    # every comparison is printed before the first failed check stops the
    # phase
    _check(exec_mod.counters().get("mesh.sharded_groups", 0) > 0,
           "the sharded compress ran shard_map groups")
    _check(same, f"mesh={n_shards} archive is byte-identical to the "
           f"single-device archive")
    _check_tau(_block_errs(hb, recon1, d_gae), tau, "single-device decode")
    _check_tau(_block_errs(hb, recon_n, d_gae), tau,
               f"mesh={n_shards} decode")
    _check(bool(np.array_equal(recon_n, recon1)),
           f"mesh={n_shards} decode is bit-identical to the single-device "
           f"decode")
    return {"byte_identical": same, "max_diff": diff}


def _cache_entries(path: str) -> int:
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--mesh", type=int, default=None, metavar="N",
                    help="run only the N-chip sharded compress/decompress "
                    "and the single-device compress it is compared with")
    args = ap.parse_args(argv)
    n_chips = args.mesh or 1

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        print(f"chip_smoke needs {n_chips} TPU device(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2

    from repro.core import exec as exec_mod
    cache_dir = exec_mod.use_compile_cache()
    before = _cache_entries(cache_dir)
    _say(f"compile cache {cache_dir}: {before} entries before")
    _say(f"device: {devices[0].device_kind} x {len(devices)}; phase uses "
         f"{n_chips}")
    _say(f"cut: training depth only, epochs x{EPOCHS_SCALE} (HBAE and BAE "
         f"30 -> {max(1, int(30 * EPOCHS_SCALE))} epochs); data size and "
         f"model widths are the paper's")
    try:
        if args.mesh:
            run_mesh(args.mesh)
        else:
            run_single_chip()
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(OUT_DIR, ignore_errors=True)
    _say(f"compile cache {cache_dir}: {_cache_entries(cache_dir)} entries "
         f"after")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": n_chips}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
