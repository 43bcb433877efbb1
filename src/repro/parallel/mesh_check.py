"""Multi-device parity selfcheck for the mesh-sharded stage pipeline.

Run as a SUBPROCESS (device count is frozen at first jax import, so a
pytest process that already initialized jax cannot host this check)::

    python -m repro.parallel.mesh_check            # 4 virtual CPU devices
    REPRO_MESH_CHECK_DEVICES=2 python -m repro.parallel.mesh_check

Asserts, on a small untrained compressor (random-init params, fitted PCA
basis — the same construction the unit tests use):

1. **batch parity** — ``compress(options=...mesh=N)`` serializes to the
   exact bytes of the single-device archive;
2. **stream parity** — ``stream_compress`` with a mesh produces the same
   bytes again, in memory AND on disk;
3. **zero retraces** — a second sharded+unsharded compress pass triggers no
   new traces (the mesh-keyed ``JitCache`` keeps both program sets live);
4. **psum basis** — the residual covariance summed over shard groups
   matches the single-device stripe-by-stripe sum, and so do the bases, to
   float32 tolerance (the order of the sum differs);
5. **sharded decompress** — the mesh decode back-end reproduces the
   single-device reconstruction within float32 tolerance and the tau
   guarantee holds on every GAE block.

Prints one JSON report; exits nonzero if any check fails.  The smoke gate
(``scripts/smoke.sh``) and ``tests/test_mesh_exec.py`` both run this.
"""
from __future__ import annotations

import json
import os
import sys

DEVICES = int(os.environ.get("REPRO_MESH_CHECK_DEVICES", "4"))


def _force_devices(n: int) -> None:
    """Must run before the first jax import in this process."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = \
            (flags + f" --xla_force_host_platform_device_count={n}").strip()


_force_devices(DEVICES)

import numpy as np                                          # noqa: E402

import jax                                                  # noqa: E402

from repro.core import CompressorConfig, HierarchicalCompressor  # noqa: E402
from repro.core import bae as bae_mod                       # noqa: E402
from repro.core import exec as exec_mod                     # noqa: E402
from repro.core import hbae as hbae_mod                     # noqa: E402
from repro.core.options import CompressOptions              # noqa: E402
from repro.parallel import mesh_exec                        # noqa: E402
from repro.runtime import archive_io                        # noqa: E402
from repro.stream import stream_compress                    # noqa: E402

TAU = 0.5


def _make_comp(n_hb: int = 24) -> tuple[HierarchicalCompressor, np.ndarray]:
    cfg = CompressorConfig(block_elems=40, k=2, emb=16, hidden=32,
                           hb_latent=8, bae_hidden=32, bae_latent=4,
                           gae_block_elems=80, hb_bin=0.01, bae_bin=0.01,
                           gae_bin=0.02)
    comp = HierarchicalCompressor(cfg)
    khb, kb = jax.random.split(jax.random.PRNGKey(0))
    comp.hbae_params = hbae_mod.hbae_init(
        khb, in_dim=cfg.block_elems, k=cfg.k, emb=cfg.emb, hidden=cfg.hidden,
        latent=cfg.hb_latent, heads=cfg.heads)
    comp.bae_params = [bae_mod.bae_init(kb, in_dim=cfg.block_elems,
                                        hidden=cfg.bae_hidden,
                                        latent=cfg.bae_latent)]
    rng = np.random.default_rng(0)
    hb = 0.1 * rng.standard_normal(
        (n_hb, cfg.k, cfg.block_elems)).astype(np.float32)
    comp.fit_basis(hb)          # shared basis: parity is about the pipeline
    return comp, hb


def main() -> int:
    checks: list[dict] = []

    def check(name: str, ok: bool, detail: str = "") -> None:
        checks.append({"name": name, "ok": bool(ok), "detail": detail})

    n_dev = len(jax.devices())
    want = DEVICES
    if n_dev < max(2, want):
        print(json.dumps({
            "ok": False, "devices": n_dev,
            "error": f"need {want} devices, found {n_dev} — jax was "
                     f"imported before XLA_FLAGS took effect, or the "
                     f"platform refuses virtual devices"}))
        return 1

    comp, hb = _make_comp()
    # chunk width 4 over 24 hyper-blocks with 4 shards: one aligned group of
    # 4 stripes (the shard_map path) + a 2-stripe ragged tail (per-stripe
    # path) — both paths exercised in one archive
    base_opts = CompressOptions(tau=TAU, chunk_hyperblocks=4)
    mesh_opts = base_opts.replace(mesh=want)

    single = comp.compress(hb, options=base_opts)
    sharded = comp.compress(hb, options=mesh_opts)
    blob_single = archive_io.serialize_archive(single)
    blob_sharded = archive_io.serialize_archive(sharded)
    check("batch_parity", blob_sharded == blob_single,
          f"{len(blob_single)} bytes, {len(single.chunks)} chunks")

    cnt = exec_mod.counters()
    check("sharded_groups_ran", cnt.get("mesh.sharded_groups", 0) >= 1
          and cnt.get("mesh.shards", 0) == want,
          f"groups={cnt.get('mesh.sharded_groups', 0)} "
          f"shards={cnt.get('mesh.shards', 0)}")

    out = os.path.join(os.environ.get("TMPDIR", "/tmp"),
                       f"mesh_check_{os.getpid()}.rba")
    try:
        result = stream_compress(comp, hb, options=mesh_opts, out_path=out)
        blob_stream = archive_io.serialize_archive(result.archive)
        with open(out, "rb") as f:
            disk = f.read()
        check("stream_parity",
              blob_stream == blob_single and disk == blob_single,
              f"stream items={result.stats.n_items} "
              f"chunks={len(result.archive.chunks)}")
    finally:
        for p in (out, out + ".partial"):
            if os.path.exists(p):
                os.unlink(p)

    before = exec_mod.total_retraces()
    comp.compress(hb, options=base_opts)
    comp.compress(hb, options=mesh_opts)
    delta = exec_mod.total_retraces() - before
    check("zero_retraces_after_warmup", delta == 0,
          f"delta={delta} counts={exec_mod.retrace_counts()}")

    # psum basis: the covariance summed over shard groups (plus the
    # per-stripe tail) equals the single-device stripe-by-stripe sum, and
    # the bases agree.  That needs a FULL-RANK covariance (GAE blocks >>
    # dims) — on a rank-deficient one the null-space eigenvectors are
    # arbitrary.  Column comparison is sign-invariant (|u_i . v_i| ~ 1):
    # eigh's per-column sign is a convention, not math.
    comp3, hb3 = _make_comp(n_hb=200)
    cov_single = comp3.residual_covariance(hb3, chunk_hyperblocks=4)
    cov_mesh = comp3.residual_covariance(hb3, mesh=want, chunk_hyperblocks=4)
    basis_single = comp3.fit_basis(hb3, chunk_hyperblocks=4)
    basis_sharded = comp3.fit_basis(hb3, mesh=want, chunk_hyperblocks=4)
    align = np.abs(np.sum(basis_single * basis_sharded, axis=0))
    cov_gap = float(np.abs(cov_mesh - cov_single).max()
                    / np.abs(cov_single).max())
    check("psum_basis_consistent",
          basis_sharded.shape == basis_single.shape
          and cov_gap < 1e-5 and bool(np.all(align > 1 - 1e-3)),
          f"covariance gap {cov_gap:.2e}, min |col alignment| = "
          f"{float(align.min()):.6f}")

    # ...and the end-to-end property that actually matters: a basis fitted
    # THROUGH the mesh still drives a guarantee-satisfying compress
    comp2, hb2 = _make_comp()
    comp2.basis = None
    comp2.fit_basis(hb2, mesh=want, chunk_hyperblocks=4)
    a2 = comp2.compress(hb2, options=base_opts)
    r2 = comp2.decompress(a2)
    d_gae = comp2.cfg.gae_block_elems or comp2.cfg.block_elems
    errs2 = np.linalg.norm((hb2 - r2).reshape(-1, d_gae), axis=1)
    check("sharded_basis_honors_tau",
          float(errs2.max()) <= TAU * (1 + 1e-5),
          f"max block l2 {float(errs2.max()):.4f} <= tau={TAU}")

    dec_single = comp.decompress(single)
    dec_sharded = comp.decompress(single, mesh=want)
    d_gae = comp.cfg.gae_block_elems or comp.cfg.block_elems
    errs = np.linalg.norm((hb - dec_sharded).reshape(-1, d_gae), axis=1)
    check("sharded_decompress",
          bool(np.array_equal(dec_sharded, dec_single))
          and float(errs.max()) <= TAU * (1 + 1e-5),
          f"max block l2 {float(errs.max()):.4f} <= tau={TAU}, "
          f"max |recon diff| = "
          f"{float(np.max(np.abs(dec_sharded - dec_single))):.3g}")

    ok = all(c["ok"] for c in checks)
    print(json.dumps({"ok": ok, "devices": n_dev, "shards": want,
                      "checks": checks}, indent=2))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
