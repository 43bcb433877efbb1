"""Streaming-vs-batch compress benchmark: parity, overlap, speedup.

Runs the SAME quick S3D workload through

* the batch path — ``HierarchicalCompressor.compress`` followed by
  ``archive_io.write_archive`` (everything serialized in memory, one atomic
  write at the end), and
* the streaming path — ``repro.stream.stream_compress`` (device dispatch /
  transfer / host coding pipelined, chunk sections appended to disk as they
  complete),

and records into ``BENCH_stream.json``:

* **parity** (hard gate, any mode): the streamed container file is
  byte-identical to ``serialize_archive`` of the batch archive, and
  ``compressed_bytes()`` match,
* **overlap** (hard gate, any mode): measured wall time with >= 2 pipeline
  stages simultaneously busy must be > 0,
* **speedup**: end-to-end (compress + write) wall clock, batch / stream.

Honest-hardware note: device/host overlap buys wall clock only when the
"device" half does not compete with the host coders for the same execution
resources.  On a CPU-only jax backend with a single usable core (this is
recorded in the ``machine`` block) both halves share one core, so the
physical upper bound on speedup is ~1.0x and the >= 1.2x gate is enforced
only when ``usable_cores >= 2``.  Parity and overlap accounting are
hardware-independent and always gate.

    PYTHONPATH=src python benchmarks/bench_stream_overlap.py            # full
    PYTHONPATH=src python benchmarks/bench_stream_overlap.py --smoke    # CI
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

from repro.core import exec as exec_mod
from repro.core.options import CompressOptions
from repro.core.pipeline import HierarchicalCompressor
from repro.runtime import archive_io
from repro.stream import stream_compress

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from bench_pipeline_throughput import s3d_workload, timed   # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small workload, 1 repeat, parity/overlap gate")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--tau", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--epochs-scale", type=float, default=0.1)
    ap.add_argument("--chunk-hyperblocks", type=int, default=16)
    ap.add_argument("--queue-depth", type=int, default=2)
    ap.add_argument("--out", default="BENCH_stream.json")
    args = ap.parse_args(argv)
    if args.smoke:
        args.repeats = 1
        # the smoke workload is only 16 hyper-blocks; narrow the stripes so
        # the pipeline actually has several chunks to overlap
        args.chunk_hyperblocks = min(args.chunk_hyperblocks, 4)

    cfg, hb = s3d_workload(args.smoke, args.seed, args.epochs_scale)
    print(f"workload: {hb.shape[0]} hyper-blocks of (k={hb.shape[1]}, "
          f"D={hb.shape[2]}) = {hb.size:,} values", file=sys.stderr)
    t0 = time.perf_counter()
    comp = HierarchicalCompressor(cfg).fit(hb, seed=args.seed)
    comp.fit_basis(hb)
    print(f"fit in {time.perf_counter() - t0:.1f}s", file=sys.stderr)

    tmpdir = tempfile.mkdtemp(prefix="bench_stream_")
    batch_path = os.path.join(tmpdir, "batch.rba")
    stream_path = os.path.join(tmpdir, "stream.rba")

    opts = CompressOptions(tau=args.tau,
                           chunk_hyperblocks=args.chunk_hyperblocks,
                           queue_depth=args.queue_depth)

    def batch_to_disk():
        archive = comp.compress(hb, options=opts)
        archive_io.write_archive(archive, batch_path)
        return archive

    def stream_to_disk():
        return stream_compress(comp, hb, options=opts, out_path=stream_path)

    # warmup both paths (jit traces, pools, page cache) before timing
    batch_archive = batch_to_disk()
    warm = stream_to_disk()
    traces_warm = exec_mod.total_retraces()

    exec_mod.reset_stage_stats()
    batch_s = timed(batch_to_disk, args.repeats)
    stream_s = timed(stream_to_disk, args.repeats)
    retrace_delta = exec_mod.total_retraces() - traces_warm

    # re-run once more for the stats record (timed() discards return values)
    result = stream_to_disk()
    stats = result.stats

    # -- parity: stream file == serialize_archive(batch archive) ------------
    with open(stream_path, "rb") as f:
        stream_bytes = f.read()
    with open(batch_path, "rb") as f:
        batch_bytes = f.read()
    batch_blob = archive_io.serialize_archive(batch_archive)
    parity_file = stream_bytes == batch_blob == batch_bytes
    parity_size = (batch_archive.compressed_bytes()
                   == result.archive.compressed_bytes()
                   == len(stream_bytes))
    usable = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") \
        else (os.cpu_count() or 1)
    speedup = batch_s / stream_s if stream_s > 0 else 0.0

    out = {
        "workload": {"dataset": "s3d", "smoke": args.smoke,
                     "hyperblocks": int(hb.shape[0]), "k": int(hb.shape[1]),
                     "block_elems": int(hb.shape[2]),
                     "n_values": int(hb.size), "tau": args.tau,
                     "chunk_hyperblocks": args.chunk_hyperblocks,
                     "n_chunks": len(result.archive.chunks),
                     "queue_depth": args.queue_depth,
                     "repeats": args.repeats},
        "machine": {"cpu_count": os.cpu_count(), "usable_cores": usable,
                    "codec_workers": exec_mod.codec_workers(),
                    "jax_backend": __import__("jax").default_backend(),
                    "speedup_gate_enforced": usable >= 2},
        "batch": {"compress_plus_write_s": batch_s,
                  "values_per_s": hb.size / batch_s},
        "stream": {"compress_plus_write_s": stream_s,
                   "values_per_s": hb.size / stream_s,
                   "wall_s": stats.wall_s,
                   "busy_s": round(stats.busy_s, 4),
                   "overlap_s": round(stats.overlap_s, 4),
                   "overlap_efficiency": round(stats.overlap_efficiency(), 4),
                   "stage_busy_s": {k: round(v, 4) for k, v in
                                    sorted(stats.stage_busy_s.items())},
                   "queue_high_water": stats.queue_high_water,
                   "bytes_written": result.bytes_written},
        "parity": {"file_byte_identical": parity_file,
                   "compressed_bytes_equal": parity_size},
        "speedup_stream_vs_batch": round(speedup, 3),
        "retraces_after_warmup": int(retrace_delta),
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"batch:  {batch_s:.3f}s  stream: {stream_s:.3f}s  "
          f"speedup {speedup:.2f}x")
    print(f"overlap: {stats.overlap_s:.3f}s busy "
          f"({stats.overlap_efficiency() * 100:.0f}% of wall) on "
          f"{usable} usable core(s)")
    print(f"parity: file identical={parity_file} sizes equal={parity_size}")
    print(f"written: {args.out}")

    ok = True
    if not (parity_file and parity_size):
        print("FAIL: stream/batch parity broken — chunk sections are not "
              "byte-identical", file=sys.stderr)
        ok = False
    if not stats.overlap_s > 0:
        print("FAIL: no measured device/host overlap", file=sys.stderr)
        ok = False
    if retrace_delta != 0:
        print(f"FAIL: {retrace_delta} retraces after warmup — streaming must "
              f"reuse the batch path's cached programs", file=sys.stderr)
        ok = False
    if usable >= 2 and speedup < 1.2:
        print(f"FAIL: speedup {speedup:.2f}x < 1.2x with {usable} usable "
              f"cores", file=sys.stderr)
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
