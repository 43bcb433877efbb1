"""Persistent execution layer for the compression hot path.

Before this layer existed every call site in ``core.pipeline`` did
``jax.jit(fn)(args)`` inline: a *fresh* jit wrapper per call, which discards
jax's compilation cache and retraces + recompiles the model on every
``compress``/``decompress``.  This module owns three things instead:

1. **A persistent jitted-function cache** (``cache()``): one long-lived
   ``jax.jit`` wrapper per (name, static-args) key.  Under each wrapper jax's
   own trace cache keys on (params pytree structure, shape, dtype), so a
   repeated call with same-shaped inputs never retraces.  Every *actual*
   trace is counted (``retrace_counts()``) by a Python side effect that only
   runs at trace time — the regression gate in ``scripts/smoke.sh`` asserts
   the count stays 0 across repeated calls after warmup.

2. **Fused device-resident stage programs**: ``encode_frontend`` fuses
   HBAE-encode -> quantize -> dequantize -> HBAE-decode -> per-stage
   BAE-encode/quantize/decode/residual-update into ONE program, and
   ``decode_backend`` fuses dequantize -> HBAE/BAE decode -> residual sum
   into one program.  ``run_compress_stage`` chains them with the quantized
   latents staying on device, so a full compress front-end is one
   host->device transfer and one device->host transfer instead of the ~8
   ``np.asarray``/``jnp.asarray`` bounces of the old path.  Compress and
   decompress both obtain the AE reconstruction from the *same*
   ``decode_backend`` program, so the reconstruction the GAE guarantee was
   verified against is exactly the one the decoder reproduces.

3. **A shared worker pool** (``map_parallel``) for the chunk-striped entropy
   coders: archive chunks are independently codable by design (see
   docs/ARCHIVE_FORMAT.md), and the Huffman/index-set work is numpy/zlib
   dominated (GIL-releasing), so a thread pool scales the host-side loops.
   Numpy's BLAS runs on one thread (``single_blas_thread``): the pool is the
   parallelism.

4. **Spans and counters** (``stage`` / ``stage_stats`` / ``counter_*``):
   each hot-path stage records its calls, wall and on-CPU seconds and the
   values it processed, and opens a ``repro/<stage>`` profiler annotation;
   ``to_device`` / ``to_host`` count the bytes every upload and fetch moves.
   ``stats_summary()`` prints them (``launch/compress.py`` does after a
   run).
"""
from __future__ import annotations

import contextlib
import ctypes
import dataclasses
import itertools
import os
import re
import sys
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Callable, Iterable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bae as bae_mod
from repro.core import gae
from repro.core import hbae as hbae_mod
from repro.core.errors import TransientStageError
from repro.core.quantization import dequantize, quantize

Array = jax.Array


# ---------------------------------------------------------------------------
# persistent jit cache with retrace accounting
# ---------------------------------------------------------------------------

def _mesh_key(mesh) -> tuple:
    """Hashable identity of a device mesh for cache keying: axis names, axis
    sizes, and the flat device ids.  Sharded and single-device programs get
    DISTINCT cache entries, so running both in one process never retraces
    either (``mesh=None`` keys exactly like the pre-mesh cache did)."""
    if mesh is None:
        return ()
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape),
            tuple(int(d.id) for d in mesh.devices.flat))


class JitCache:
    """One persistent ``jax.jit`` wrapper per (name, statics, mesh) key.

    The wrapper body increments a per-name retrace counter — the body only
    executes while jax is *tracing*, so the counter counts actual retraces
    (shape/dtype/structure changes), not calls.

    ``mesh`` extends the key for ``shard_map``-wrapped programs: a sharded
    program is pinned to the mesh it was built over, so the same ``name``
    may coexist at several mesh shapes (plus the unsharded ``mesh=None``
    entry) without evicting or retracing one another.  ``fn`` is only
    consulted on the first call for a given key; callers that rebuild a
    ``shard_map`` wrapper per call still hit the persistent entry.
    """

    def __init__(self):
        self._fns: dict = {}
        self._retraces: dict[str, int] = {}
        self._lock = threading.Lock()

    def get(self, name: str, fn: Callable, *,
            static_argnums: Sequence[int] = (),
            static_argnames: Sequence[str] = (),
            mesh=None) -> Callable:
        key = (name, tuple(static_argnums), tuple(static_argnames),
               _mesh_key(mesh))
        with self._lock:
            cached = self._fns.get(key)
            if cached is None:
                def counted(*args, __fn=fn, __name=name, **kwargs):
                    self.count_retrace(__name)
                    return __fn(*args, **kwargs)
                # jit names the program after the wrapper: ``jit_<name>``
                counted.__name__ = counted.__qualname__ = program_name(name)
                cached = jax.jit(counted, static_argnums=static_argnums,
                                 static_argnames=static_argnames)
                self._fns[key] = cached
        return cached

    def count_retrace(self, name: str) -> None:
        with self._lock:
            self._retraces[name] = self._retraces.get(name, 0) + 1

    def retrace_counts(self) -> dict[str, int]:
        with self._lock:
            return dict(self._retraces)

    def total_retraces(self) -> int:
        with self._lock:
            return sum(self._retraces.values())


def program_name(name: str) -> str:
    """A cache key's name as a program name: ``decode_backend@hb4`` ->
    ``decode_backend_hb4`` (the HLO module is ``jit_<program_name>``)."""
    return re.sub(r"[^A-Za-z0-9_]", "_", name)


_CACHE = JitCache()


def cache() -> JitCache:
    return _CACHE


def retrace_counts() -> dict[str, int]:
    return _CACHE.retrace_counts()


def total_retraces() -> int:
    return _CACHE.total_retraces()


# ---------------------------------------------------------------------------
# persistent compilation cache placement
# ---------------------------------------------------------------------------

#: Where JAX's persistent compilation cache lives when
#: ``JAX_COMPILATION_CACHE_DIR`` is unset: one fixed directory inside the
#: checkout (the path is part of the cache key, so it must never move).
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def use_compile_cache() -> str:
    """Place JAX's persistent compilation cache and return its directory.

    Entry points call this before anything compiles (never at import).  With
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it and nothing is
    changed; otherwise the cache goes to ``COMPILE_CACHE_DIR``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


# ---------------------------------------------------------------------------
# stage timing / throughput counters
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StageStat:
    calls: int = 0
    seconds: float = 0.0
    values: int = 0
    #: on-CPU seconds of the threads inside the stage; on a host-only stage,
    #: ``seconds - cpu_seconds`` is time spent waiting for the GIL, a lock or
    #: a core
    cpu_seconds: float = 0.0

    def values_per_s(self) -> float:
        return self.values / self.seconds if self.seconds > 0 else 0.0

    def oncpu_share(self) -> float:
        return self.cpu_seconds / self.seconds if self.seconds > 0 else 0.0


_STAGES: dict[str, StageStat] = {}
_STAGE_LOCK = threading.Lock()
#: ``repro/<stage>`` annotation names, built once per stage name
_TRACE_NAMES: dict[str, str] = {}


@contextlib.contextmanager
def stage(name: str, n_values: int = 0):
    """Time one hot-path stage; accumulates wall and on-CPU time and the
    values it processed.

    The stage is also a ``repro/<name>`` profiler annotation, on the clock
    the device planes share, so a trace shows the program's spans beside
    the device's operations; with no profiler active it costs one check.
    Stages nest: an inner stage's time is also its outer stage's.

    Thread-safe: the codec worker pool and the streaming scheduler both enter
    stages concurrently, so every read-modify-write of the accumulator happens
    under ``_STAGE_LOCK`` (the ``StageStat`` instances themselves are only
    ever mutated while the lock is held; ``stage_stats`` hands out copies).
    """
    trace_name = _TRACE_NAMES.get(name)
    if trace_name is None:
        trace_name = _TRACE_NAMES.setdefault(name, "repro/" + name)
    with jax.profiler.TraceAnnotation(trace_name):
        c0 = time.thread_time()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            record_stage(name, time.perf_counter() - t0, n_values,
                         cpu_seconds=time.thread_time() - c0)


def record_stage(name: str, seconds: float, n_values: int = 0,
                 calls: int = 1, cpu_seconds: float = 0.0) -> None:
    """Accumulate a pre-measured duration into a stage counter (the streaming
    scheduler measures busy time inside worker threads and folds it in here).
    Thread-safe."""
    with _STAGE_LOCK:
        st = _STAGES.setdefault(name, StageStat())
        st.calls += int(calls)
        st.seconds += float(seconds)
        st.values += int(n_values)
        st.cpu_seconds += float(cpu_seconds)


def stage_stats() -> dict[str, StageStat]:
    with _STAGE_LOCK:
        return {k: dataclasses.replace(v) for k, v in _STAGES.items()}


def reset_stage_stats() -> None:
    """Clear stage timings AND the gauge/counter registry."""
    with _STAGE_LOCK:
        _STAGES.clear()
        _COUNTERS.clear()


# -- gauge/counter registry (queue depths, overlap seconds, ...) ------------
# Scalar counters that don't fit the calls/seconds/values shape of StageStat:
# the streaming scheduler records max queue depths and measured device/host
# overlap here.  Shares _STAGE_LOCK so a stats snapshot is one lock hop.

_COUNTERS: dict[str, float] = {}


def counter_add(name: str, delta: float = 1.0) -> None:
    with _STAGE_LOCK:
        _COUNTERS[name] = _COUNTERS.get(name, 0.0) + float(delta)


def counter_max(name: str, value: float) -> None:
    """Record a high-water mark (e.g. observed queue depth)."""
    with _STAGE_LOCK:
        if value > _COUNTERS.get(name, float("-inf")):
            _COUNTERS[name] = float(value)


def counters() -> dict[str, float]:
    with _STAGE_LOCK:
        return dict(_COUNTERS)


# -- host<->device transfers --------------------------------------------------
# Every upload and fetch of the compress and decompress paths goes through
# these two, which count the bytes moved under ``xfer.h2d_bytes`` and
# ``xfer.d2h_bytes``.

def to_device(a, sharding=None) -> Array:
    """Upload one host array (with ``sharding``: split over its devices);
    an array already on a device is not counted."""
    moved = not isinstance(a, jax.Array)
    out = (jnp.asarray(a) if sharding is None
           else jax.device_put(np.asarray(a) if moved else a, sharding))
    if moved:
        counter_add("xfer.h2d_bytes", out.nbytes)
    return out


def to_host(tree):
    """``jax.device_get(tree)``; counts the bytes of its device arrays."""
    counter_add("xfer.d2h_bytes", sum(
        x.nbytes for x in jax.tree.leaves(tree) if isinstance(x, jax.Array)))
    return jax.device_get(tree)


def stats_summary() -> str:
    """Human-readable per-stage throughput + counter + retrace report."""
    lines = []
    for name, st in sorted(stage_stats().items()):
        # durations folded in by ``record_stage`` may carry no CPU time
        oncpu = (f", {100 * st.oncpu_share():.0f}% on CPU"
                 if st.cpu_seconds else "")
        lines.append(f"{name}: {st.calls} calls, {st.seconds:.3f}s, "
                     f"{st.values_per_s() / 1e6:.2f} Mvalues/s{oncpu}")
    for name, value in sorted(counters().items()):
        lines.append(f"{name}: {value:g}")
    traces = retrace_counts()
    if traces:
        total = sum(traces.values())
        parts = ", ".join(f"{k}={v}" for k, v in sorted(traces.items()))
        lines.append(f"traces: {total} ({parts})")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# shared worker pool for chunk-parallel entropy coding
# ---------------------------------------------------------------------------

_POOL: Optional[ThreadPoolExecutor] = None
_POOL_LOCK = threading.Lock()


def codec_workers() -> int:
    """Worker count for chunk-parallel entropy coding (env-overridable;
    ``REPRO_CODEC_WORKERS=1`` forces the serial path)."""
    env = os.environ.get("REPRO_CODEC_WORKERS", "")
    if env.strip():
        try:
            return max(1, int(env))
        except ValueError:
            pass
    return max(1, min(32, os.cpu_count() or 1))


#: ``prctl`` option that sets the calling thread's OS name (linux/prctl.h)
_PR_SET_NAME = 15


def _name_os_thread() -> None:
    """Give the calling thread its Python name (at most 15 bytes) as its OS
    name, so a profiler shows each codec worker on a line of its own.  Does
    nothing off Linux or where libc has no ``prctl``."""
    if not sys.platform.startswith("linux"):
        return
    try:
        prctl = ctypes.CDLL(None, use_errno=True).prctl
    except (OSError, AttributeError):
        return
    prctl.argtypes = (ctypes.c_int, ctypes.c_char_p, ctypes.c_ulong,
                      ctypes.c_ulong, ctypes.c_ulong)
    prctl.restype = ctypes.c_int
    name = threading.current_thread().name.encode()[:15]
    prctl(_PR_SET_NAME, name, 0, 0, 0)


# -- one BLAS thread per process ----------------------------------------------
# The codec workers each run the GAE correction product (``gae._apply_codes``,
# a float32 numpy matmul) at once.  OpenBLAS keeps ONE thread pool per
# process, so concurrent calls queue on it, and its sgemm rounds differently
# at different thread counts.  The pool is the parallelism: every product
# runs on one BLAS thread, so its floats depend on neither the worker count
# nor the host's core count (OpenBLAS still picks its kernels by CPU model).
# The limit is process-wide, so it is set once and never restored.

#: readers of the BLAS thread count in force, one per library limited;
#: ``None`` until ``single_blas_thread`` has run
_BLAS_READERS: Optional[list] = None
_BLAS_LOCK = threading.Lock()


def single_blas_thread() -> None:
    """Limit numpy's BLAS to one thread, once per process (idempotent and
    thread-safe).  Uses ``threadpoolctl`` where it imports, else the
    ``openblas_set_num_threads`` of each loaded OpenBLAS; where neither finds
    a library it does nothing, and ``blas_threads()`` reads 0."""
    global _BLAS_READERS
    if _BLAS_READERS is not None:
        return
    with _BLAS_LOCK:
        if _BLAS_READERS is None:
            _BLAS_READERS = _limit_blas()


def blas_threads() -> int:
    """The BLAS thread count in force (the largest over the libraries
    limited); 0 where no library was found.  A few microseconds."""
    return max((int(get()) for get in _BLAS_READERS or ()), default=0)


def _limit_blas() -> list:
    try:
        from threadpoolctl import ThreadpoolController
    except ImportError:
        return _limit_openblas()
    blas = ThreadpoolController().select(user_api="blas")
    blas.limit(limits=1)
    return [lib.get_num_threads for lib in blas.lib_controllers]


def _limit_openblas() -> list:
    """Without ``threadpoolctl``: every OpenBLAS mapped into the process
    (numpy's among them), through its own exported setter.  Linux only."""
    try:
        with open("/proc/self/maps") as f:
            paths = {parts[5].strip() for parts in
                     (line.split(None, 5) for line in f) if len(parts) == 6}
    except OSError:
        return []
    readers = []
    for path in sorted(p for p in paths if "openblas" in os.path.basename(p)):
        try:
            lib = ctypes.CDLL(path)
        except OSError:
            continue
        # plain, 64-bit-integer and scipy-openblas builds mangle the names
        for prefix, suffix in itertools.product(("", "scipy_"),
                                                ("", "64_", "_64")):
            setter = getattr(lib, f"{prefix}openblas_set_num_threads{suffix}",
                             None)
            getter = getattr(lib, f"{prefix}openblas_get_num_threads{suffix}",
                             None)
            if setter is not None and getter is not None:
                setter.argtypes, setter.restype = (ctypes.c_int,), None
                getter.argtypes, getter.restype = (), ctypes.c_int
                setter(1)
                readers.append(getter)
                break
    return readers


def _pool() -> ThreadPoolExecutor:
    global _POOL
    with _POOL_LOCK:
        if _POOL is None:
            # before any worker can be inside a product
            single_blas_thread()
            _POOL = ThreadPoolExecutor(max_workers=codec_workers(),
                                       thread_name_prefix="repro-codec",
                                       initializer=_name_os_thread)
        return _POOL


def reset_pool() -> None:
    """Tear down the shared codec pool; the next submission lazily rebuilds
    it.  Used by tests/chaos to emulate losing the host worker pool."""
    global _POOL
    with _POOL_LOCK:
        if _POOL is not None:
            _POOL.shutdown(wait=False, cancel_futures=True)
        _POOL = None


def pool_submit(fn: Callable, *args, **kwargs) -> Future:
    """Submit one call onto the shared codec pool (the streaming scheduler's
    host-encode stage rides the same workers as ``map_parallel``).

    Resilient to a torn-down pool: a submission refused because the executor
    was shut down rebuilds the pool once and resubmits; a second refusal
    surfaces as ``TransientStageError`` so the streaming retry ladder (not
    the caller) owns the failure.
    """
    global _POOL
    try:
        return _pool().submit(fn, *args, **kwargs)
    except RuntimeError:
        with _POOL_LOCK:
            _POOL = None
        try:
            return _pool().submit(fn, *args, **kwargs)
        except RuntimeError as e:
            raise TransientStageError(
                f"codec pool rejected submission: {e}") from e


def map_parallel(fn: Callable, items: Iterable) -> list:
    """``[fn(x) for x in items]`` across the shared pool, order-preserving.

    Falls back to the serial loop for <=1 items or a 1-worker configuration
    so behavior stays bit-identical and easy to force in tests.

    Exception semantics are DETERMINISTIC BY ITEM INDEX, not completion
    order: if several items raise, the exception propagated is always the one
    from the lowest-index failing item — exactly what the serial loop would
    raise — regardless of worker scheduling.  Items after the first detected
    failure are cancelled if they have not started; items before it always
    ran to completion, so a failing streaming compress is reproducible in
    tests.
    """
    items = list(items)
    if len(items) <= 1 or codec_workers() <= 1:
        return [fn(x) for x in items]
    futures = [pool_submit(fn, x) for x in items]
    results: list = []
    first_err: Optional[BaseException] = None
    for f in futures:
        if first_err is None:
            try:
                results.append(f.result())
            except BaseException as e:   # noqa: BLE001 — re-raised below
                first_err = e
        else:
            f.cancel()
    if first_err is not None:
        raise first_err
    return results


# ---------------------------------------------------------------------------
# fused device-resident stage programs
# ---------------------------------------------------------------------------

def _encode_frontend(hbae_params: dict, bae_params: list, x: Array,
                     hb_bin: float, bae_bin: float):
    """x -> (q_lh, [q_lb per stage]); the full quantized-latent front-end as
    one device program.  Residual chaining requires the intermediate decoded
    reconstruction, so the decode work happens here too — but the
    reconstruction handed to callers always comes from ``decode_backend`` so
    encode/decode agree bit-exactly."""
    latent = hbae_mod.hbae_encode(hbae_params, x)
    q_lh = quantize(latent, hb_bin)
    recon = hbae_mod.hbae_decode(hbae_params, dequantize(q_lh, hb_bin))
    q_lbs = []
    if bae_params:
        n, k, d = x.shape
        resid = (x - recon).reshape(n * k, d)
        for p in bae_params:
            lb = bae_mod.bae_encode(p, resid)
            q_lb = quantize(lb, bae_bin)
            r_hat = bae_mod.bae_decode(p, dequantize(q_lb, bae_bin))
            recon = recon + r_hat.reshape(n, k, d)
            resid = resid - r_hat
            q_lbs.append(q_lb)
    return q_lh, q_lbs


def _decode_backend(hbae_params: dict, bae_params: list, q_lh: Array,
                    q_lbs: list, hb_bin: float, bae_bin: float) -> Array:
    """(q_lh, [q_lb]) -> reconstruction, as one device program."""
    recon = hbae_mod.hbae_decode(hbae_params, dequantize(q_lh, hb_bin))
    for p, q_lb in zip(bae_params, q_lbs):
        r_hat = bae_mod.bae_decode(p, dequantize(q_lb, bae_bin))
        recon = recon + r_hat.reshape(recon.shape)
    return recon


def _as_q32(q: np.ndarray) -> np.ndarray:
    """Entropy-decoded latents arrive int64; the device programs trace on the
    int32 the quantizer emits — cast host-side so the trace cache hits."""
    q = np.asarray(q)
    return q.astype(np.int32) if q.dtype != np.int32 else q


def run_compress_stage_async(hbae_params: dict, bae_params: list,
                             hyperblocks: np.ndarray, hb_bin: float,
                             bae_bin: float):
    """Dispatch the fused compress front-end WITHOUT blocking on the result.

    Returns the on-device ``(q_lh, [q_lb per stage], recon)`` arrays.  jax
    dispatch is asynchronous, so the call returns as soon as the programs are
    enqueued — the streaming scheduler dispatches stripe *i+1* while stripe
    *i*'s results are still being computed/fetched.  Pass the handles to
    ``fetch_compress_stage`` to materialize numpy arrays.
    """
    enc = _CACHE.get("encode_frontend", _encode_frontend)
    dec = _CACHE.get("decode_backend", _decode_backend)
    x = to_device(hyperblocks)
    q_lh, q_lbs = enc(hbae_params, bae_params, x, hb_bin, bae_bin)
    recon = dec(hbae_params, bae_params, q_lh, q_lbs, hb_bin, bae_bin)
    return q_lh, q_lbs, recon


def fetch_compress_stage(handles) -> tuple[np.ndarray, list[np.ndarray],
                                           np.ndarray]:
    """Block until the dispatched front-end finishes and fetch numpy results
    (the per-stripe ``device_get`` half of the double-buffered transfer)."""
    q_lh, q_lbs, recon = to_host(handles)
    return np.asarray(q_lh), [np.asarray(q) for q in q_lbs], np.asarray(recon)


def run_compress_stage(hbae_params: dict, bae_params: list,
                       hyperblocks: np.ndarray, hb_bin: float, bae_bin: float
                       ) -> tuple[np.ndarray, list[np.ndarray], np.ndarray]:
    """Full device-resident compress front-end: one upload, two fused
    programs (latents stay on device between them), one download.

    Returns numpy ``(q_lh, [q_lb per stage], recon)``; ``recon`` is computed
    by the same ``decode_backend`` program ``run_decompress_stage_async``
    runs on the same stripe shape, so the GAE encoder corrects exactly what
    the decoder will reproduce.
    """
    return fetch_compress_stage(run_compress_stage_async(
        hbae_params, bae_params, hyperblocks, hb_bin, bae_bin))


def run_decompress_stage_async(hbae_params: dict, bae_params: list,
                               q_lh: np.ndarray, q_lbs: list, hb_bin: float,
                               bae_bin: float, mesh=None) -> Array:
    """Dispatch the fused dequantize+decode back-end on ONE stripe's
    latents, or with a ``mesh`` on one shard group (``n_shards`` equal-width
    stripes, one per shard), without blocking.  These are the programs and
    shapes ``run_compress_stage*`` decoded the same stripes with, so the
    reconstruction is bit-identical to the one the GAE encoder verified.
    Returns the on-device reconstruction."""
    q_lh = _as_q32(q_lh)
    q_lbs = [_as_q32(q) for q in q_lbs]
    if mesh is None:
        dec = _CACHE.get("decode_backend", _decode_backend)
        return dec(hbae_params, bae_params, to_device(q_lh),
                   [to_device(q) for q in q_lbs], hb_bin, bae_bin)
    from jax.sharding import PartitionSpec as P
    shard = P(_mesh_axis())
    dec = _sharded_program(
        "decode_backend", _decode_backend, mesh,
        (P(), P(), shard, shard, P(), P()), shard)
    counter_max("mesh.shards", int(mesh.shape[_mesh_axis()]))
    return dec(hbae_params, bae_params, put_sharded(q_lh, mesh),
               [put_sharded(q, mesh) for q in q_lbs], hb_bin, bae_bin)


def _add_residual_covariance(cov: Array, x: Array, recon: Array) -> Array:
    """``cov`` plus the covariance of one stripe's residual ``x - recon``,
    cut into GAE blocks of ``cov``'s dimension."""
    return cov + gae.residual_covariance((x - recon).reshape(-1, cov.shape[0]))


def _add_residual_covariance_psum(cov: Array, x: Array, recon: Array
                                  ) -> Array:
    """The same over a shard group: each shard's covariance, ``psum``-ed."""
    return cov + gae.residual_covariance(
        (x - recon).reshape(-1, cov.shape[0]), axis_name=_mesh_axis())


def run_basis_stage_async(hbae_params: dict, bae_params: list,
                          hyperblocks: np.ndarray, hb_bin: float,
                          bae_bin: float, cov: Array, mesh=None) -> Array:
    """Add the GAE residual covariance of ONE stripe, or with a ``mesh`` of
    one shard group (one stripe per shard), to the device-resident ``cov``
    (D_gae, D_gae), without blocking.  The residual is the stripe minus the
    reconstruction ``run_compress_stage`` (``run_compress_stage_sharded``)
    computes for it, so the basis is fitted on the residuals compress codes,
    by the programs compress runs."""
    if mesh is None:
        x = to_device(hyperblocks)
        _, _, recon = run_compress_stage_async(hbae_params, bae_params, x,
                                               hb_bin, bae_bin)
        add = _CACHE.get("residual_covariance", _add_residual_covariance)
        return add(cov, x, recon)
    from jax.sharding import PartitionSpec as P
    shard = P(_mesh_axis())
    x = put_sharded(hyperblocks, mesh)
    _, _, recon = run_compress_stage_sharded_async(hbae_params, bae_params, x,
                                                   hb_bin, bae_bin, mesh)
    add = _sharded_program("residual_covariance",
                           _add_residual_covariance_psum, mesh,
                           (P(), shard, shard), P())
    return add(cov, x, recon)


# ---------------------------------------------------------------------------
# mesh-sharded stage programs (shard_map over the hyper-block data axis)
# ---------------------------------------------------------------------------
# One shard processes EXACTLY one stripe: the caller stacks ``n_shards``
# equal-width stripes (parallel.mesh_exec.plan_shard_groups), so the
# per-shard block shapes equal the single-device per-stripe shapes and the
# per-shard math is bit-identical to the unsharded path — which is what makes
# sharded archives byte-identical to single-device archives.  Params ride in
# replicated (in_spec P()); latents stay device-resident and sharded between
# the encode and decode programs (no gather in the middle).

def _mesh_axis() -> str:
    from repro.core.options import MESH_AXIS
    return MESH_AXIS


def _sharded_program(name: str, fn: Callable, mesh, in_specs, out_specs
                     ) -> Callable:
    """Build-or-fetch one shard_map-wrapped jitted program.  The retrace
    counter name carries the shard count so sharded and unsharded traces are
    distinguishable in ``retrace_counts()``."""
    axis = _mesh_axis()
    counted_name = f"{name}@{axis}{mesh.shape[axis]}"
    wrapped = jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return _CACHE.get(counted_name, wrapped, mesh=mesh)


def put_sharded(a: np.ndarray, mesh) -> Array:
    """Upload ``a`` split over the mesh's hyper-block axis, each shard's rows
    straight to its own device (no staging on the first device)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return to_device(a, NamedSharding(mesh, P(_mesh_axis())))


def run_compress_stage_sharded_async(hbae_params: dict, bae_params: list,
                                     stacked: np.ndarray, hb_bin: float,
                                     bae_bin: float, mesh):
    """Dispatch the fused compress front-end for ONE shard group: ``stacked``
    is ``n_shards`` equal-width stripes concatenated on the hyper-block axis
    (shape ``(n_shards * w, k, d)``).  Each shard runs the same two fused
    programs the single-device path runs on a ``(w, k, d)`` stripe; the
    quantized latents stay sharded on device between them.  Returns handles
    for ``fetch_compress_stage``.
    """
    from jax.sharding import PartitionSpec as P
    axis = _mesh_axis()
    shard = P(axis)
    enc = _sharded_program(
        "encode_frontend", _encode_frontend, mesh,
        (P(), P(), shard, P(), P()), (shard, shard))
    dec = _sharded_program(
        "decode_backend", _decode_backend, mesh,
        (P(), P(), shard, shard, P(), P()), shard)
    x = put_sharded(stacked, mesh)
    q_lh, q_lbs = enc(hbae_params, bae_params, x, hb_bin, bae_bin)
    recon = dec(hbae_params, bae_params, q_lh, q_lbs, hb_bin, bae_bin)
    return q_lh, q_lbs, recon


def run_compress_stage_sharded(hbae_params: dict, bae_params: list,
                               stacked: np.ndarray, hb_bin: float,
                               bae_bin: float, mesh
                               ) -> tuple[np.ndarray, list[np.ndarray],
                                          np.ndarray]:
    """Blocking sharded compress front-end for one shard group; numpy
    results cover the whole group (callers slice per stripe)."""
    out = fetch_compress_stage(run_compress_stage_sharded_async(
        hbae_params, bae_params, stacked, hb_bin, bae_bin, mesh))
    counter_max("mesh.shards", int(mesh.shape[_mesh_axis()]))
    counter_add("mesh.sharded_groups")
    return out

