"""End-to-end compressor pipeline (paper Fig. 1).

``HierarchicalCompressor`` ties together:
  hyper-block AE (coarse)  ->  block-wise residual AE(s) (fine)  ->
  GAE PCA post-processing (guaranteed per-block l2 bound)  ->
  quantization + Huffman + index-bitmask/zlib bitstream.

The object is fit on (a training split of) the data, then ``compress`` returns
an ``Archive`` whose ``total_bytes()`` is the honest storage cost (AE latents +
GAE coefficients + index sets + per-block headers).  Model weights and the PCA
basis are excluded by default — the paper's ratio accounting amortizes them
("we considered the latent spaces of both autoencoders, as well as the PCA
coefficients and corresponding index information", Sec. III-C); pass
``include_model_cost=True`` to count them too.
"""
from __future__ import annotations

import dataclasses
import json
import math
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bae as bae_mod
from repro.core import entropy, gae
from repro.core import exec as exec_mod
from repro.core import hbae as hbae_mod
from repro.core import training
from repro.core.errors import (ArchiveError, ChecksumMismatch, ChunkDamage,
                               ConfigError, DamageReport,
                               GuaranteeUnsatisfiable, MalformedStream)
from repro.core.options import CompressOptions

Array = jax.Array

#: Stripe width in hyper-blocks that ``fit`` and ``fit_basis`` run at unless
#: told otherwise: compress's own default, so the basis is fitted on the
#: stripes compress codes.
STRIPE_HYPERBLOCKS = CompressOptions.chunk_hyperblocks


@dataclasses.dataclass
class CompressorConfig:
    block_elems: int                 # flattened AE block size
    k: int                           # blocks per hyper-block
    emb: int = 128
    hidden: int = 256
    hb_latent: int = 128             # paper: 128 S3D / 64 E3SM,XGC
    bae_hidden: int = 256
    bae_latent: int = 16             # paper: 16 for all datasets
    heads: int = 1
    use_attention: bool = True       # False => 'HBAE-woa' ablation
    use_bae: bool = True             # False => 'HBAE' ablation
    n_bae_stages: int = 1            # 2 => 'StackAE' ablation
    hb_bin: float = 0.005
    bae_bin: float = 0.005
    gae_bin: float = 0.01
    gae_block_elems: Optional[int] = None   # GAE may re-block (paper Sec. II-D)
    epochs_hbae: int = 30
    epochs_bae: int = 30
    batch: int = 64
    lr: float = 1e-3


@dataclasses.dataclass
class ArchiveChunk:
    """One hyper-block stripe: every stream needed to decode hyper-blocks
    ``[hb_start, hb_start + n_hyperblocks)`` independently of other chunks.

    A non-empty ``verbatim_blob`` marks a QUARANTINED stripe: the learned
    encoder could not ship it (exhausted retries or an unsatisfiable
    guarantee), so the payload is the deflate-packed raw float32 stripe
    itself — losslessly decodable, hence trivially within any tau — and all
    latent/GAE streams are absent (``hb_stream is None``).
    """
    hb_start: int
    n_hyperblocks: int
    hb_stream: Optional[entropy.HuffmanStream]
    bae_streams: list[entropy.HuffmanStream]
    gae_coeff_stream: Optional[entropy.HuffmanStream]
    gae_index_blob: bytes
    gae_binexp_blob: bytes
    verbatim_blob: bytes = b""


@dataclasses.dataclass
class Archive:
    """Compressed representation, striped into independently-decodable chunks.

    ``chunks`` entries may be ``None`` after a tolerant container read
    (``archive_io.read_archive(strict=False)``): the stripe failed its digest
    or framing checks and ``chunk_errors[i]`` holds the reason.
    """
    n_hyperblocks: int
    n_values: int                    # original float32 count
    chunk_hyperblocks: int           # stripe width (hyper-blocks per chunk)
    gae_dim: int                     # PCA basis dimension (0 = no GAE section)
    chunks: list[Optional[ArchiveChunk]]
    chunk_errors: dict[int, str] = dataclasses.field(default_factory=dict)
    _size_cache: Optional[int] = dataclasses.field(
        default=None, repr=False, compare=False)

    def verbatim_chunks(self) -> list[int]:
        """Indices of quarantined (lossless verbatim-fallback) chunks."""
        return [i for i, c in enumerate(self.chunks)
                if c is not None and c.verbatim_blob]

    def compressed_bytes(self) -> int:
        """Honest on-disk cost: the exact size of the serialized container
        (magic, section table, digests, framing — everything).  Computed from
        the section framing arithmetic (no full serialize) and cached, so
        ``compression_ratio`` is O(sections) once instead of O(archive) per
        query; mutators must call ``invalidate_size_cache``."""
        if self._size_cache is None:
            from repro.runtime import archive_io   # runtime owns the container
            self._size_cache = archive_io.serialized_size(self)
        return self._size_cache

    def invalidate_size_cache(self) -> None:
        self._size_cache = None

    def compression_ratio(self, include_model_bytes: int = 0) -> float:
        return (self.n_values * 4) / (self.compressed_bytes() + include_model_bytes)


@dataclasses.dataclass
class _VerbatimStripe:
    """Decoded form of a quarantined chunk: the raw hyper-blocks."""
    data: np.ndarray


MODEL_FORMAT = "repro-compressor-v2"

# Static (non-array) param-tree leaves that the manifest records by name +
# field dict instead of pickling.  Anything else non-array fails save loudly.
def _static_registry() -> dict:
    from repro.core.attention import AttnMeta
    from repro.core.hbae import HbaeMeta
    return {"AttnMeta": AttnMeta, "HbaeMeta": HbaeMeta}


def _flatten_params(obj, prefix: str, leaves: list, statics: dict) -> None:
    """Walk dict/list param trees into (path, array) leaves; registered static
    dataclasses are recorded as JSON-able entries in ``statics``."""
    if isinstance(obj, dict):
        for key in sorted(obj):
            _flatten_params(obj[key], f"{prefix}/{key}" if prefix else key,
                            leaves, statics)
    elif isinstance(obj, (list, tuple)):
        for i, item in enumerate(obj):
            _flatten_params(item, f"{prefix}/{i}" if prefix else str(i),
                            leaves, statics)
    elif type(obj).__name__ in _static_registry():
        statics[prefix] = {"class": type(obj).__name__,
                           "fields": dataclasses.asdict(obj)}
    elif hasattr(obj, "shape") and hasattr(obj, "dtype"):
        leaves.append((prefix, np.asarray(obj)))
    else:
        raise TypeError(f"cannot serialize param leaf {prefix!r} "
                        f"of type {type(obj).__name__}")


def _assemble_params(entries: list, statics: dict) -> dict:
    """Rebuild the nested dict tree from (path, value) pairs + statics."""
    registry = _static_registry()
    root: dict = {}
    items = list(entries)
    for path, spec in statics.items():
        if spec.get("class") not in registry:
            raise MalformedStream(f"unknown static class {spec.get('class')!r}")
        items.append((path, registry[spec["class"]](**spec["fields"])))
    for path, value in items:
        parts = path.split("/")
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
            if not isinstance(node, dict):
                raise MalformedStream(f"conflicting manifest paths at {path!r}")
        node[parts[-1]] = value
    return root


class HierarchicalCompressor:
    """fit / compress / decompress on hyper-block-shaped data (N, k, D)."""

    def __init__(self, config: CompressorConfig):
        self.cfg = config
        self.hbae_params: Optional[dict] = None
        self.bae_params: list[dict] = []
        self.basis: Optional[np.ndarray] = None

    # -- training ----------------------------------------------------------
    def fit(self, hyperblocks: np.ndarray, seed: int = 0,
            log: Optional[Callable] = None) -> "HierarchicalCompressor":
        """Train the HBAE on ``hyperblocks``, then each BAE stage on the
        residual the stages before it leave.  The training steps gather
        their batches from the whole field held on the device; the
        residuals are computed stripe by stripe (``STRIPE_HYPERBLOCKS``
        wide, span ``fit_forward``), so no forward program holds the
        field."""
        cfg = self.cfg
        n, k, d = hyperblocks.shape
        assert k == cfg.k and d == cfg.block_elems, (hyperblocks.shape, cfg)
        key = jax.random.PRNGKey(seed)
        khb, *kbs = jax.random.split(key, 1 + max(cfg.n_bae_stages, 1))
        self.hbae_params = training.train_hbae(
            khb, hyperblocks, emb=cfg.emb, hidden=cfg.hidden, latent=cfg.hb_latent,
            heads=cfg.heads, use_attention=cfg.use_attention,
            epochs=cfg.epochs_hbae, batch=cfg.batch, lr=cfg.lr, seed=seed, log=log)
        self.bae_params = []
        if not cfg.use_bae:
            return self
        spans = self.stripe_spans(n, STRIPE_HYPERBLOCKS, with_gae=False)
        resid = np.empty((n * k, d), np.float32)
        hbae_fn = exec_mod.cache().get("hbae_apply", hbae_mod.hbae_apply)
        with exec_mod.stage("fit_forward", hyperblocks.size):
            for s, w in spans:
                x = hyperblocks[s:s + w]
                y, _ = hbae_fn(self.hbae_params, exec_mod.to_device(x))
                resid[s * k:(s + w) * k] = (x - exec_mod.to_host(y)).reshape(
                    w * k, d)
                exec_mod.counter_add("fit.stripes")
        bae_fn = exec_mod.cache().get("bae_apply", bae_mod.bae_apply)
        for st in range(cfg.n_bae_stages):
            p = training.train_bae(kbs[st], resid, hidden=cfg.bae_hidden,
                                   latent=cfg.bae_latent, epochs=cfg.epochs_bae,
                                   batch=max(cfg.batch * 4, 256), lr=cfg.lr,
                                   seed=seed + st, log=log)
            self.bae_params.append(p)
            if st + 1 == cfg.n_bae_stages:
                break
            # the next stage trains on what this one leaves
            with exec_mod.stage("fit_forward", resid.size):
                for s, w in spans:
                    rows = resid[s * k:(s + w) * k]
                    r_hat, _ = bae_fn(p, exec_mod.to_device(rows))
                    rows -= exec_mod.to_host(r_hat)
                    exec_mod.counter_add("fit.stripes")
        return self

    # -- forward helpers ----------------------------------------------------
    def _stage_params(self) -> list[dict]:
        return self.bae_params if self.cfg.use_bae else []

    # -- PCA basis -----------------------------------------------------------
    def fit_basis(self, hyperblocks: np.ndarray, mesh=None,
                  chunk_hyperblocks: int = STRIPE_HYPERBLOCKS) -> np.ndarray:
        """PCA basis of the AE residuals at GAE block granularity.

        The field runs stripe by stripe, on the stripes ``compress`` cuts at
        ``chunk_hyperblocks`` and through the programs it runs on them, so
        the basis is fitted on exactly the residuals compress codes.  Each
        stripe's D x D residual covariance is added up on the device in
        float32 and one ``eigh`` of the sum gives the basis (span
        ``basis_fit``).  With a ``mesh`` (anything
        ``parallel.mesh_exec.resolve_mesh`` accepts) the aligned groups of
        stripes run one per shard, as compress runs them, and their
        covariances are ``psum``-ed over the hyper-block axis.
        """
        with exec_mod.stage("basis_fit", hyperblocks.size):
            cov = self.residual_covariance(hyperblocks, mesh,
                                           chunk_hyperblocks)
            self.basis = np.asarray(gae.pca_basis(jnp.asarray(cov)))
        return self.basis

    def residual_covariance(self, hyperblocks: np.ndarray, mesh=None,
                            chunk_hyperblocks: int = STRIPE_HYPERBLOCKS
                            ) -> np.ndarray:
        """The (D_gae, D_gae) float32 covariance ``fit_basis`` diagonalizes:
        ``r.T @ r`` over every GAE block's AE residual ``r``, summed stripe
        by stripe on the device."""
        cfg = self.cfg
        d_gae = cfg.gae_block_elems or cfg.block_elems
        spans = self.stripe_spans(hyperblocks.shape[0], chunk_hyperblocks,
                                  with_gae=True)
        runs = [(s, s + w, None) for s, w in spans]
        resolved = None
        if mesh is not None:
            from repro.parallel import mesh_exec
            resolved = mesh_exec.resolve_mesh(mesh)
        if resolved is not None:
            groups, tail = mesh_exec.plan_shard_groups(
                spans, mesh_exec.mesh_shards(resolved))
            runs = ([(*mesh_exec.group_slice(g), resolved) for g in groups]
                    + [(s, s + w, None) for s, w in tail])
        zero = np.zeros((d_gae, d_gae), np.float32)
        # one sum per placement: the shard groups' (replicated over the
        # mesh) and the single-device stripes'
        sums: dict = {}
        for a, b, m in runs:
            if m not in sums:
                sums[m] = (exec_mod.to_device(zero) if m is None else
                           exec_mod.to_device(zero, mesh_exec.replicated(m)))
            prev = sums[m]
            sums[m] = exec_mod.run_basis_stage_async(
                self.hbae_params, self._stage_params(), hyperblocks[a:b],
                cfg.hb_bin, cfg.bae_bin, prev, mesh=m)
            # at most two stripes in flight: the device never holds more
            prev.block_until_ready()
            exec_mod.counter_add("fit.stripes")
        return sum(np.asarray(c, np.float32)
                   for c in exec_mod.to_host(list(sums.values())))

    def _gae_view(self, blocks3d: np.ndarray) -> np.ndarray:
        """(N, k, D) -> (N_gae, D_gae): GAE may use a different block size."""
        d_gae = self.cfg.gae_block_elems or self.cfg.block_elems
        flat = blocks3d.reshape(-1)
        assert flat.size % d_gae == 0
        return flat.reshape(-1, d_gae)

    def _gae_unview(self, gae_blocks: np.ndarray, shape3d: tuple) -> np.ndarray:
        return gae_blocks.reshape(shape3d)

    # -- compress / decompress ----------------------------------------------
    def _chunk_width(self, requested: int, with_gae: bool) -> int:
        """Stripe width in hyper-blocks, aligned so every chunk covers a whole
        number of GAE blocks (chunks must decode independently).

        A non-positive request is a :class:`ConfigError` (it used to be
        silently clamped to 1, which hid caller bugs and produced archives
        with a different stripe width than asked for)."""
        cfg = self.cfg
        width = int(requested)
        if width < 1:
            raise ConfigError(
                f"chunk_hyperblocks must be >= 1, got {requested!r} (a "
                f"zero-width stripe can never tile the hyper-block axis)")
        if with_gae:
            d_gae = cfg.gae_block_elems or cfg.block_elems
            per_hb = cfg.k * cfg.block_elems
            align = d_gae // math.gcd(d_gae, per_hb)   # chunk width multiple
            width = ((width + align - 1) // align) * align
        return width

    def stripe_spans(self, n_hyperblocks: int, chunk_hyperblocks: int,
                     with_gae: bool) -> list[tuple[int, int]]:
        """``[(hb_start, n_hb), ...]`` stripe tiling of ``n_hyperblocks`` at
        the GAE-aligned chunk width.  The SAME tiling drives the batch
        compress loop, the streaming scheduler, and the streaming archive
        writer's up-front section table."""
        width = self._chunk_width(chunk_hyperblocks, with_gae=with_gae)
        return [(s, min(width, n_hyperblocks - s))
                for s in range(0, n_hyperblocks, width)]

    def encode_stripe_device(self, stripe: np.ndarray
                             ) -> tuple[np.ndarray, list[np.ndarray],
                                        np.ndarray]:
        """Device half of one stripe's encode: fused front-end + shared
        decode program on the stripe's hyper-blocks only."""
        return exec_mod.run_compress_stage(
            self.hbae_params, self._stage_params(), stripe,
            self.cfg.hb_bin, self.cfg.bae_bin)

    def encode_stripe_host(self, hb_start: int, stripe: np.ndarray,
                           q_lh: np.ndarray, q_lbs: list[np.ndarray],
                           recon: np.ndarray, tau: Optional[float],
                           gae_dim: int) -> ArchiveChunk:
        """Host half of one stripe's encode: GAE error-bound coding + chunk
        entropy coding, from the stripe's own data only.

        Both the batch ``compress`` loop and the streaming scheduler call
        exactly this function on exactly the same slices, which is what makes
        their chunk sections byte-identical by construction (not by floating-
        point luck across different batch shapes).
        """
        cfg = self.cfg
        k, d = cfg.k, cfg.block_elems
        codes: list[gae.GAEBlockCode] = []
        if tau is not None:
            d_gae = cfg.gae_block_elems or d
            gae_per_hb = (k * d) // d_gae
            with exec_mod.stage("gae_encode", stripe.size):
                x_gae = self._gae_view(stripe)
                r_gae = self._gae_view(recon)
                try:
                    _, codes = gae.gae_encode_blocks(x_gae, r_gae, self.basis,
                                                     tau, cfg.gae_bin)
                except GuaranteeUnsatisfiable as e:
                    # re-raise with the GLOBAL GAE block index so diagnostics
                    # are stripe-independent
                    raise GuaranteeUnsatisfiable(
                        block=hb_start * gae_per_hb + e.block, err=e.err,
                        tau=e.tau, max_refine=e.max_refine) from e
        with exec_mod.stage("entropy_encode", stripe.size):
            # GAEBlockCode stores indices/coefficients in ascending index
            # order — exactly the bitmask decode order, no per-code sort
            all_coeffs, index_sets, binexps = [], [], []
            for c in codes:
                index_sets.append(c.indices)
                all_coeffs.append(c.qcoeffs)
                binexps.append(c.bin_exp)
            coeffs = (np.concatenate(all_coeffs) if all_coeffs else
                      np.zeros(0, np.int64))
            with exec_mod.stage("huffman_encode", stripe.size):
                hb_stream = entropy.huffman_compress(q_lh)
                bae_streams = [entropy.huffman_compress(q_lb)
                               for q_lb in q_lbs]
                coeff_stream = (entropy.huffman_compress(coeffs)
                                if coeffs.size else None)
            index_blob = binexp_blob = b""
            if tau is not None:
                with exec_mod.stage("index_encode", stripe.size):
                    index_blob = entropy.encode_index_sets(index_sets,
                                                           gae_dim)
                    binexp_blob = entropy.zlib_pack(
                        np.asarray(binexps, np.uint8).tobytes())
        return ArchiveChunk(
            hb_start=hb_start, n_hyperblocks=stripe.shape[0],
            hb_stream=hb_stream, bae_streams=bae_streams,
            gae_coeff_stream=coeff_stream, gae_index_blob=index_blob,
            gae_binexp_blob=binexp_blob)

    def encode_stripe_verbatim(self, hb_start: int,
                               stripe: np.ndarray) -> ArchiveChunk:
        """Guaranteed-bound fallback for a quarantined stripe: ship the raw
        float32 values (deflate-packed).  Lossless, so the per-block l2
        error is exactly 0 <= tau for any tau; costs compression ratio on
        this stripe only.  Decoded by ``decode_stripe_verbatim``."""
        raw = np.ascontiguousarray(stripe, dtype="<f4").tobytes()
        return ArchiveChunk(
            hb_start=int(hb_start), n_hyperblocks=int(stripe.shape[0]),
            hb_stream=None, bae_streams=[], gae_coeff_stream=None,
            gae_index_blob=b"", gae_binexp_blob=b"",
            verbatim_blob=entropy.zlib_pack(raw))

    def decode_stripe_verbatim(self, chunk: ArchiveChunk) -> np.ndarray:
        """Inverse of ``encode_stripe_verbatim``; validates the payload size
        against the chunk's declared hyper-block range."""
        cfg = self.cfg
        raw = entropy.zlib_unpack(chunk.verbatim_blob)
        want = chunk.n_hyperblocks * cfg.k * cfg.block_elems * 4
        if len(raw) != want:
            raise MalformedStream(
                f"verbatim chunk holds {len(raw)} bytes for "
                f"{chunk.n_hyperblocks} hyper-blocks, expected {want}")
        return np.frombuffer(raw, "<f4").reshape(
            chunk.n_hyperblocks, cfg.k, cfg.block_elems).copy()

    def prepare_compress(self, hyperblocks: np.ndarray, tau: Optional[float],
                         mesh=None,
                         chunk_hyperblocks: int = STRIPE_HYPERBLOCKS) -> int:
        """Shared compress preamble: fit the PCA basis if the caller asked
        for a guarantee and none exists yet (on compress's stripes, sharded
        over ``mesh`` when one is active).  Returns ``gae_dim``."""
        if tau is not None:
            if self.basis is None:
                self.fit_basis(hyperblocks, mesh=mesh,
                               chunk_hyperblocks=chunk_hyperblocks)
            return int(self.basis.shape[0])
        return 0

    def encode_group_device(self, group, hyperblocks: np.ndarray, mesh
                            ) -> list[tuple]:
        """Device half of one shard GROUP's encode: ``len(group)`` equal-width
        stripes run as ONE ``shard_map`` call, one stripe per shard
        (``parallel.mesh_exec.plan_shard_groups`` guarantees the alignment).
        Returns per-stripe ``(q_lh, q_lbs, recon)`` tuples in span order —
        the same slices ``encode_stripe_device`` would have produced, so the
        downstream host coders cannot tell the paths apart."""
        from repro.parallel import mesh_exec
        start, stop = mesh_exec.group_slice(group)
        g_lh, g_lbs, g_recon = exec_mod.run_compress_stage_sharded(
            self.hbae_params, self._stage_params(), hyperblocks[start:stop],
            self.cfg.hb_bin, self.cfg.bae_bin, mesh)
        k = self.cfg.k
        out = []
        for s, w in group:
            lo = s - start
            out.append((g_lh[lo:lo + w],
                        [q[lo * k:(lo + w) * k] for q in g_lbs],
                        g_recon[lo:lo + w]))
        return out

    def compress(self, hyperblocks: np.ndarray,
                 options: Optional[CompressOptions] = None) -> Archive:
        """Batch-synchronous compress: the device front-end runs stripe by
        stripe to completion, THEN the host GAE/entropy coders fan out over
        the finished stripes.  ``repro.stream.stream_compress`` runs the same
        per-stripe stages pipelined (host coding of stripe *i* overlapped
        with the device stage of stripe *i+1*) and produces byte-identical
        chunks.

        Configuration comes in as ONE ``repro.core.options.CompressOptions``
        (``None`` means ``CompressOptions()``).  With ``options.mesh`` set,
        aligned runs of stripes execute as single ``shard_map`` calls — one
        stripe per shard — and the archive stays byte-identical to the
        single-device result (per-shard shapes equal per-stripe shapes, so
        the floats are bit-equal, and chunk boundaries never move).
        """
        opts = options if options is not None else CompressOptions()
        tau = opts.tau
        n, k, d = hyperblocks.shape
        mesh = None
        if opts.mesh is not None:
            from repro.parallel import mesh_exec
            mesh = mesh_exec.resolve_mesh(opts.mesh)
        gae_dim = self.prepare_compress(hyperblocks, tau, mesh=mesh,
                                        chunk_hyperblocks=opts.chunk_hyperblocks)
        spans = self.stripe_spans(n, opts.chunk_hyperblocks,
                                  with_gae=tau is not None)

        # 1+2. fused device-resident AE front-end.  Unsharded: one stripe per
        # program call (the stripe IS the archive chunk, so batch and
        # streaming run identical device shapes).  Sharded: aligned groups of
        # ``n_shards`` stripes run as one shard_map call each; the ragged
        # tail takes the per-stripe path.
        latents: list[tuple] = []
        with exec_mod.stage("ae_encode", hyperblocks.size):
            tail = spans
            if mesh is not None:
                from repro.parallel import mesh_exec
                groups, tail = mesh_exec.plan_shard_groups(
                    spans, mesh_exec.mesh_shards(mesh))
                for group in groups:
                    latents.extend(self.encode_group_device(
                        group, hyperblocks, mesh))
            for start, n_hb in tail:
                latents.append(self.encode_stripe_device(
                    hyperblocks[start:start + n_hb]))

        # 3+4. host-side GAE + entropy coding, chunk-parallel over stripes
        # (chunks are independently codable by construction).  Shard
        # boundaries coincide with stripe boundaries, so each chunk's
        # entropy fan-out consumes only rows its own shard produced.
        def encode_chunk(i: int) -> ArchiveChunk:
            start, n_hb = spans[i]
            q_lh, q_lbs, recon = latents[i]
            return self.encode_stripe_host(
                start, hyperblocks[start:start + n_hb], q_lh, q_lbs, recon,
                tau, gae_dim)

        chunks: list[Optional[ArchiveChunk]] = exec_mod.map_parallel(
            encode_chunk, range(len(spans)))

        return Archive(n_hyperblocks=n, n_values=hyperblocks.size,
                       chunk_hyperblocks=self._chunk_width(
                           opts.chunk_hyperblocks, with_gae=tau is not None),
                       gae_dim=gae_dim, chunks=chunks)

    # -- decode helpers ------------------------------------------------------
    def _decode_chunk(self, chunk: ArchiveChunk, archive: Archive
                      ) -> tuple[np.ndarray, list[np.ndarray],
                                 list[gae.GAEBlockCode]]:
        """Decode one chunk's streams into quantized latents + GAE codes,
        cross-checking every count against the model configuration.  Raises
        a typed ``ArchiveError`` on any inconsistency.  A quarantined
        (verbatim) chunk short-circuits to a ``_VerbatimStripe`` carrying the
        losslessly decoded hyper-blocks."""
        cfg = self.cfg
        if chunk.verbatim_blob:
            return _VerbatimStripe(self.decode_stripe_verbatim(chunk))
        if chunk.hb_stream is None:
            raise MalformedStream("chunk has neither latent streams nor a "
                                  "verbatim payload")
        n_hb, k, d = chunk.n_hyperblocks, cfg.k, cfg.block_elems
        want_hb = n_hb * cfg.hb_latent
        if chunk.hb_stream.count != want_hb:
            raise MalformedStream(
                f"hb stream has {chunk.hb_stream.count} symbols, "
                f"expected {want_hb}")
        n_values = n_hb * k * d
        with exec_mod.stage("huffman_decode", n_values):
            q_lh = entropy.huffman_decompress(chunk.hb_stream)\
                .reshape(n_hb, cfg.hb_latent)
            if len(chunk.bae_streams) != len(self.bae_params):
                raise MalformedStream(
                    f"{len(chunk.bae_streams)} BAE streams for "
                    f"{len(self.bae_params)} BAE stages")
            q_lbs = []
            for stream in chunk.bae_streams:
                want = n_hb * k * cfg.bae_latent
                if stream.count != want:
                    raise MalformedStream(
                        f"BAE stream has {stream.count} symbols, "
                        f"expected {want}")
                q_lbs.append(entropy.huffman_decompress(stream)
                             .reshape(n_hb * k, cfg.bae_latent))
        codes: list[gae.GAEBlockCode] = []
        if chunk.gae_index_blob:
            if archive.gae_dim <= 0:
                raise MalformedStream("GAE section present but gae_dim == 0")
            d_gae = cfg.gae_block_elems or d
            if (n_hb * k * d) % d_gae:
                raise MalformedStream(
                    f"chunk of {n_hb * k * d} values not divisible into "
                    f"GAE blocks of {d_gae}")
            n_gae = n_values // d_gae
            with exec_mod.stage("index_decode", n_values):
                index_sets = entropy.decode_index_sets(
                    chunk.gae_index_blob, expect_dim=archive.gae_dim,
                    expect_sets=n_gae)
                binexps = np.frombuffer(
                    entropy.zlib_unpack(chunk.gae_binexp_blob), np.uint8)
            if binexps.size != n_gae:
                raise MalformedStream(
                    f"{binexps.size} bin exponents for {n_gae} GAE blocks")
            total = int(sum(s.size for s in index_sets))
            have = (chunk.gae_coeff_stream.count
                    if chunk.gae_coeff_stream is not None else 0)
            if have != total:
                raise MalformedStream(
                    f"coefficient stream has {have} values, index sets "
                    f"declare {total}")
            # the stripe's values were counted on its first entry
            with exec_mod.stage("huffman_decode", 0):
                coeffs = (entropy.huffman_decompress(chunk.gae_coeff_stream)
                          if chunk.gae_coeff_stream is not None
                          else np.zeros(0, np.int64))
            pos = 0
            for i, idx in enumerate(index_sets):
                codes.append(gae.GAEBlockCode(
                    m=idx.size, indices=idx, qcoeffs=coeffs[pos:pos + idx.size],
                    bin_exp=int(binexps[i])))
                pos += idx.size
        return q_lh, q_lbs, codes

    def decompress(self, archive: Archive, strict: bool = True, mesh=None
                   ) -> Union[np.ndarray, tuple[np.ndarray, DamageReport]]:
        """Decode an archive back to hyper-blocks.

        ``strict=True`` (default) raises a typed ``ArchiveError`` on the first
        damaged or inconsistent chunk.  ``strict=False`` returns
        ``(reconstruction, DamageReport)``: damaged stripes decode from zeroed
        latents with no GAE correction (and no guarantee), every other stripe
        is digest-verified and still satisfies the per-block bound.

        The AE back-end and the GAE correction run stripe by stripe, on the
        shapes ``compress`` ran (``_ae_decode``), so the decoded floats are
        the ones the encoder verified against tau.  ``mesh`` (anything
        ``parallel.mesh_exec.resolve_mesh`` accepts) runs the back-end as
        ``compress`` with the same mesh does: aligned groups of stripes, one
        per shard.  Entropy decode and GAE correction are host-side and
        chunk-parallel.
        """
        cfg = self.cfg
        n, k, d = archive.n_hyperblocks, cfg.k, cfg.block_elems
        report = DamageReport(n_hyperblocks=n, n_chunks=len(archive.chunks))
        if archive.gae_dim and self.basis is None:
            raise MalformedStream("archive has a GAE section but this "
                                  "compressor has no fitted basis")
        if archive.gae_dim and self.basis.shape[0] != archive.gae_dim:
            raise MalformedStream(
                f"archive GAE dimension {archive.gae_dim} != basis "
                f"dimension {self.basis.shape[0]}")
        if archive.n_values != n * k * d:
            raise MalformedStream(
                f"archive declares {archive.n_values} values for "
                f"{n}x{k}x{d} hyper-blocks")

        q_lh = np.zeros((n, cfg.hb_latent), np.int64)
        q_lbs = [np.zeros((n * k, cfg.bae_latent), np.int64)
                 for _ in self.bae_params]
        spans: list[tuple[int, int]] = []     # the stripes compress coded
        gae_stripes: list[tuple[int, int, list[gae.GAEBlockCode]]] = []
        verbatim_spans: list[tuple[int, int, np.ndarray]] = []

        # Chunks are independently decodable (docs/ARCHIVE_FORMAT.md), so the
        # entropy fan-out runs on the shared pool; per-chunk errors are
        # captured and re-raised in chunk order to keep strict-mode behavior
        # deterministic and identical to the old serial loop.
        def decode_one(chunk: Optional[ArchiveChunk]):
            if chunk is None:
                return None
            try:
                return self._decode_chunk(chunk, archive)
            except ArchiveError as e:
                return e

        with exec_mod.stage("entropy_decode", archive.n_values):
            decoded = exec_mod.map_parallel(decode_one, archive.chunks)

        covered = 0
        for ci, (chunk, result) in enumerate(zip(archive.chunks, decoded)):
            if chunk is None:
                start = covered
                n_hb = min(archive.chunk_hyperblocks, n - start)
                covered += n_hb
                spans.append((start, n_hb))
                err = archive.chunk_errors.get(ci, "chunk unreadable")
                if strict:
                    raise MalformedStream(f"chunk {ci} damaged: {err}")
                report.damaged.append(ChunkDamage(
                    chunk=ci, hb_start=start, n_hyperblocks=n_hb,
                    section="chunk", error=err))
                continue
            if chunk.hb_start != covered:
                raise MalformedStream(
                    f"chunk {ci} starts at hyper-block {chunk.hb_start}, "
                    f"expected {covered}")
            covered += chunk.n_hyperblocks
            spans.append((chunk.hb_start, chunk.n_hyperblocks))
            if isinstance(result, ArchiveError):
                if strict:
                    raise result
                report.damaged.append(ChunkDamage(
                    chunk=ci, hb_start=chunk.hb_start,
                    n_hyperblocks=chunk.n_hyperblocks, section="decode",
                    error=repr(result)))
                continue
            if isinstance(result, _VerbatimStripe):
                # quarantined stripe: raw values land after the AE backend
                # runs (its latent rows stay zero; no GAE codes exist here)
                verbatim_spans.append((chunk.hb_start,
                                       chunk.hb_start + chunk.n_hyperblocks,
                                       result.data))
                continue
            c_lh, c_lbs, c_codes = result
            s, e = chunk.hb_start, chunk.hb_start + chunk.n_hyperblocks
            q_lh[s:e] = c_lh
            for stage_i, c_lb in enumerate(c_lbs):
                q_lbs[stage_i][s * k:e * k] = c_lb
            if c_codes:
                gae_stripes.append((s, e, c_codes))
        if covered != n:
            raise MalformedStream(
                f"chunks cover {covered} hyper-blocks, archive declares {n}")

        resolved_mesh = None
        if mesh is not None:
            from repro.parallel import mesh_exec
            resolved_mesh = mesh_exec.resolve_mesh(mesh)
        with exec_mod.stage("ae_decode", archive.n_values):
            recon = self._ae_decode(q_lh, q_lbs, spans, resolved_mesh)

        # GAE correction stripe by stripe: the encoder verified each stripe
        # with this same arithmetic on this same block batch
        def correct(item) -> None:
            s, e, codes = item
            r_gae = self._gae_view(recon[s:e])          # a view into recon
            r_gae[:] = gae.gae_decode_blocks(r_gae, self.basis, codes,
                                             cfg.gae_bin)

        if gae_stripes:
            with exec_mod.stage("gae_decode", archive.n_values):
                exec_mod.map_parallel(correct, gae_stripes)
        for s, e, data in verbatim_spans:
            recon[s:e] = data
        if strict:
            return recon
        return recon, report

    def _ae_decode(self, q_lh: np.ndarray, q_lbs: list[np.ndarray],
                   spans: list[tuple[int, int]], mesh) -> np.ndarray:
        """Fused dequantize+decode back-end over the archive's own stripe
        tiling.  Each stripe runs the program ``compress`` ran on it, at the
        same shape (with a mesh: one stripe per shard in the aligned groups
        ``compress`` forms, the ragged tail per stripe), so the
        reconstruction is bit-identical to the one the GAE encoder verified.
        A program compiled for another batch shape may round differently —
        on a TPU, whose default-precision matmuls take bf16 passes, enough to
        push blocks past tau."""
        cfg = self.cfg
        k = cfg.k
        runs = [(s, s + w, None) for s, w in spans]
        if mesh is not None:
            from repro.parallel import mesh_exec
            groups, tail = mesh_exec.plan_shard_groups(
                spans, mesh_exec.mesh_shards(mesh))
            runs = ([(*mesh_exec.group_slice(g), mesh) for g in groups]
                    + [(s, s + w, None) for s, w in tail])
        handles = [exec_mod.run_decompress_stage_async(
            self.hbae_params, self.bae_params, q_lh[a:b],
            [q[a * k:b * k] for q in q_lbs], cfg.hb_bin, cfg.bae_bin, mesh=m)
            for a, b, m in runs]
        recon = np.empty((q_lh.shape[0], k, cfg.block_elems), np.float32)
        for (a, b, _), part in zip(runs, exec_mod.to_host(handles)):
            recon[a:b] = part
        return recon

    # -- persistence ---------------------------------------------------------
    # Manifest + npz layout (no pickle anywhere on the read path): a single
    # .npz holding one array per tensor plus a JSON manifest (uint8 array)
    # with per-tensor sha256 digests — the same integrity posture as
    # ``runtime.checkpoint.CheckpointManager``, whose hashing and atomic-write
    # machinery this reuses.
    def save(self, path: str) -> None:
        from repro.runtime.archive_io import atomic_write_bytes
        from repro.runtime.checkpoint import _sha

        leaves: list[tuple[str, np.ndarray]] = []
        statics: dict[str, dict] = {}
        _flatten_params({"hbae": jax.device_get(self.hbae_params),
                         "bae": jax.device_get(self.bae_params)},
                        "", leaves, statics)
        if self.basis is not None:
            leaves.append(("basis", np.asarray(self.basis)))
        manifest = {"format": MODEL_FORMAT,
                    "cfg": dataclasses.asdict(self.cfg),
                    "n_bae_stages": len(self.bae_params),
                    "has_basis": self.basis is not None,
                    "statics": statics, "tensors": []}
        arrays: dict[str, np.ndarray] = {}
        for i, (tpath, arr) in enumerate(leaves):
            arrays[f"t{i}"] = arr
            manifest["tensors"].append(
                {"key": f"t{i}", "path": tpath, "shape": list(arr.shape),
                 "dtype": str(arr.dtype), "sha256": _sha(arr)})
        arrays["__manifest__"] = np.frombuffer(
            json.dumps(manifest, sort_keys=True).encode(), np.uint8)
        import io
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        atomic_write_bytes(path, buf.getvalue())

    @classmethod
    def load(cls, path: str) -> "HierarchicalCompressor":
        from repro.runtime.checkpoint import _sha
        try:
            data = np.load(path, allow_pickle=False)
        except Exception as e:
            raise MalformedStream(f"unreadable model file {path!r}: {e}") from e
        if "__manifest__" not in data:
            raise MalformedStream(f"{path!r} has no manifest (legacy pickle "
                                  "models are not supported on the read path)")
        try:
            manifest = json.loads(bytes(data["__manifest__"]).decode())
        except (UnicodeDecodeError, json.JSONDecodeError) as e:
            raise MalformedStream(f"corrupt model manifest: {e}") from e
        if manifest.get("format") != MODEL_FORMAT:
            raise MalformedStream(
                f"unsupported model format {manifest.get('format')!r}")
        entries: list[tuple[str, np.ndarray]] = []
        for t in manifest["tensors"]:
            if t["key"] not in data:
                raise MalformedStream(f"model tensor {t['path']} missing")
            arr = data[t["key"]]
            if _sha(arr) != t["sha256"]:
                raise ChecksumMismatch(f"model tensor {t['path']} hash mismatch")
            entries.append((t["path"], arr))
        tree = _assemble_params(entries, manifest.get("statics", {}))
        obj = cls(CompressorConfig(**manifest["cfg"]))
        obj.hbae_params = tree.get("hbae")
        bae = tree.get("bae", {})
        obj.bae_params = [bae[str(i)] for i in range(manifest["n_bae_stages"])]
        obj.basis = tree.get("basis") if manifest["has_basis"] else None
        return obj

    def model_bytes(self) -> int:
        """Storage cost of the decoder-side model (params + PCA basis), using
        each leaf's ACTUAL dtype width — a float16 or float64 leaf is no
        longer mis-billed at 4 bytes/element."""
        total = sum(x.size * np.dtype(x.dtype).itemsize
                    for x in jax.tree.leaves((self.hbae_params,
                                              self.bae_params)))
        if self.basis is not None:
            total += self.basis.size * np.dtype(self.basis.dtype).itemsize
        return total
