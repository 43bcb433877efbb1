"""Compile the compressor's programs and kernels for a described TPU v5e.

Nothing runs here: each test lowers a program for one chip of a ``v5e:2x2``
topology that is described, not attached, and compiles it with the TPU
compiler, which refuses what the chip would refuse (kernels Mosaic cannot
lower, VMEM or HBM overflows).  The topology is described inside a
module-scoped fixture, never while a module is imported, so every pytest
worker collects the same tests and only the worker that runs this file
loads the TPU library.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs import get_compressor_config
from repro.core import bae as bae_mod
from repro.core import exec as exec_mod
from repro.core import gae
from repro.core import hbae as hbae_mod

#: one v5e chip's HBM (16 GB, Google Cloud documentation "TPU v5e")
V5E_HBM_BYTES = 16 * 10**9
#: hyper-blocks per stripe: the compression CLI's default chunk width
STRIPE = 64
#: E3SM at the paper's size: 720x240x1440 values as (6,16,16) blocks, k=5
E3SM_HYPERBLOCKS = 32400
#: hyper-blocks of each dataset at the paper's size: S3D 58x50x640x640 as
#: (58,5,4,4) blocks, k=10; XGC 8 planes of 16395 nodes, k=8
PAPER_HYPERBLOCKS = {"s3d": 25600, "e3sm": E3SM_HYPERBLOCKS, "xgc": 16395}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from repro.core.options import MESH_AXIS
    return Mesh(np.array(topo.devices[:4]), (MESH_AXIS,))


def _spec(chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile(fn, *args):
    """Compile ``fn`` for the described chip; assert it fits one chip's
    HBM and return the compiled program."""
    compiled = jax.jit(fn).lower(*args).compile()
    _assert_fits(compiled)
    return compiled


def _assert_fits(compiled) -> None:
    mem = compiled.memory_analysis()            # per device
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < V5E_HBM_BYTES, used


def _params(chip, dataset: str):
    """Shapes of the full-width HBAE and BAE parameters of ``dataset``."""
    cfg = get_compressor_config(dataset)
    hb = jax.eval_shape(lambda: hbae_mod.hbae_init(
        jax.random.PRNGKey(0), in_dim=cfg.block_elems, k=cfg.k, emb=cfg.emb,
        hidden=cfg.hidden, latent=cfg.hb_latent, heads=cfg.heads))
    bae = jax.eval_shape(lambda: bae_mod.bae_init(
        jax.random.PRNGKey(0), in_dim=cfg.block_elems, hidden=cfg.bae_hidden,
        latent=cfg.bae_latent))
    place = lambda tree: jax.tree.map(      # noqa: E731
        lambda s: _spec(chip, s.shape, s.dtype), tree)
    return cfg, place(hb), [place(bae)]


def _stage_args(chip, dataset: str, n: int) -> dict:
    cfg, hb, bae = _params(chip, dataset)
    scalar = _spec(chip, ())
    return {
        "encode_frontend": (exec_mod._encode_frontend, hb, bae,
                            _spec(chip, (n, cfg.k, cfg.block_elems)),
                            scalar, scalar),
        "decode_backend": (exec_mod._decode_backend, hb, bae,
                           _spec(chip, (n, cfg.hb_latent), jnp.int32),
                           [_spec(chip, (n * cfg.k, cfg.bae_latent),
                                  jnp.int32)], scalar, scalar),
    }


@pytest.mark.parametrize("program", ["encode_frontend", "decode_backend"])
@pytest.mark.parametrize("dataset", ["s3d", "e3sm", "xgc"])
def test_stage_program_compiles_at_stripe_size(one_chip, dataset, program):
    fn, *args = _stage_args(one_chip, dataset, STRIPE)[program]
    _compile(fn, *args)


def _fit_args(chip, dataset: str) -> dict:
    """The programs ``fit`` and ``fit_basis`` run on the paper-size field:
    the training steps, each gathering its batch from the whole field (or
    the whole BAE residual) on the device, and the per-stripe forward,
    covariance (the ``eigh`` of the sum holds D_gae^2 floats)."""
    from repro.core import training
    from repro.train import optim as optim_mod

    cfg, hb, bae = _params(chip, dataset)
    n = PAPER_HYPERBLOCKS[dataset]
    k, d = cfg.k, cfg.block_elems
    opt = optim_mod.adam(lr=cfg.lr)
    state = lambda p: jax.tree.map(                 # noqa: E731
        lambda s: _spec(chip, s.shape, s.dtype), jax.eval_shape(opt.init, p))
    d_gae = cfg.gae_block_elems
    idx = lambda b: _spec(chip, (b,), jnp.int32)    # noqa: E731
    # the field as the training loops hold it: a row per block, padded to
    # whole 128-lane tiles
    rows = _spec(chip, (n * k, d + -d % training.LANES))
    return {
        "hbae_step": (
            lambda p, s, data, i: training._hbae_step(
                p, s, training._batch(data, i, (k, d)), opt),
            hb, state(hb), rows, idx(cfg.batch)),
        "bae_step": (
            lambda p, s, data, i: training._bae_step(
                p, s, training._batch(data, i, (d,)), opt),
            bae[0], state(bae[0]), rows, idx(max(4 * cfg.batch, 256))),
        "hbae_apply": (hbae_mod.hbae_apply, hb,
                       _spec(chip, (STRIPE, cfg.k, cfg.block_elems))),
        "residual_covariance": (
            exec_mod._add_residual_covariance, _spec(chip, (d_gae, d_gae)),
            _spec(chip, (STRIPE, cfg.k, cfg.block_elems)),
            _spec(chip, (STRIPE, cfg.k, cfg.block_elems))),
    }


@pytest.mark.parametrize("program", ["hbae_step", "bae_step", "hbae_apply",
                                     "residual_covariance"])
@pytest.mark.parametrize("dataset", ["s3d", "e3sm", "xgc"])
def test_paper_size_fit_program_fits_one_chip(one_chip, dataset, program):
    # fit and fit_basis run stripe by stripe; only the training steps see
    # the whole field, which stays on the device for their gathers
    fn, *args = _fit_args(one_chip, dataset)[program]
    _compile(fn, *args)


def _gae_stripe(dataset: str) -> tuple[int, int]:
    cfg = get_compressor_config(dataset)
    d = cfg.gae_block_elems
    return STRIPE * cfg.k * cfg.block_elems // d, d


@pytest.mark.parametrize("dataset", ["s3d", "e3sm", "xgc"])   # D=80/256/1521
def test_gae_select_compiles(one_chip, dataset):
    """The selection fits one chip and moves its coefficients through its
    sort: no gather, which the TPU lowers as one per element (a ``kCustom``
    fusion fed by bit-packed indices)."""
    n, d = _gae_stripe(dataset)
    scalar = _spec(one_chip, ())
    text = _compile(gae.gae_select, _spec(one_chip, (n, d)),
                    _spec(one_chip, (d, d)), scalar, scalar).as_text()
    assert "gather(" not in text
    assert "kind=kCustom" not in text


@pytest.mark.parametrize("dataset", ["s3d", "e3sm", "xgc"])   # D=80/256/1521
def test_gae_project_kernel_compiles(one_chip, dataset):
    from repro.kernels.gae_project.ops import gae_project
    n, d = _gae_stripe(dataset)
    compiled = _compile(lambda r, u: gae_project(r, u, interpret=False),
                        _spec(one_chip, (n, d)), _spec(one_chip, (d, d)))
    assert "tpu_custom_call" in compiled.as_text()


def test_quantize_kernel_compiles(one_chip):
    from repro.kernels.quantize.ops import quantize_fused
    n, d = _gae_stripe("e3sm")
    compiled = _compile(lambda x: quantize_fused(x, 0.02, interpret=False),
                        _spec(one_chip, (n, d)))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("b,n,d,heads", [
    (STRIPE, 5, 128, 1),            # E3SM HBAE: k=5 embeddings of 128
    (STRIPE, 10, 128, 1),           # S3D HBAE: k=10
    (E3SM_HYPERBLOCKS, 5, 128, 1),  # a whole field's embeddings at once
    (STRIPE, 10, 128, 2),           # heads folded into the batch axis
])
def test_block_attention_kernel_compiles(one_chip, b, n, d, heads):
    from repro.kernels.block_attention.ops import block_attention
    qkv = [_spec(one_chip, (b, n, d)) for _ in range(3)]
    compiled = _compile(
        lambda q, k, v: block_attention(q, k, v, heads=heads,
                                        interpret=False), *qkv)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("program", ["encode_frontend", "decode_backend"])
def test_sharded_stage_program_compiles_for_four_chips(mesh4, monkeypatch,
                                                       program):
    # CompressOptions(mesh=4) and decompress(mesh=4) stack 4 stripes, one
    # per shard
    monkeypatch.setattr(exec_mod, "_CACHE", exec_mod.JitCache())
    repl, shard = NamedSharding(mesh4, P()), NamedSharding(mesh4, P("hb"))
    cfg, hb, bae = _params(repl, "e3sm")
    scalar = _spec(repl, ())
    n = 4 * STRIPE
    if program == "encode_frontend":
        fn = exec_mod._sharded_program(
            program, exec_mod._encode_frontend, mesh4,
            (P(), P(), P("hb"), P(), P()), (P("hb"), P("hb")))
        args = (hb, bae, _spec(shard, (n, cfg.k, cfg.block_elems)),
                scalar, scalar)
    else:
        fn = exec_mod._sharded_program(
            program, exec_mod._decode_backend, mesh4,
            (P(), P(), P("hb"), P("hb"), P(), P()), P("hb"))
        args = (hb, bae, _spec(shard, (n, cfg.hb_latent), jnp.int32),
                [_spec(shard, (n * cfg.k, cfg.bae_latent), jnp.int32)],
                scalar, scalar)
    _assert_fits(fn.lower(*args).compile())
