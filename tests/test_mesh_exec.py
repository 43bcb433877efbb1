"""Mesh-sharded stage pipeline + CompressOptions surface.

Two layers:

* in-process tests — option validation, shard group planning, and
  single-device equivalence of ``mesh=1`` (these run on however many
  devices the test process happens to have);
* the multi-device parity gate — ``repro.parallel.mesh_check`` run as a
  SUBPROCESS, because ``--xla_force_host_platform_device_count`` is frozen
  at first jax import and pytest has long since imported jax.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core.errors import ConfigError
from repro.core.options import MESH_AXIS, CompressOptions
from repro.parallel import mesh_exec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# -- CompressOptions validation ----------------------------------------------

def test_options_defaults_are_valid():
    opts = CompressOptions()
    assert opts.tau is None
    assert opts.chunk_hyperblocks == 64
    assert not opts.fault_tolerant()
    assert opts.mesh_shards() == 0


@pytest.mark.parametrize("kw", [
    {"chunk_hyperblocks": 0},
    {"chunk_hyperblocks": -3},
    {"chunk_hyperblocks": 2.5},
    {"chunk_hyperblocks": True},
    {"tau": 0.0},
    {"tau": -1.0},
    {"queue_depth": 0},
    {"retries": -1},
    {"stage_deadline_s": 0.0},
    {"mesh": 0},
    {"mesh": -2},
    {"mesh": True},
    {"mesh": "four"},
])
def test_options_reject_bad_configs(kw):
    with pytest.raises(ConfigError):
        CompressOptions(**kw)


def test_options_reject_mesh_without_hb_axis():
    class FakeMesh:
        axis_names = ("x",)
        shape = {"x": 4}
    with pytest.raises(ConfigError, match=MESH_AXIS):
        CompressOptions(mesh=FakeMesh())


def test_options_reject_mesh_sharding_other_axes():
    class FakeMesh:
        axis_names = (MESH_AXIS, "model")
        shape = {MESH_AXIS: 2, "model": 2}
    with pytest.raises(ConfigError, match="model"):
        CompressOptions(mesh=FakeMesh())


def test_options_accept_mesh_with_aux_size1_axes():
    class FakeMesh:
        axis_names = (MESH_AXIS, "aux")
        shape = {MESH_AXIS: 4, "aux": 1}
    opts = CompressOptions(mesh=FakeMesh())
    assert opts.mesh_shards() == 4


def test_options_replace_revalidates():
    opts = CompressOptions(tau=0.5)
    assert opts.replace(tau=1.0).tau == 1.0
    with pytest.raises(ConfigError):
        opts.replace(chunk_hyperblocks=0)


def test_options_fault_tolerant_views():
    assert CompressOptions(retries=2).fault_tolerant()
    assert CompressOptions(stage_deadline_s=1.0).fault_tolerant()
    assert CompressOptions(chaos_seed=7).fault_tolerant()
    assert not CompressOptions(tau=0.5).fault_tolerant()
    assert CompressOptions(mesh=3).mesh_shards() == 3


# -- shard group planning -----------------------------------------------------

def _spans(widths):
    """Consecutive (start, width) spans — the pipeline's stripe tiling."""
    spans, start = [], 0
    for w in widths:
        spans.append((start, w))
        start += w
    return spans


def test_plan_shard_groups_all_aligned():
    groups, tail = mesh_exec.plan_shard_groups(_spans([4, 4, 4, 4]), 2)
    assert len(groups) == 2 and tail == []
    assert mesh_exec.group_slice(groups[0]) == (0, 8)
    assert mesh_exec.group_slice(groups[1]) == (8, 16)


def test_plan_shard_groups_ragged_tail():
    spans = _spans([4, 4, 4, 4, 4, 2])
    groups, tail = mesh_exec.plan_shard_groups(spans, 4)
    assert len(groups) == 1
    assert tail == spans[4:]


def test_plan_shard_groups_unequal_widths_stop_grouping():
    # widths diverge inside the second candidate group: everything from
    # there on takes the per-stripe path
    spans = _spans([4, 4, 4, 3, 4, 4])
    groups, tail = mesh_exec.plan_shard_groups(spans, 2)
    assert len(groups) == 1
    assert tail == spans[2:]


def test_plan_shard_groups_fewer_spans_than_shards():
    spans = _spans([4, 4])
    groups, tail = mesh_exec.plan_shard_groups(spans, 4)
    assert groups == [] and tail == spans


def test_resolve_mesh_trivial_specs():
    assert mesh_exec.resolve_mesh(None) is None
    assert mesh_exec.resolve_mesh(1) is None


def test_make_compress_mesh_rejects_impossible():
    with pytest.raises(ConfigError):
        mesh_exec.make_compress_mesh(0)
    with pytest.raises(ConfigError):
        mesh_exec.make_compress_mesh(10 ** 6)


# -- options surface equivalence (single device) ------------------------------

@pytest.mark.parametrize("entry", ["compress", "stream_compress"])
def test_compress_entry_points_take_only_options(comp_hb, entry):
    """``CompressOptions`` is the one configuration surface: the old
    ``tau=`` kwarg is no parameter of either entry point."""
    from repro.stream import stream_compress
    comp, hb = comp_hb
    call = {"compress": lambda **kw: comp.compress(hb, **kw),
            "stream_compress": lambda **kw: stream_compress(comp, hb, **kw)}
    with pytest.raises(TypeError, match="tau"):
        call[entry](tau=0.5)


def test_mesh1_options_byte_identical_to_unsharded(comp_hb):
    """mesh=1 resolves to no mesh at all — same programs, same bytes."""
    from repro.runtime import archive_io
    comp, hb = comp_hb
    opts = CompressOptions(tau=0.5, chunk_hyperblocks=8)
    a = comp.compress(hb, options=opts)
    b = comp.compress(hb, options=opts.replace(mesh=1))
    assert archive_io.serialize_archive(a) == archive_io.serialize_archive(b)


@pytest.fixture(scope="module")
def comp_hb():
    import jax
    from repro.core import CompressorConfig, HierarchicalCompressor
    from repro.core import bae as bae_mod
    from repro.core import hbae as hbae_mod
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
        (24, cfg.k, cfg.block_elems)).astype(np.float32)
    comp.fit_basis(hb)
    return comp, hb


# -- the multi-device parity gate (subprocess) --------------------------------

def test_mesh_check_subprocess_four_devices():
    """Full sharded-vs-single parity suite under 4 virtual devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)      # mesh_check sets its own
    env["REPRO_MESH_CHECK_DEVICES"] = "4"
    proc = subprocess.run(
        [sys.executable, "-m", "repro.parallel.mesh_check"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=600)
    assert proc.returncode == 0, \
        f"mesh_check failed:\n{proc.stdout}\n{proc.stderr}"
    report = json.loads(proc.stdout)
    assert report["ok"]
    assert report["devices"] >= 4
    names = {c["name"] for c in report["checks"]}
    assert {"batch_parity", "stream_parity", "zero_retraces_after_warmup",
            "psum_basis_consistent", "sharded_decompress"} <= names
