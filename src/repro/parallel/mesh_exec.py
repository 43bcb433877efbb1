"""Mesh construction + stripe-to-shard span alignment for the sharded
compress pipeline.

The paper's hyper-block design makes archive chunks independently codable,
which is exactly the property that lets the fused device programs in
``core/exec.py`` scale past one device: the hyper-block axis is a pure data
axis.  This module owns the three mesh-level concerns:

* **Mesh construction** (``resolve_mesh`` / ``make_compress_mesh``): a 1-D
  ``jax.sharding.Mesh`` over the hyper-block data axis ``MESH_AXIS`` —
  ``"hb"`` — reusing the naming conventions of ``parallel/sharding.py``
  (named axes, ``PartitionSpec`` replication for parameters).
* **Stripe-to-shard span alignment** (``plan_shard_groups``): the stripe IS
  the archive chunk, so alignment is a span-planning problem, not a format
  change.  Consecutive equal-width stripes are grouped ``n_shards`` at a
  time; each group is stacked into ONE ``shard_map`` call where every shard
  processes EXACTLY one stripe.  Per-shard block shapes therefore equal the
  single-device per-stripe shapes, which is what makes the sharded archive
  byte-identical to the single-device archive (bit-equal floats, not
  floating-point luck).  Ragged tails — the last short stripe, or a final
  group with fewer than ``n_shards`` stripes — fall back to the per-stripe
  single-device path.
* **Host-local entropy fan-out**: because shard boundaries coincide with
  stripe boundaries, every chunk's GAE + entropy coding consumes only rows
  its own shard produced — nothing ever crosses a shard boundary on the
  host side.

The PCA basis fit runs over the same groups
(``HierarchicalCompressor.residual_covariance``): each shard adds its
stripe's D x D residual covariance, one ``psum`` per group makes it global,
and the groups' sums are added up (``replicated`` places the running sum).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.errors import ConfigError
from repro.core.options import MESH_AXIS

Span = tuple  # (hb_start, n_hyperblocks)


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------

def available_devices() -> int:
    return len(jax.devices())


def make_compress_mesh(n_shards: Optional[int] = None) -> Mesh:
    """1-D compress mesh over the hyper-block data axis.

    ``n_shards=None`` takes every addressable device.  Requesting more shards
    than devices is a :class:`ConfigError` — the same condition would
    otherwise surface as an opaque ``jax.make_mesh`` failure mid-run.
    """
    have = available_devices()
    want = have if n_shards is None else int(n_shards)
    if want < 1:
        raise ConfigError(f"compress mesh needs >= 1 shard, got {want}")
    if want > have:
        raise ConfigError(
            f"compress mesh wants {want} shards but only {have} device(s) "
            f"are addressable (XLA_FLAGS=--xla_force_host_platform_"
            f"device_count=N forces N virtual CPU devices)")
    return jax.make_mesh((want,), (MESH_AXIS,))


def resolve_mesh(spec) -> Optional[Mesh]:
    """Resolve a ``CompressOptions.mesh`` field to a concrete ``Mesh``.

    ``None`` and meshes/counts of size 1 resolve to ``None`` (single-device
    execution: the sharded path would add wrapper overhead for nothing and
    the unsharded path is the byte-identity reference).
    """
    if spec is None:
        return None
    if isinstance(spec, int):
        if spec <= 1:
            return None
        return make_compress_mesh(spec)
    if not isinstance(spec, Mesh):
        raise ConfigError(f"cannot resolve a {type(spec).__name__} into a "
                          f"compress mesh")
    if MESH_AXIS not in spec.axis_names:
        raise ConfigError(f"compress mesh is missing the {MESH_AXIS!r} axis "
                          f"(axes: {tuple(spec.axis_names)})")
    return spec if spec.shape[MESH_AXIS] > 1 else None


def mesh_shards(mesh: Optional[Mesh]) -> int:
    return 1 if mesh is None else int(mesh.shape[MESH_AXIS])


# ---------------------------------------------------------------------------
# stripe-to-shard span alignment
# ---------------------------------------------------------------------------

def plan_shard_groups(spans: Sequence[Span], n_shards: int
                      ) -> tuple[list[list[Span]], list[Span]]:
    """Align the stripe tiling to shard boundaries.

    Returns ``(groups, tail)``: ``groups`` is a list of span groups, each
    exactly ``n_shards`` consecutive spans of EQUAL width (one stripe per
    shard — the alignment invariant the byte-identity guarantee rests on);
    ``tail`` is every remaining span (ragged width or an incomplete final
    group), to be run through the per-stripe single-device path.

    The function is a pure reindexing of the pipeline's existing
    ``stripe_spans`` tiling: it never changes chunk boundaries, so archives
    produced with and without a mesh have identical section tables.
    """
    if n_shards < 1:
        raise ConfigError(f"plan_shard_groups needs n_shards >= 1, "
                          f"got {n_shards}")
    spans = list(spans)
    if n_shards == 1:
        return [], spans
    groups: list[list[Span]] = []
    tail: list[Span] = []
    i = 0
    while i + n_shards <= len(spans):
        cand = spans[i:i + n_shards]
        widths = {int(w) for _, w in cand}
        if len(widths) == 1:
            groups.append(cand)
            i += n_shards
        else:
            break
    tail.extend(spans[i:])
    return groups, tail


def group_slice(group: Sequence[Span]) -> tuple[int, int]:
    """A shard group covers one CONTIGUOUS hyper-block range (spans are
    consecutive by construction): returns ``(start, stop)``."""
    start = int(group[0][0])
    stop = int(group[-1][0] + group[-1][1])
    return start, stop


def replicated(mesh: Mesh):
    """The sharding of an array held whole on every device of ``mesh``."""
    return NamedSharding(mesh, P())
