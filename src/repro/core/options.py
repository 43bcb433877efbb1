"""The one compress configuration object: ``CompressOptions``.

``HierarchicalCompressor.compress``, ``stream_compress`` and
``launch/compress.py`` (which builds one from its argv flags) all take a
single frozen ``CompressOptions``; there is no other way to configure a
compress.

Validation happens at CONSTRUCTION time and raises a typed
:class:`~repro.core.errors.ConfigError` — a zero-width chunk or a mesh
without the hyper-block axis fails here, in one obvious place, instead of as
a mid-run XLA shape crash deep inside a sharded trace.

The ``mesh`` field is deliberately loose about types so this module stays
import-light (no jax device initialization at option-construction time):

* ``None``  — single-device execution (the default),
* ``int``   — shard over that many devices of a 1-D ``("hb",)`` mesh built
  by ``repro.parallel.mesh_exec.resolve_mesh`` at run time,
* ``jax.sharding.Mesh`` — used as-is; must carry the hyper-block data axis
  ``repro.parallel.mesh_exec.MESH_AXIS`` (``"hb"``) and may not shard any
  other axis (the compress pipeline is data-parallel only).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

from repro.core.errors import ConfigError

#: Name of the hyper-block data axis every compress mesh must carry.  Lives
#: here (not in ``parallel.mesh_exec``) so option validation never imports
#: jax; ``mesh_exec`` re-exports it.
MESH_AXIS = "hb"


@dataclasses.dataclass(frozen=True)
class CompressOptions:
    """One frozen configuration object for a compress run (batch or stream).

    Fields:

    * ``tau`` — per-GAE-block l2 error bound; ``None`` disables the GAE
      guarantee stage entirely.
    * ``chunk_hyperblocks`` — requested stripe width (hyper-blocks per
      independently-decodable archive chunk).  The pipeline may round it UP
      for GAE block alignment; it is never silently clamped up from zero —
      a non-positive width is a :class:`ConfigError` here.
    * ``queue_depth`` — streaming inter-stage queue bound (backpressure).
    * ``retries`` — per-item transient-failure retries (enables the
      fault-tolerance ladder + quarantine fallback when set).
    * ``stage_deadline_s`` — per-attempt watchdog deadline on the streaming
      compute stages (implies fault tolerance).
    * ``chaos_seed`` — seeded live fault injection (implies fault tolerance).
    * ``mesh`` — device mesh for the sharded stage pipeline (see module
      docstring for the accepted forms).
    """
    tau: Optional[float] = None
    chunk_hyperblocks: int = 64
    queue_depth: int = 2
    retries: Optional[int] = None
    stage_deadline_s: Optional[float] = None
    chaos_seed: Optional[int] = None
    mesh: Optional[object] = None     # None | int | jax.sharding.Mesh

    def __post_init__(self):
        if not isinstance(self.chunk_hyperblocks, int) \
                or isinstance(self.chunk_hyperblocks, bool):
            raise ConfigError(
                f"chunk_hyperblocks must be an int, got "
                f"{type(self.chunk_hyperblocks).__name__}")
        if self.chunk_hyperblocks < 1:
            raise ConfigError(
                f"chunk_hyperblocks must be >= 1, got "
                f"{self.chunk_hyperblocks} (a zero-width stripe can never "
                f"tile the hyper-block axis)")
        if self.tau is not None and not self.tau > 0:
            raise ConfigError(f"tau must be > 0 (or None to disable the "
                              f"guarantee stage), got {self.tau}")
        if self.queue_depth < 1:
            raise ConfigError(f"queue_depth must be >= 1, got "
                              f"{self.queue_depth}")
        if self.retries is not None and self.retries < 0:
            raise ConfigError(f"retries must be >= 0, got {self.retries}")
        if self.stage_deadline_s is not None and not self.stage_deadline_s > 0:
            raise ConfigError(f"stage_deadline_s must be > 0, got "
                              f"{self.stage_deadline_s}")
        self._validate_mesh()

    def _validate_mesh(self) -> None:
        mesh = self.mesh
        if mesh is None:
            return
        if isinstance(mesh, bool):
            raise ConfigError("mesh must be None, an int shard count, or a "
                              "jax.sharding.Mesh — got a bool")
        if isinstance(mesh, int):
            if mesh < 1:
                raise ConfigError(f"mesh shard count must be >= 1, got {mesh}")
            return
        # Duck-typed Mesh check (axis_names/shape) so constructing options
        # never imports jax; a real Mesh always has both attributes.
        axis_names = getattr(mesh, "axis_names", None)
        shape = getattr(mesh, "shape", None)
        if axis_names is None or shape is None:
            raise ConfigError(
                f"mesh must be None, an int shard count, or a "
                f"jax.sharding.Mesh, got {type(mesh).__name__}")
        if MESH_AXIS not in axis_names:
            raise ConfigError(
                f"compress mesh is missing the hyper-block data axis "
                f"{MESH_AXIS!r} (axes: {tuple(axis_names)}) — the stage "
                f"pipeline shards over {MESH_AXIS!r} only")
        for name in axis_names:
            if name != MESH_AXIS and shape[name] != 1:
                raise ConfigError(
                    f"compress mesh axis {name!r} has size {shape[name]}; "
                    f"only the {MESH_AXIS!r} data axis may be sharded "
                    f"(size-1 auxiliary axes are fine)")

    # -- derived views -------------------------------------------------------
    def fault_tolerant(self) -> bool:
        """True when any fault-tolerance knob is set (retries / deadline /
        chaos) — the streaming path then arms the retry→quarantine ladder."""
        return (self.retries is not None or self.stage_deadline_s is not None
                or self.chaos_seed is not None)

    def mesh_shards(self) -> int:
        """Requested shard count WITHOUT resolving devices (0 = unsharded);
        a concrete Mesh reports its ``hb``-axis size."""
        if self.mesh is None:
            return 0
        if isinstance(self.mesh, int):
            return self.mesh
        return int(self.mesh.shape[MESH_AXIS])

    def replace(self, **changes) -> "CompressOptions":
        """Functional update (re-validates)."""
        return dataclasses.replace(self, **changes)
