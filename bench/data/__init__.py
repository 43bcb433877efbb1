"""Device generators of the paper's datasets, one module per dataset; each
has ``hyperblocks(config, seed) -> np.ndarray`` of shape (N, k, D)."""
from __future__ import annotations

import importlib


def hyperblocks(config: dict, seed: int):
    mod = importlib.import_module(f"bench.data.{config['dataset']}")
    return mod.hyperblocks(config, seed)
