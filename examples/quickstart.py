"""Quickstart: compress a scientific field with a GUARANTEED error bound.

The paper's pipeline end-to-end on an S3D-like multi-species combustion field:
  1. block + hyper-block the data at the paper's geometry,
  2. fit the attention-based hyper-block autoencoder + residual block AE,
  3. compress with a user error bound tau (PCA-GAE post-processing),
  4. decompress and VERIFY every block satisfies ||x - x^G||_2 <= tau.

Runs on CPU in a few minutes:  PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.data import synthetic
from repro.data.blocks import nrmse
from repro.core.options import CompressOptions
from repro.core.pipeline import HierarchicalCompressor

TAU = 0.5          # per-block l2 bound in the normalized domain

# 1. synthetic S3D-like data at the paper's block geometry (58 species,
#    blocks 58x5x4x4 flattened to 4640, hyper-blocks of k=10)
cfg, hyperblocks = synthetic.make_dataset("s3d", quick=True, seed=0)
print(f"data: {hyperblocks.shape[0]} hyper-blocks of "
      f"(k={hyperblocks.shape[1]}, D={hyperblocks.shape[2]})")

# 2. fit HBAE -> BAE (paper Sec. III-C schedule: Adam, lr=1e-3, MSE)
comp = HierarchicalCompressor(cfg).fit(hyperblocks, seed=0)

# 3. compress with the error-bound guarantee
archive = comp.compress(hyperblocks, options=CompressOptions(tau=TAU))
print(f"compression ratio: {archive.compression_ratio():.1f}x "
      f"({archive.compressed_bytes():,} bytes for "
      f"{hyperblocks.nbytes:,} raw)")

# 4. decompress + verify the hard guarantee per GAE block
recon = comp.decompress(archive)
d_gae = cfg.gae_block_elems or cfg.block_elems
errs = np.linalg.norm(
    hyperblocks.reshape(-1, d_gae) - recon.reshape(-1, d_gae), axis=1)
print(f"NRMSE: {nrmse(hyperblocks, recon):.2e}")
print(f"max per-block l2 error: {errs.max():.4f}  (tau = {TAU})")
assert errs.max() <= TAU * (1 + 1e-5), "error-bound guarantee violated!"
print("guarantee holds for every block ✓")
