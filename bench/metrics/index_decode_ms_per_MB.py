"""Host-clock ms in the ``index_decode`` stage per MB of float32 values: the
index sets and bin exponents of a chunk.  Summed over the codec pool's
threads."""
from bench.metrics import stage_ms_per_MB


def read(ctx):
    return stage_ms_per_MB(ctx, "index_decode")
