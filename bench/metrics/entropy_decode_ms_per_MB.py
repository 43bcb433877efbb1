"""Host-clock ms in the ``entropy_decode`` stage per MB of float32 values."""
from bench.metrics import stage_ms_per_MB


def read(ctx):
    return stage_ms_per_MB(ctx, "entropy_decode")
