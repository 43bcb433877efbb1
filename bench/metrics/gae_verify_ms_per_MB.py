"""Host-clock ms in the ``gae_verify`` stage per MB of float32 values: the
code extraction, verification with the decoder's arithmetic, and repair.
Summed over the codec pool's threads."""
from bench.metrics import stage_ms_per_MB


def read(ctx):
    return stage_ms_per_MB(ctx, "gae_verify")
