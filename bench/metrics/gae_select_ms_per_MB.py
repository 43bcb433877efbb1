"""Host-clock ms in the ``gae_select`` stage per MB of float32 values: the
upload of the residual and basis, the selection, and the fetch of the
codes.  Summed over the codec pool's threads."""
from bench.metrics import stage_ms_per_MB


def read(ctx):
    return stage_ms_per_MB(ctx, "gae_select")
