"""Host-clock ms in the ``huffman_encode`` stage per MB of float32 values: the
every Huffman stream of a stripe.  Summed over the codec pool's threads."""
from bench.metrics import stage_ms_per_MB


def read(ctx):
    return stage_ms_per_MB(ctx, "huffman_encode")
