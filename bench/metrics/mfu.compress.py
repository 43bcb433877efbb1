"""Model FLOPs of compress per second over the chips' bf16 peak."""
from bench.metrics import mfu_pct


def read(ctx):
    return mfu_pct(ctx)
