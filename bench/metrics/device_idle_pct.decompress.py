"""Share of the traced decompress window in which the device ran no operation."""
from bench.metrics import device_idle_pct


def read(ctx):
    return device_idle_pct(ctx)
