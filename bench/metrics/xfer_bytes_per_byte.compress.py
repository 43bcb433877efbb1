"""Bytes moved between host and device (the program's ``xfer.h2d_bytes``
and ``xfer.d2h_bytes`` counters) per float32 input byte of the window."""


def read(ctx):
    from repro.core import exec as exec_mod
    counts = exec_mod.counters()
    moved = [counts[k] for k in ("xfer.h2d_bytes", "xfer.d2h_bytes")
             if k in counts]
    if not moved or not ctx.window_bytes:
        return None
    return sum(moved) / ctx.window_bytes
