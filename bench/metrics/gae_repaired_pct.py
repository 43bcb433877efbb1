"""Share of the window's GAE blocks that the encoder's repair rounds touched
(the program's ``gae.repaired_blocks`` over ``gae.blocks`` counters)."""


def read(ctx):
    from repro.core import exec as exec_mod
    counts = exec_mod.counters()
    blocks = counts.get("gae.blocks")
    if not blocks:
        return None
    return 100.0 * counts.get("gae.repaired_blocks", 0.0) / blocks
