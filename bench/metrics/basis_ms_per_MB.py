"""Wall milliseconds of the set-up's PCA basis fit
(``HierarchicalCompressor.fit_basis``, the ``basis`` part the mode times)
per MB of the float32 field it fits."""


def read(ctx):
    seconds = ctx.setup_parts.get("basis")
    hb = getattr(ctx, "hb", None)
    if seconds is None or hb is None or not hb.nbytes:
        return None
    return seconds * 1e3 / (hb.nbytes / 1e6)
