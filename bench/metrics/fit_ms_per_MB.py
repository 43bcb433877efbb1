"""Wall milliseconds of the set-up's model fit
(``HierarchicalCompressor.fit``, the ``fit`` part the mode times) per MB of
the float32 field it fits."""


def read(ctx):
    seconds = ctx.setup_parts.get("fit")
    hb = getattr(ctx, "hb", None)
    if seconds is None or hb is None or not hb.nbytes:
        return None
    return seconds * 1e3 / (hb.nbytes / 1e6)
