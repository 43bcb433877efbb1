"""Share of the window's GAE blocks that keep at least one coefficient."""


def read(ctx):
    share = ctx.facts.get("gae_coded_share")
    return None if share is None else 100.0 * share
