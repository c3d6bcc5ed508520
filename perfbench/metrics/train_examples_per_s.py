"""Joined examples trained in the window over the window's seconds."""


def read(ctx):
    if not ctx.train or ctx.window_s <= 0:
        return None
    return ctx.stats["examples"] / ctx.window_s
