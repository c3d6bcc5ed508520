"""Share (%) of the traced window in which no operation ran on the
device: 1 - union of the ``XLA Ops`` intervals over the window."""


def read(ctx):
    r = ctx.trace
    if r is None or r.window_s <= 0 or not r.devices:
        return None
    return 100.0 * (1.0 - r.busy_s / r.window_s)
