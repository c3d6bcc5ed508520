"""Set-up seconds: building the cluster, filling its tables, warming
every shape of the cell's traffic (compiles included)."""


def read(ctx):
    return ctx.setup_s
