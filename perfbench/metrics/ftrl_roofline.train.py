"""Share (%) of the fused FTRL chain's roofline: the least time the
window's updates need (``counts.ftrl`` over every train batch's unique
ids per group, bytes-bound) over the device time of the FTRL programs
(``jit__ftrl_program``) in the trace."""

PROGRAM = r"^jit__ftrl_program$"


def read(ctx):
    r, pk = ctx.trace, ctx.peaks
    if r is None or pk is None or not ctx.unique_per_batch:
        return None
    t = r.module_ns(PROGRAM) * 1e-9
    if t <= 0:
        return None
    c = ctx.counts
    load = c.map_load(ctx.cfg["sizing"]["ids_per_master"])
    ops = nbytes = 0.0
    for u in ctx.unique_per_batch:
        for g, dim in ctx.cfg["groups"].items():
            o, b = c.ftrl(u[g], dim, load)
            ops += o
            nbytes += b
    return 100.0 * c.least_time(ops, nbytes, pk)[0] / t
