"""Share (%) of the chip's peak that the window's training work needs:
the least time of its forward and backward operations
(``counts.ctr_flops``) and of the bytes its PS pulls and updates move
(``counts.train_bytes``, per batch and group over unique ids), the
larger of the two (bytes for these models), over the window."""


def read(ctx):
    pk = ctx.peaks
    if pk is None or not ctx.unique_per_batch or ctx.window_s <= 0:
        return None
    c, cfg = ctx.counts, ctx.cfg
    k = cfg["groups"].get("v", 1)
    ops = c.ctr_flops(ctx.stats["examples"], len(cfg["field_vocab"]), k,
                      cfg["model_type"])
    nbytes = sum(c.train_bytes(u[g], dim) for u in ctx.unique_per_batch
                 for g, dim in cfg["groups"].items())
    return 100.0 * c.least_time(ops, nbytes, pk)[0] / ctx.window_s
