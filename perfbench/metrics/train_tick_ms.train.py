"""Mean milliseconds per tick in ``train_scheduler.tick``: the join's
drain, the train batches' pull, loss and gradients, and the masters'
fused FTRL updates, a harness span."""


def read(ctx):
    t = ctx.spans.get("train_tick")
    if not t:
        return None
    return sum(b - a for a, b in t) / len(t) * 1e3
