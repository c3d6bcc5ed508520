"""Milliseconds per tick in the serving replicas' polls (consume,
decode, apply, cache invalidation), summed over replicas, a harness
span."""


def read(ctx):
    t = ctx.spans.get("apply")
    if not t:
        return None
    ticks = ctx.stats.get("ticks") or 0
    if not ticks:
        return None
    return sum(b - a for a, b in t) / ticks * 1e3
