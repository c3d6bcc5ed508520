"""Mean milliseconds per tick in the push half of ``sync_tick``
(collect, gather, encode, produce), a harness span."""


def read(ctx):
    t = ctx.spans.get("push")
    if not t:
        return None
    return sum(b - a for a, b in t) / len(t) * 1e3
