"""Milliseconds per tick of host work on the replica side of the sync
plane: the self time of the program's ``sync.apply``, ``sync.decode``
and ``cache.invalidate`` spans, summed over replicas. Needs a window
traced with the program's spans (``harness/spans.py``)."""

from harness import spans


def read(ctx):
    return spans.per_tick_ms(ctx, names=("sync.apply", "sync.decode",
                                         "cache.invalidate"))
