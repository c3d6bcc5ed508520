"""Milliseconds per tick of host work in the training plane: the self
time of the program's ``train.*`` spans (join drain, dedup, pull,
forward, gradient aggregation), the spans nested in them (``ps.*``,
``device.wait``) taken out. Needs a window traced with the program's
spans (``harness/spans.py``)."""

from harness import spans


def read(ctx):
    return spans.per_tick_ms(ctx, prefixes=("train.",))
