"""Milliseconds per tick of the training plane's pooled lookup: the self
time of the program's ``train.pool`` span (the unique rows and the
inverse copied up, the pooled lookup dispatched). Needs a window traced
with the program's spans (``harness/spans.py``)."""

from harness import spans


def read(ctx):
    return spans.per_tick_ms(ctx, names=("train.pool",))
