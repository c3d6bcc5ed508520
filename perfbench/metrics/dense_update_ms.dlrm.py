"""Milliseconds per tick of the dense tower's update: the self time of the
program's ``train.dense_update`` span (the optimizer step over the tower,
its new weights read back and pushed to master 0). Needs a window traced
with the program's spans (``harness/spans.py``)."""

from harness import spans


def read(ctx):
    return spans.per_tick_ms(ctx, names=("train.dense_update",))
