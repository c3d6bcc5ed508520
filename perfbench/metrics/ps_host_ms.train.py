"""Milliseconds per tick of host work in the master shards: the self
time of the program's ``ps.*`` spans (dedup, ``ensure``, mirror sync,
the fused FTRL call's padding and dispatch, write-back), blocking reads
of the device (``device.wait``) taken out. Needs a window traced with
the program's spans (``harness/spans.py``)."""

from harness import spans


def read(ctx):
    return spans.per_tick_ms(ctx, prefixes=("ps.",))
