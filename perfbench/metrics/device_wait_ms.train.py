"""Milliseconds per tick the host waits on the device: the program's
``device.wait`` spans, each a blocking read of device outputs, the copy
to the host included. Needs a window traced with the program's spans
(``harness/spans.py``)."""

from harness import spans


def read(ctx):
    return spans.per_tick_ms(ctx, names=("device.wait",))
