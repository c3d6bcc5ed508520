"""Bytes read back from the device per example trained: the program's
``device_io.d2h_bytes`` counter over the window (z', n', w' rows, found
masks, predictions, gradients, codec outputs). Needs the window's
counter deltas (``harness/spans.py``)."""

from harness import spans


def read(ctx):
    return spans.bytes_per_example(ctx, "d2h_bytes")
