"""Bytes handed from the host to the device's programs per example
trained: the program's ``device_io.h2d_bytes`` counter over the window
(id limbs, gradients, rows and codec inputs; the device mirror's table
uploads are counted apart). Needs the window's counter deltas
(``harness/spans.py``)."""

from harness import spans


def read(ctx):
    return spans.bytes_per_example(ctx, "h2d_bytes")
