"""Host<->device traffic of the PS and training entry points: what they
hand to jitted programs, what they read back, and each blocking read as
a ``device.wait`` span of ``repro.obs.trace``. Kept apart from
``kernels/ops.py`` so counting does not import the Pallas kernels."""

from __future__ import annotations

import numpy as np

from repro.obs import trace as obs_trace


class DeviceIO:
    """Process-wide counts: bytes of host arrays handed to jitted
    programs, bytes of outputs read back, and the blocking reads
    (``device.wait`` sites passed). The device mirror's table uploads
    count in its own counters, not here."""

    __slots__ = ("h2d_bytes", "d2h_bytes", "waits")

    def __init__(self):
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.waits = 0

    def metrics(self) -> dict:
        return {"h2d_bytes": self.h2d_bytes, "d2h_bytes": self.d2h_bytes,
                "waits": self.waits}


DEVICE_IO = DeviceIO()


def count_h2d(*arrays) -> None:
    """Count the host arrays among ``arrays`` about to be handed to a
    jitted program; an array already on the device is no upload."""
    DEVICE_IO.h2d_bytes += sum(a.nbytes for a in arrays
                               if isinstance(a, np.ndarray))


def to_host(*outs) -> tuple:
    """Blocking reads of device outputs into host arrays, as one
    ``device.wait`` span: the host waits for the program, then copies."""
    with obs_trace.get_tracer().span("device.wait"):
        host = tuple(np.asarray(o) for o in outs)
    DEVICE_IO.d2h_bytes += sum(h.nbytes for h in host)
    DEVICE_IO.waits += 1
    return host
