"""Share (%) of the pooled multi-hot lookup's roofline: the least time of
the bytes the window's pooled gathers and their transposes move
(``families/dlrm_dcnv2_counts.py``, ``pool``, over the examples trained
and every call's unique ids; bytes-bound) over the device time of their
programs (``jit__pooled_lookup``, ``jit__pooled_grad``) in the trace."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "perfbench_dlrm_counts",
    Path(__file__).resolve().parents[1] / "families/dlrm_dcnv2_counts.py")
dlrm_counts = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dlrm_counts)

PROGRAM = r"^jit__pooled_(lookup|grad)$"


def read(ctx):
    r, pk = ctx.trace, ctx.peaks
    if r is None or pk is None or not ctx.unique_per_batch \
            or "multi_hot" not in ctx.cfg:
        return None
    t = r.module_ns(PROGRAM) * 1e-9
    if t <= 0:
        return None
    unique = sum(sum(u.values()) for u in ctx.unique_per_batch)
    nbytes = dlrm_counts.pool(ctx.cfg, ctx.stats["examples"], unique)
    return 100.0 * ctx.counts.least_time(0.0, nbytes, pk)[0] / t
