"""Share (%) of the DLRM-DCNv2 tower's roofline: the least time of the
window's tower work (``families/dlrm_dcnv2_counts.py``: operations of the
predict and the training forward and backward over the examples trained,
bytes of the weights, their gradients and the pooled rows per call; the
larger, against the bfloat16 peak and HBM bandwidth) over the device time
of the tower's programs (``jit__dlrm_predict``, ``jit__dlrm_loss_grads``)
in the trace."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "perfbench_dlrm_counts",
    Path(__file__).resolve().parents[1] / "families/dlrm_dcnv2_counts.py")
dlrm_counts = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dlrm_counts)

PROGRAM = r"^jit__dlrm_(predict|loss_grads)$"


def read(ctx):
    r, pk = ctx.trace, ctx.peaks
    if r is None or pk is None or not ctx.unique_per_batch \
            or "multi_hot" not in ctx.cfg:
        return None
    t = r.module_ns(PROGRAM) * 1e-9
    if t <= 0:
        return None
    ex = ctx.stats["examples"]
    ops = ex * dlrm_counts.tower_step(ctx.cfg)
    nbytes = dlrm_counts.tower_bytes(ctx.cfg, len(ctx.unique_per_batch), ex)
    return 100.0 * ctx.counts.least_time(ops, nbytes, pk)[0] / t
