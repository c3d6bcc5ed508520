"""Share (%) of the chip's bfloat16 peak that the window's tower work
needs: for every example trained in the window, the predict-before-train
forward pass and the training forward and backward passes of the
DLRM-DCNv2 tower (``families/dlrm_dcnv2_counts.py``, ``tower_step``),
over the window at the peak. The tower runs float32 matmuls at
``precision=HIGHEST``, several bfloat16 passes each, so the share reads
well under what bfloat16 matmuls would reach."""

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "perfbench_dlrm_counts",
    Path(__file__).resolve().parents[1] / "families/dlrm_dcnv2_counts.py")
dlrm_counts = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(dlrm_counts)


def read(ctx):
    pk = ctx.peaks
    if (pk is None or not ctx.train or ctx.window_s <= 0
            or "multi_hot" not in ctx.cfg):
        return None
    ops = ctx.stats["examples"] * dlrm_counts.tower_step(ctx.cfg)
    return 100.0 * ops / pk["flops_bf16"] / ctx.window_s
