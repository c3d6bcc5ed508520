"""Operations and bytes of family ``dlrm_dcnv2``'s device work, from
shapes alone (the readers ``step_mfu.dlrm``, ``tower_roofline.dlrm`` and
``pool_roofline.dlrm`` take them; ``tests/test_dlrm_dcnv2.py`` holds them
to hand counts). Counts are of the work a window's examples need, not of
how the program pads or splits it: padded examples are not counted, so a
share reads low, never high.

Tower: a matmul of an ``(m, k)`` by a ``(k, n)`` matrix is ``2 m k n``
operations; the forward pass's matmuls are the bottom MLP's, the cross
layers' two low-rank factors and the top MLP's; elementwise work (bias,
ReLU, the cross layers' products and sums) is counted too, one operation
an element. The backward pass is two matmuls for each forward one (by the
input and by the weight), except the bottom MLP's first, whose input (the
dense features) takes no gradient, and twice the elementwise work. Bytes
are float32: the tower's weights read once by a forward pass, twice by a
training pass (forward and backward) and its gradients written once, and
the pooled rows in and their gradient out.

Pooled lookup: each of an example's slots reads one ``dim``-wide row and
one int32 index, and each field's pooled row is written; its transpose
reads the pooled gradient and the indices, adds each slot's gradient into
its unique row (read and written) and writes the unique rows out.
"""

from __future__ import annotations

F32, IDX = 4, 4


def _mlp(sizes) -> list:
    return [(sizes[i], sizes[i + 1]) for i in range(len(sizes) - 1)]


def matmuls(cfg: dict) -> list:
    """(k, n) of every forward matmul of one example, in order."""
    d = (len(cfg["multi_hot"]) + 1) * cfg["embed_dim"]
    out = _mlp([cfg["dense_features"]] + list(cfg["bottom_mlp"]))
    for _ in range(cfg["dcn_layers"]):
        out += [(d, cfg["dcn_rank"]), (cfg["dcn_rank"], d)]
    return out + _mlp([d] + list(cfg["top_mlp"]))


def elementwise(cfg: dict) -> int:
    """Elementwise operations of one example's forward pass: a bias add
    and a ReLU on every MLP layer's output (no ReLU on the logit), and a
    cross layer's bias add, product with x0 and residual add, three an
    element of its width."""
    d = (len(cfg["multi_hot"]) + 1) * cfg["embed_dim"]
    mlp = sum(2 * n for _, n in _mlp([cfg["dense_features"]]
                                     + list(cfg["bottom_mlp"])))
    mlp += sum(2 * n for _, n in _mlp([d] + list(cfg["top_mlp"]))) - 1
    return mlp + 3 * d * cfg["dcn_layers"]


def weights(cfg: dict) -> int:
    """Parameters of the tower: every matmul's weight, the MLP layers'
    biases and each cross layer's bias."""
    d = (len(cfg["multi_hot"]) + 1) * cfg["embed_dim"]
    return (sum(k * n for k, n in matmuls(cfg)) + sum(cfg["bottom_mlp"])
            + sum(cfg["top_mlp"]) + d * cfg["dcn_layers"])


def tower_forward(cfg: dict) -> float:
    """Operations of one example's forward pass."""
    return float(sum(2 * k * n for k, n in matmuls(cfg))
                 + elementwise(cfg))


def tower_backward(cfg: dict) -> float:
    """Operations of one example's backward pass."""
    mm = matmuls(cfg)
    k0, n0 = mm[0]
    return float(sum(4 * k * n for k, n in mm) - 2 * k0 * n0
                 + 2 * elementwise(cfg))


def tower_step(cfg: dict) -> float:
    """Operations the training plane runs for one example: the
    predict-before-train forward, then the training forward and
    backward."""
    return 2.0 * tower_forward(cfg) + tower_backward(cfg)


def tower_bytes(cfg: dict, batches: int, examples: int) -> float:
    """HBM bytes of ``batches`` train steps over ``examples`` examples:
    weights read by the predict and the training forward, read again by
    the backward, gradients written; pooled rows read twice, their
    gradient written."""
    pooled = len(cfg["multi_hot"]) * cfg["embed_dim"] * F32
    return batches * 4.0 * weights(cfg) * F32 + examples * 3.0 * pooled


def pool(cfg: dict, examples: int, unique: int) -> float:
    """HBM bytes of one batch's pooled lookup and its transpose."""
    slots = sum(cfg["multi_hot"])
    row = cfg["embed_dim"] * F32
    pooled = examples * len(cfg["multi_hot"]) * row
    look = examples * slots * (row + IDX) + pooled
    grad = pooled + examples * slots * (IDX + 2 * row) + unique * row
    return float(look + grad)
