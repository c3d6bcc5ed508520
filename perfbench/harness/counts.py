"""The operations and bytes the algorithm needs for the cells' device
work, from shapes alone, and the least time a chip could take for them.

All counts are of the work, not of how the program splits it into calls:
a batch's unique ids per table group. Bytes are HBM
bytes at float32 rows, int64 ids and int32 slots.

Probe (``hashmap_probe``): each id in (8 B), its position and found flag
out (4 + 1 B), and the key limbs (8 B a slot) of the slots the host map
examines: the home slot, plus one 8-slot window for the share of ids
whose chain runs past it, taken as half the map's load (linear probing at
load a displaces about a/2 of the keys). No arithmetic is counted: the
probe is bytes-bound.
"""

from __future__ import annotations

ID_B, SLOT_B, ROW_B = 8, 4, 4
WINDOW = 8


def probe(n: int, load: float) -> tuple[float, float]:
    """(ops, bytes) of probing ``n`` ids in a map at ``load``."""
    slots = 1.0 + WINDOW * load / 2.0
    return 0.0, n * (ID_B + SLOT_B + 1 + slots * 8)


def ftrl(n: int, dim: int, load: float) -> tuple[float, float]:
    """The fused FTRL chain over ``n`` unique ids of a ``dim``-wide group:
    probe, slot read, grads in, (z, n) read, (z, n, w) written to the
    arenas and returned. About 20 operations an element."""
    _, pb = probe(n, load)
    e = n * dim
    return 20.0 * e, pb + n * SLOT_B + e * ROW_B * (1 + 2 + 3 + 3)


def ctr_flops(examples: int, fields: int, k: int, model: str,
              backward: bool = True) -> float:
    """Operations of FM (or LR) on ``examples``: forward F + 3Fk + 3k + 5
    (linear sum, field sum, squares, interaction, sigmoid; LR F + 5),
    backward F + 2Fk + 3 (LR F + 3)."""
    f = fields
    if model == "lr":
        fwd, bwd = f + 5.0, f + 3.0
    else:
        fwd, bwd = f + 3.0 * f * k + 3.0 * k + 5.0, f + 2.0 * f * k + 3.0
    return examples * (fwd + bwd if backward else fwd)


def train_bytes(unique: int, dim: int) -> float:
    """PS bytes of one batch's ``unique`` ids of a ``dim``-wide group:
    the pull (w read, returned), then the update (id and grad in, z and n
    read, z, n and w written)."""
    return unique * (2 * dim * ROW_B + ID_B + dim * ROW_B
                     + 2 * dim * ROW_B + 3 * dim * ROW_B)


def least_time(ops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """(seconds, bound): the larger of ops over peak FLOP/s and bytes over
    peak HBM bandwidth, and which of the two it is."""
    tf = ops / peaks["flops_bf16"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf > tb else (tb, "bytes")


def map_load(rows: int) -> float:
    """Load of an id map holding ``rows`` ids under the program's growth
    rule (capacity the least power of two, at least 1024, above four
    times the ids)."""
    cap = 1024
    while rows * 4 >= cap:
        cap *= 2
    return rows / cap
