"""Compile rehearsal for a TPU v5e chip that is described, not attached.

The PS main path goes through Mosaic and XLA's TPU compiler at FM widths
(D=8 and D=1, 8192 ids = 256 examples x 32 fields, arenas of 2^22 rows),
so a kernel the chip would refuse fails here, at no chip time. Nothing
runs: ``chip_smoke.py`` is the run on the chip.

``ops`` picks interpret mode from ``jax.default_backend()``, which is the
CPU here, so the ``mosaic`` fixture turns it off for these compiles. The
PS entry points in ``ops`` pad host arrays around jitted programs; the
programs are what is lowered here, at an already padded length.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import hashmap_probe as hm
from repro.kernels import ops

N_IDS = 256 * 32
ARENA_ROWS = 1 << 22
VMEM_CAP = 1 << 20          # key-table capacities on each side of the
HBM_CAP = 1 << 22           # placement bound


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")     # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:              # noqa: BLE001 — any failure
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    yield
    # jit reuses a trace for the same shapes whatever the sharding, so a
    # later CPU call at these shapes would get the Mosaic trace
    jax.clear_caches()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _keys(sharding, cap):
    """Shapes of a ``cap``-slot map's key limbs as the device mirror
    uploads them (``wrap_pad_limbs``), plus its slot map and shift."""
    flat = jax.ShapeDtypeStruct((cap,), jnp.uint32)
    lo, _ = jax.eval_shape(lambda k: hm.wrap_pad_limbs(k, k, cap=cap), flat)
    k = _spec(sharding, lo.shape, jnp.uint32)
    return k, k, _spec(sharding, (cap,), jnp.int32), 64 - cap.bit_length() + 1


def _ids(sharding):
    i = _spec(sharding, (N_IDS,), jnp.uint32)
    return i, i


def _check(compiled, *, kernel, below):
    """``kernel``: a Pallas kernel must be in the program (or must not);
    temporaries must stay below ``below`` bytes."""
    assert ("tpu_custom_call" in compiled.as_text()) == kernel
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < below, (temp, below)


@pytest.mark.parametrize("cap", [VMEM_CAP, HBM_CAP], ids=["vmem", "hbm"])
def test_probe_compiles(one_chip, mosaic, cap):
    """Both placements, picked by capacity; temporaries stay below the key
    table's own size."""
    assert (cap > hm.VMEM_SLOT_BOUND) == (cap == HBM_CAP)
    klo, khi, _, shift = _keys(one_chip, cap)
    c = ops.hashmap_probe.lower(klo, khi, *_ids(one_chip),
                                shift=shift).compile()
    _check(c, kernel=True, below=8 * cap)


@pytest.mark.parametrize("placement", ["vmem", "hbm"])
@pytest.mark.parametrize("cap", [1 << 4, 1 << 12])
def test_probe_small_map_compiles(one_chip, mosaic, cap, placement):
    """Maps smaller than one probe window (the window clamps to the
    capacity) and a few rows of 128 slots, each placement pinned."""
    klo, khi, _, shift = _keys(one_chip, cap)
    c = ops.hashmap_probe.lower(klo, khi, *_ids(one_chip), shift=shift,
                                placement=placement).compile()
    _check(c, kernel=True, below=N_IDS * 64)


@pytest.mark.parametrize("d", [8, 1])
def test_row_gather_scatter_compile(one_chip, d):
    """Row gather and the mirror's donated row scatter are XLA ops on the
    compact arena: no kernel, no arena-sized temporary."""
    arena = _spec(one_chip, (ARENA_ROWS, d), jnp.float32)
    slots = _spec(one_chip, (N_IDS,), jnp.int32)
    rows = _spec(one_chip, (N_IDS, d), jnp.float32)
    gather = jax.jit(lambda a, s: jnp.take(a, s, axis=0, mode="clip"))
    _check(gather.lower(arena, slots).compile(), kernel=False,
           below=ARENA_ROWS * d * 4)
    c = ops._scatter_program.lower(arena, slots, rows).compile()
    _check(c, kernel=False, below=ARENA_ROWS * d * 4)


def test_key_table_scatter_compiles(one_chip):
    """The mirror's incremental key upload: flat slot positions scattered
    into the donated ``(R, 128)`` key-limb table of a map past the VMEM
    bound, and slots into its ``(cap,)`` slot map — in place, no
    temporary the size of either table."""
    klo, _, slot_of, _ = _keys(one_chip, HBM_CAP)
    pos = _spec(one_chip, (N_IDS,), jnp.int32)
    limbs = _spec(one_chip, (N_IDS,), jnp.uint32)
    c = ops._scatter_program.lower(klo, pos, limbs).compile()
    _check(c, kernel=False, below=klo.size * 4)
    c = ops._scatter_program.lower(slot_of, pos, pos).compile()
    _check(c, kernel=False, below=HBM_CAP * 4)


@pytest.mark.parametrize("d", [8, 1])
def test_ftrl_row_update_compiles(one_chip, mosaic, d):
    rows = _spec(one_chip, (N_IDS, d), jnp.float32)
    c = ops.ftrl_row_update.lower(rows, rows, rows).compile()
    _check(c, kernel=True, below=ARENA_ROWS * d * 4)


@pytest.mark.parametrize("d,cap", [(8, HBM_CAP), (1, VMEM_CAP)])
def test_fused_lookup_compiles(one_chip, mosaic, d, cap):
    klo, khi, slot_of, shift = _keys(one_chip, cap)
    arena = _spec(one_chip, (ARENA_ROWS, d), jnp.float32)
    c = ops._lookup_program.lower(klo, khi, slot_of, arena, *_ids(one_chip),
                               shift=shift).compile()
    _check(c, kernel=True, below=ARENA_ROWS * d * 4)


@pytest.mark.parametrize("d,cap", [(8, VMEM_CAP), (1, HBM_CAP)])
def test_fused_ftrl_apply_compiles(one_chip, mosaic, d, cap):
    """The training hot path with its three arenas donated: updated in
    place, so no temporary approaches an arena."""
    klo, khi, slot_of, shift = _keys(one_chip, cap)
    arena = _spec(one_chip, (ARENA_ROWS, d), jnp.float32)
    grads = _spec(one_chip, (N_IDS, d), jnp.float32)
    c = ops._ftrl_program.lower(
        klo, khi, slot_of, arena, arena, arena, *_ids(one_chip), grads,
        shift=shift, alpha=0.05, beta=1.0, l1=1.0, l2=1.0).compile()
    _check(c, kernel=True, below=ARENA_ROWS * d * 4)


@pytest.mark.parametrize("d", [8, 1])
def test_codec_pair_compiles(one_chip, mosaic, d):
    x = _spec(one_chip, (N_IDS, d), jnp.float32)
    _check(ops._quantize_program.lower(x).compile(), kernel=True,
           below=N_IDS * d * 4)
    q = _spec(one_chip, (N_IDS, d), jnp.int8)
    s = _spec(one_chip, (N_IDS, 1), jnp.float32)
    _check(ops._dequantize_program.lower(q, s).compile(), kernel=True,
           below=N_IDS * d * 4)


def _kernel_names(compiled) -> set:
    """Instruction names (less the ``.N`` suffix) of the Pallas calls in
    a compiled program: the names the profiler's op events carry."""
    return {m.group(1) for m in re.finditer(
        r"^\s*(?:ROOT )?%(\S+?)(?:\.\d+)? = .*"
        r"custom_call_target=\"tpu_custom_call\"",
        compiled.as_text(), re.M)}


def test_kernels_keep_their_names(one_chip, mosaic):
    """Each ``pallas_call`` passes ``name=``, so the probe no longer shows
    as the loop body it sits in."""
    klo, khi, slot_of, shift = _keys(one_chip, 1 << 12)
    arena = _spec(one_chip, (1 << 14, 8), jnp.float32)
    grads = _spec(one_chip, (N_IDS, 8), jnp.float32)
    c = ops._ftrl_program.lower(
        klo, khi, slot_of, arena, arena, arena, *_ids(one_chip), grads,
        shift=shift, alpha=0.05, beta=1.0, l1=1.0, l2=1.0).compile()
    assert _kernel_names(c) == {"hashmap_probe", "ftrl_row_update"}
    x = _spec(one_chip, (N_IDS, 8), jnp.float32)
    assert _kernel_names(ops._quantize_program.lower(x).compile()) == \
        {"quantize_rows"}
    q = _spec(one_chip, (N_IDS, 8), jnp.int8)
    sc = _spec(one_chip, (N_IDS, 1), jnp.float32)
    assert _kernel_names(ops._dequantize_program.lower(q, sc).compile()) \
        == {"dequantize_rows"}


# DLRM-DCNv2 at its published widths: one 2048-example batch of 214
# multi-hot slots over 26 fields, 128-wide rows, the tower by its rows
DLRM_B = 2048


def test_pooled_lookup_and_transpose_compile(one_chip):
    """The pooled lookup over the chunked unique-row buffer and its
    transpose (a sorted segment sum): XLA ops, no kernel. Their large
    temporaries are a batch's gathered rows (the lookup keeps two, the
    transpose one), never the buffer's size on top."""
    from repro.configs.weips_ctr import DLRM_DCNV2
    slots = DLRM_DCNV2.id_slots
    cap = ops.POOL_CHUNK * -(-DLRM_B * slots // ops.POOL_CHUNK)
    rows = _spec(one_chip, (cap, 128), jnp.float32)
    inv = _spec(one_chip, (DLRM_B, slots), jnp.int32)
    c = ops._pooled_lookup.lower(rows, inv,
                                 sizes=DLRM_DCNV2.multi_hot).compile()
    gathered = DLRM_B * slots * 128 * 4
    _check(c, kernel=False, below=2 * gathered + (8 << 20))
    g = _spec(one_chip, (DLRM_B, DLRM_DCNV2.fields, 128), jnp.float32)
    idx = _spec(one_chip, (DLRM_B * slots,), jnp.int32)
    c = ops._pooled_grad.lower(g, idx, idx, rows=cap).compile()
    _check(c, kernel=False, below=gathered + (1 << 20))


@pytest.mark.parametrize("rows,d", [(512, 3456), (4096, 512), (8, 3456),
                                    (4096, 1024), (256, 1)])
def test_tower_rows_quantize_compiles(one_chip, mosaic, rows, d):
    """The int8 codec over the DLRM tower's rows as the pusher pads them:
    the cross layers' (rank, 3456) and (3456, rank) factors, a bias row,
    the top MLP's first and last weights."""
    x = _spec(one_chip, (rows, d), jnp.float32)
    _check(ops._quantize_program.lower(x).compile(), kernel=True,
           below=rows * d * 4 + (1 << 20))


@pytest.mark.parametrize("shape", [(13, 512), (3456, 512), (512, 3456),
                                   (3456, 1024), (3456,), (256, 1), (1,)],
                         ids=lambda s: "x".join(map(str, s)))
def test_tower_tensor_quantize_compiles(one_chip, mosaic, shape):
    """The int8 codec over a DLRM tower tensor where it lives, at its own
    shape (rows padded to whole kernel blocks inside the program): the
    13-row first weight, the 3,456-row cross and top weights, a bias, the
    one-column last weight and the one-element last bias."""
    x = _spec(one_chip, shape, jnp.float32)
    c = ops._quantize_tensor_program.lower(x).compile()
    assert _kernel_names(c) == {"quantize_rows"}
    rows = shape[0] if len(shape) > 1 else 1
    _check(c, kernel=True, below=(rows + 255) * shape[-1] * 4 + (1 << 20))


def test_fused_ftrl_apply_compiles_128_wide(one_chip, mosaic):
    """The training hot path at DLRM's row width, on a master's arenas of
    2^20 rows (2^22 key slots): updated in place."""
    klo, khi, slot_of, shift = _keys(one_chip, HBM_CAP)
    arena = _spec(one_chip, (1 << 20, 128), jnp.float32)
    grads = _spec(one_chip, (N_IDS, 128), jnp.float32)
    c = ops._ftrl_program.lower(
        klo, khi, slot_of, arena, arena, arena, *_ids(one_chip), grads,
        shift=shift, alpha=0.05, beta=1.0, l1=1.0, l2=1.0).compile()
    _check(c, kernel=True, below=(1 << 20) * 128 * 4)
