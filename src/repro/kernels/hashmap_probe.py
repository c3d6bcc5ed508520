"""Pallas hashmap-probe kernel — the device-resident half of
``core.hashmap.IdHashMap``.

The PS addressing core resolves minibatches of int64 feature ids to arena
slots with a Fibonacci-hash, windowed open-addressing probe. This kernel
runs the SAME probe against a device-resident copy of the map's slot-id
table, so the sparse hot path (probe → gather → update → scatter) never
bounces ids back to the host between stages. Semantics mirror
``IdHashMap._probe`` bit-for-bit: identical home slots, identical window
walk, identical EMPTY/TOMB handling — the host map stays the oracle (see
``tests/test_kernels.py``).

TPU adaptation — 32-bit limbs: TPUs (and jax without x64) have no native
int64 vector arithmetic, so the wrapper reinterprets both the key table
and the query ids as (lo, hi) uint32 limb pairs (a free ``.view`` on the
host). The Fibonacci multiply-shift needs only the TOP 32 bits of
``id * ⌊2^64/φ⌋ mod 2^64`` (capacities are ≤ 2^32, so the slot index
lives entirely in the upper limb), which a 32×32→hi32 ``mulhi`` plus two
wrapping multiplies reconstructs exactly. Key equality is a two-limb
compare; the sentinels split as EMPTY = (0, 0x80000000) and
TOMB = (1, 0x80000000).

Probe semantics (identical to the host map): round 1 reads the home
slot — a hit resolves, an EMPTY home resolves as not-found; each tail
round reads ``_WINDOW`` consecutive slots — the first hit in the window
wins (``argmax`` order on the host), a window holding EMPTY resolves as
not-found, survivors advance ``_WINDOW`` slots.

Kernel structure — one kernel, two key-table placements. The key limbs
are laid out as ``(R, 128)`` rows (``wrap_pad_limbs``), the table
repeated past its end so that the rows holding any window are
contiguous. Per grid step the kernel takes ``chunk`` ids, brings the
rows that hold each id's ``window``-slot probe window into one
``(8, 128)`` tile of VMEM scratch, reads row f of every tile back with a
stride-8 load into a ``(chunk, rows · 128)`` block, and resolves as many
host-probe rounds as the window covers. Ids whose chain runs past the
window continue in another pass (a ``lax.while_loop`` over passes; at
the map's ≤25 % load almost every id resolves in the first).

  * ``placement="vmem"`` puts the whole key table in VMEM once per pass
    and copies rows with vector loads. Cheapest for small maps; the table
    must fit the scoped-VMEM limit (``VMEM_SLOT_BOUND``).
  * ``placement="hbm"`` keeps the key table in HBM (``pl.ANY``) and copies
    one 8-row tile per id and limb with ``pltpu.make_async_copy`` into a
    double-buffered scratch: the next chunk's copies start before this
    chunk is probed, so their latency hides behind the probe arithmetic.
    VMEM holds ``2 · chunk`` tiles per limb whatever the table size, so
    map capacity is bounded by HBM.

``ops.hashmap_probe`` picks the placement from the capacity unless the
caller pins one.

Mosaic constraints the layout answers to: a copy or store may address a
single sublane row of a tiled VMEM buffer only at a static offset, so
each id gets a whole tile at a dynamic (tile-aligned) offset; per-id
state travels lane-dense as ``(N/128, 1, 128)`` int32 blocks and is
transposed to columns in the kernel; the window offsets ride in 1024-id
SMEM blocks (XLA's 1-D tiling); the first in-window hit is a ``min``
over a masked iota (Mosaic has no gather from a VMEM table and lowers
``argmax`` for f32 only); and vectors select only on vector masks.

``pos`` is garbage where ``found`` is False — same contract as the host
probe; callers mask.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_WINDOW = 8                         # must match core.hashmap._WINDOW

# Slots probed per id per pass: 31 host-probe rounds, which covers the
# probe chains of a map at ≤25 % load in one pass.
_DMA_WINDOW = 256
_LANES = 128                        # key slots per table row
_TILE = 8                           # rows copied per id: one (8, 128) tile
# Ids per SMEM block of window offsets: XLA tiles a 1-D int32 array by
# 1024, and Mosaic takes the block only in that layout. Batches pad to it
# (``ops`` pads its calls to multiples of it, so nothing pads twice).
OFFSET_BLOCK = 1024
# Ids probed per grid step: the lane width of the (1, 1, chunk) state
# blocks, and a divisor of OFFSET_BLOCK.
_DMA_CHUNK = 128
# Default scoped-VMEM limit of a TPU v5e kernel (the smallest of the TPU
# generations this code targets). The VMEM placement holds the whole
# padded key table there (8 B per slot for the two limbs) beside 2 MiB of
# row tiles and the per-id state blocks; the bound is the largest
# power-of-two capacity whose table fits in half the limit.
SCOPED_VMEM_BYTES = 16 << 20
VMEM_SLOT_BOUND = 1 << ((SCOPED_VMEM_BYTES // 2 // 8).bit_length() - 1)

# ⌊2^64/φ⌋ split into uint32 limbs (lo, hi). Plain ints: jnp scalars
# created at module scope would be captured as constants by the kernel
# trace, which pallas rejects — materialize them inside the trace.
_FIB_LO = 0x7F4A7C15
_FIB_HI = 0x9E3779B9
_SENT_HI = 0x80000000               # EMPTY/TOMB upper limb


def _mulhi32(a, b):
    """High 32 bits of a 32×32-bit unsigned multiply, from 16-bit limbs
    (uint32 lane arithmetic only — every partial product and carry sum
    stays below 2^32)."""
    a0, a1 = a & jnp.uint32(0xFFFF), a >> jnp.uint32(16)
    b0, b1 = b & jnp.uint32(0xFFFF), b >> jnp.uint32(16)
    t = a1 * b0 + ((a0 * b0) >> jnp.uint32(16))
    t2 = a0 * b1 + (t & jnp.uint32(0xFFFF))
    return a1 * b1 + (t >> jnp.uint32(16)) + (t2 >> jnp.uint32(16))


def fib_home_u32(id_lo, id_hi, *, shift: int):
    """Home slots from uint32 id limbs — bit-equal to
    ``core.hashmap.home_slots`` on the reassembled int64 ids.

    The full product mod 2^64 is ``lo·FIB + ((hi·FIB_lo + lo·FIB_hi)
    << 32)``; slot indices are its bits [shift, 64) with shift ≥ 32, so
    only the upper limb ``mulhi(lo, FIB_lo) + hi·FIB_lo + lo·FIB_hi``
    (wrapping uint32 adds) is ever needed."""
    upper = (_mulhi32(id_lo, jnp.uint32(_FIB_LO))
             + id_hi * jnp.uint32(_FIB_LO) + id_lo * jnp.uint32(_FIB_HI))
    return (upper >> jnp.uint32(shift - 32)).astype(jnp.int32)


def _rows(cap: int) -> int:
    """Rows of 128 slots in the padded key table: a copy of ``_TILE`` rows
    may start at the row of any slot ``< cap``."""
    return (cap - 1) // _LANES + _TILE


def _fetch_rows(window: int) -> int:
    """128-slot rows one probe window spans at any start offset."""
    return (_LANES - 1 + window + _LANES - 1) // _LANES


def wrap_pad_limbs(keys_lo, keys_hi, *, cap: int):
    """Lay exact-capacity key-limb arrays out as the probe reads them:
    ``(R, 128)`` rows, slot ``t`` at ``t // 128, t % 128``, extended past
    ``cap`` by repeating the table (padded slot ``cap + t`` mirrors slot
    ``t``) so the rows a window reads hold it CONTIGUOUSLY wherever it
    starts — a copy never wraps mid-transfer. Window-local offsets are
    folded back with ``(start + offset) & (cap - 1)``. Works on host numpy
    and traced jax arrays alike (the device mirror pre-pads once per
    upload; the probe wrapper pads ad-hoc inputs on the fly)."""
    shape = (_rows(cap), _LANES)
    xp = np if isinstance(keys_lo, np.ndarray) else jnp
    return (xp.resize(keys_lo, shape), xp.resize(keys_hi, shape))


def _col(row):
    """(1, n) lane vector -> (n, 1) sublane column (a (8, n) transpose)."""
    return jnp.transpose(jnp.broadcast_to(row, (8, row.shape[1])))[:, 0:1]


def _row(col):
    """(n, 1) sublane column -> (1, n) lane vector."""
    return jnp.transpose(jnp.broadcast_to(col, (col.shape[0], 8)))[0:1, :]


def _probe_kernel(first_s, start_now, start_next, klo, khi, qlo_ref, qhi_ref,
                  pos_ref, found_ref, act_ref, cur_ref,
                  pos_out, found_out, act_out, cur_out,
                  buf_lo, buf_hi, sem, *, cap, window, chunk, placement):
    """One probe pass over one id chunk: bring the rows that hold each
    id's window into a ``(_TILE, 128)`` tile of VMEM scratch — one
    ``_TILE``-row copy per limb from HBM, double-buffered (the NEXT
    chunk's copies start first), or row loads from the VMEM-resident
    table — then resolve as many host-probe rounds as the window covers.
    ``start_now``/``start_next`` are the SMEM blocks of window offsets
    that hold this and the next chunk's."""
    i = pl.program_id(0)
    nsteps = pl.num_programs(0)
    per_block = OFFSET_BLOCK // chunk
    now0 = (i % per_block) * chunk
    next0 = ((i + 1) % per_block) * chunk
    nrows = _fetch_rows(window)
    width = nrows * _LANES

    def for_each_id(starts, base, slot, fn):
        """``fn(first table row, first scratch row)`` for every id."""
        def body(c, carry):
            dst = pl.multiple_of((slot * chunk + c) * _TILE, _TILE)
            fn(starts[base + c] >> 7, dst)
            return carry
        jax.lax.fori_loop(0, chunk, body, 0)

    if placement == "hbm":
        def copies(slot, op):
            def fn(row, dst):
                for src, buf, limb in ((klo, buf_lo, 0), (khi, buf_hi, 1)):
                    op(pltpu.make_async_copy(src.at[pl.ds(row, _TILE)],
                                             buf.at[pl.ds(dst, _TILE)],
                                             sem.at[slot, limb]))
            return fn

        @pl.when(i == 0)
        def _start_first():
            for_each_id(start_now, now0, 0, copies(0, lambda cp: cp.start()))

        @pl.when(i + 1 < nsteps)
        def _prefetch_next():               # overlap: next chunk's copies
            nxt = (i + 1) % 2               # fly while this chunk probes
            for_each_id(start_next, next0, nxt,
                        copies(nxt, lambda cp: cp.start()))

        slot = i % 2
        for_each_id(start_now, now0, slot,
                    copies(slot, lambda cp: cp.wait()))
    else:
        slot = 0

        def load(row, dst):
            for f in range(nrows):
                buf_lo[pl.ds(dst + f, 1), :] = klo[pl.ds(row + f, 1), :]
                buf_hi[pl.ds(dst + f, 1), :] = khi[pl.ds(row + f, 1), :]
        for_each_id(start_now, now0, slot, load)

    # row f of every id's tile: a stride-_TILE load -> (chunk, width)
    base = slot * chunk * _TILE
    kw_lo = jnp.concatenate(
        [buf_lo[pl.ds(base + f, chunk, stride=_TILE), :]
         for f in range(nrows)], axis=1)
    kw_hi = jnp.concatenate(
        [buf_hi[pl.ds(base + f, chunk, stride=_TILE), :]
         for f in range(nrows)], axis=1)
    qlo = _col(qlo_ref[0])                              # (chunk, 1)
    qhi = _col(qhi_ref[0])
    cur = _col(cur_ref[0])
    first_i = first_s[0]                                # 1 on pass one

    start = first_i
    k_groups = window // _WINDOW - first_i   # window is a multiple of 8
    off = (jax.lax.broadcasted_iota(jnp.int32, (chunk, width), 1)
           - (cur & jnp.int32(_LANES - 1)))
    valid = (off >= start) & (off < start + _WINDOW * k_groups)
    grp = (off - start) >> 3                            # garbage off-valid
    match = (kw_lo == qlo) & (kw_hi == qhi)
    empty = (kw_hi == jnp.uint32(_SENT_HI)) & (kw_lo == jnp.uint32(0))
    hitw = match & valid
    emptyw = empty & valid
    big = jnp.int32(width)                   # > any group index or offset
    gmin = jnp.min(jnp.where(hitw | emptyw, grp, big), axis=1,
                   keepdims=True)                       # (chunk, 1)
    resolved_w = gmin < big
    # first hit inside the resolving group: min over a masked iota
    ploc = jnp.min(jnp.where(hitw & (grp == gmin), off, big), axis=1,
                   keepdims=True)
    found_w = ploc < big

    # first pass: the home slot (offset 0) is checked BEFORE any window
    at_home = off == 0
    hit0 = jnp.max(jnp.where(at_home & match, 1, 0), axis=1,
                   keepdims=True) == 1
    empty0 = jnp.max(jnp.where(at_home & empty, 1, 0), axis=1,
                     keepdims=True) == 1
    # (Mosaic selects between vectors only on a vector mask)
    first = (jnp.zeros((chunk, 1), jnp.int32) + first_i) == 1
    resolved = resolved_w | (first & (hit0 | empty0))
    fnd = (first & (hit0 | (~empty0 & found_w))) | (~first & found_w)
    ploc = jnp.where(first & hit0, jnp.int32(0), ploc)

    act = _col(act_ref[0]) != 0
    newly = act & resolved & fnd
    abspos = (cur + ploc) & jnp.int32(cap - 1)
    pos_out[0] = _row(jnp.where(newly, abspos, _col(pos_ref[0])))
    found_out[0] = _row(((_col(found_ref[0]) != 0) | newly)
                        .astype(jnp.int32))
    alive = act & ~resolved
    act_out[0] = _row(alive.astype(jnp.int32))
    adv = start + _WINDOW * k_groups
    cur_out[0] = _row(jnp.where(alive, (cur + adv) & jnp.int32(cap - 1),
                                cur))


def _probe_pass(klo, khi, qlo, qhi, pos, found, active, cur, first, *,
                cap, window, chunk, placement, interpret):
    nsteps = cur.shape[0]
    npad = nsteps * chunk
    per_block = OFFSET_BLOCK // chunk
    nblocks = npad // OFFSET_BLOCK
    kernel = functools.partial(_probe_kernel, cap=cap, window=window,
                               chunk=chunk, placement=placement)
    col = pl.BlockSpec((1, 1, chunk), lambda i, first_s: (i, 0, 0))
    starts = cur.reshape(npad)
    table = pl.BlockSpec(memory_space=(pltpu.VMEM if placement == "vmem"
                                       else pl.ANY))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                          # round-1 flag
        grid=(nsteps,),
        in_specs=[
            pl.BlockSpec((OFFSET_BLOCK,),              # window offsets of
                         lambda i, first_s: (i // per_block,),  # this and
                         memory_space=pltpu.SMEM),      # the next chunk
            pl.BlockSpec((OFFSET_BLOCK,),
                         lambda i, first_s: (jnp.minimum(
                             (i + 1) // per_block, nblocks - 1),),
                         memory_space=pltpu.SMEM),
            table, table, col, col, col, col, col, col],
        out_specs=[col, col, col, col],
        scratch_shapes=[pltpu.VMEM((2 * chunk * _TILE, _LANES), jnp.uint32),
                        pltpu.VMEM((2 * chunk * _TILE, _LANES), jnp.uint32),
                        pltpu.SemaphoreType.DMA((2, 2))],
    )
    col_i32 = jax.ShapeDtypeStruct((nsteps, 1, chunk), jnp.int32)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[col_i32] * 4,
        interpret=interpret,
        name="hashmap_probe",
    )(first, starts, starts, klo, khi, qlo, qhi, pos, found, active, cur)


def hashmap_probe(keys_lo: jax.Array, keys_hi: jax.Array,
                  ids_lo: jax.Array, ids_hi: jax.Array, *,
                  shift: int, placement: str, interpret: bool = False,
                  window: int = _DMA_WINDOW, chunk: int = _DMA_CHUNK):
    """Probe a device-resident slot-id table.

    Args:
      keys_lo, keys_hi: uint32 — the map's key array as little-endian
        32-bit limbs (EMPTY/TOMB sentinels included), either (C,) exact
        capacity (padded here on the fly) or already laid out by
        ``wrap_pad_limbs`` (the device mirror uploads them that way so
        steady-state calls pad nothing). C is a power of two, as
        ``IdHashMap`` capacities always are.
      ids_lo, ids_hi: (N,) uint32 — query ids, same limb split.
      shift: the map's Fibonacci shift (``64 - log2(C)``; 32 ≤ shift ≤
        60); the capacity is ``2**(64 - shift)``, so it survives padding.
      placement: ``"vmem"`` (whole table in VMEM; C ≤ ``VMEM_SLOT_BOUND``)
        or ``"hbm"`` (table stays in HBM).
      window: slots probed per id per pass (clamped to C).
      chunk: ids probed per grid step.

    Returns:
      (pos (N,) int32, found (N,) bool). ``pos`` is the key's table slot
      where ``found``; garbage otherwise. Bit-equal to
      ``IdHashMap._probe`` on the same state.
    """
    assert placement in ("vmem", "hbm"), f"unknown placement {placement!r}"
    cap = 1 << (64 - int(shift))
    w = min(window, cap)
    assert _fetch_rows(w) <= _TILE and OFFSET_BLOCK % chunk == 0
    n = ids_lo.shape[0]
    if n == 0:
        return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), jnp.bool_))
    if keys_lo.ndim == 1:
        keys_lo, keys_hi = wrap_pad_limbs(keys_lo, keys_hi, cap=cap)
    assert keys_lo.shape == (_rows(cap), _LANES), \
        f"key table must be ({cap},) or wrap-padded ({_rows(cap)}, 128)"

    npad = -(-n // OFFSET_BLOCK) * OFFSET_BLOCK
    zpad = npad - n
    qlo = jnp.concatenate([ids_lo, jnp.zeros((zpad,), jnp.uint32)])
    qhi = jnp.concatenate([ids_hi, jnp.zeros((zpad,), jnp.uint32)])
    # sentinel-valued queries can never be stored: probe id 0, force
    # not-found at the end (mirrors the host probe's `bad` handling)
    bad = (qhi == jnp.uint32(_SENT_HI)) & (qlo <= jnp.uint32(1))
    qlo = jnp.where(bad, jnp.uint32(0), qlo)
    qhi = jnp.where(bad, jnp.uint32(0), qhi)
    dense = (npad // chunk, 1, chunk)       # lane-dense per-id state
    home = fib_home_u32(qlo, qhi, shift=shift).reshape(dense)
    active0 = (jnp.arange(npad) < n).astype(jnp.int32).reshape(dense)
    max_passes = cap // _WINDOW + 2

    def cond(state):
        r, _, _, _, active = state
        return jnp.logical_and(r < max_passes, active.any())

    def body(state):
        r, cur, pos, found, active = state
        first = (r == 0).astype(jnp.int32).reshape(1)
        pos, found, active, cur = _probe_pass(
            keys_lo, keys_hi, qlo.reshape(dense), qhi.reshape(dense),
            pos, found, active, cur, first, cap=cap, window=w, chunk=chunk,
            placement=placement, interpret=interpret)
        return r + 1, cur, pos, found, active

    init = (jnp.int32(0), home, home, jnp.zeros(dense, jnp.int32), active0)
    _, _, pos, found, _ = jax.lax.while_loop(cond, body, init)
    return pos.reshape(npad)[:n], (found.reshape(npad)[:n] != 0) & ~bad[:n]
