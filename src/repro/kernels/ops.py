"""Jit'd public wrappers for the Pallas kernels, and the fused PS chains
built from them.

On the CPU backend kernels execute in interpret mode — the kernel body
runs in Python for correctness validation; on TPU the same calls compile
to Mosaic. ``interpret`` resolves from the backend.

Row gathers and scatters on the PS arenas are XLA ops, not kernels: XLA
stores a narrow ``(V, D)`` f32 arena (D = 1 or 8) with the row index as
the minor (lane) dimension, compactly, while a Pallas operand is laid out
row-major with D padded to 128 lanes — a whole-table relayout copy 128/D
times the arena's size before any row moves.

The PS entry points (``embedding_scatter``, ``fused_lookup``,
``fused_gather``, ``fused_ftrl_apply``, ``quantize_rows``,
``dequantize_rows``) take host arrays of any length, pad them to a few
power-of-two lengths — every distinct length compiles its own program —
and return unpadded results. ``quantize_tensors`` takes dense tensors,
whose shapes are fixed, on the device or the host, and codes each at
its own shape. ``PooledLookup`` (multi-hot fields
sum-pooled from a batch's unique rows, and its transpose) moves rows in
fixed-size chunks through one device buffer instead, so its programs
compile once per batch shape whatever the unique-row count. What they
hand to the device and read back is counted, and each blocking read is a
span, through ``kernels/device_io.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

import numpy as np

from repro.kernels import decode_attention as _da
from repro.kernels import delta_codec as _dc
from repro.kernels import flash_attention as _fa
from repro.kernels import ftrl_row_update as _ftrl
from repro.kernels import hashmap_probe as _hm
from repro.kernels.device_io import count_h2d, to_host


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def int64_limbs(a: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Split a host int64 array into (lo, hi) uint32 limb arrays — the id
    format the device probe consumes (jax runs with x64 disabled; see
    ``kernels/hashmap_probe.py``). A reinterpreting view + two strided
    copies; assumes a little-endian host (x86/ARM)."""
    v = np.ascontiguousarray(a, dtype=np.int64).view(np.uint32)
    v = v.reshape(-1, 2)
    return np.ascontiguousarray(v[:, 0]), np.ascontiguousarray(v[:, 1])


def _bucket(n: int, floor: int = _hm.OFFSET_BLOCK) -> int:
    """Padded length of an ``n``-row device call: the next power of two, at
    least ``floor`` (the probe's batch granule,
    ``hashmap_probe.OFFSET_BLOCK``, unless the caller gives another)."""
    return max(floor, 1 << (max(n, 1) - 1).bit_length())


def _pad(a, n: int, fill) -> np.ndarray:
    """Host copy of ``a`` extended along axis 0 to ``n`` rows of ``fill``."""
    a = np.asarray(a)
    out = np.full((n,) + a.shape[1:], fill, dtype=a.dtype)
    out[:len(a)] = a
    return out


def _id_limbs(ids, n: int):
    """Host int64 ids as uint32 limbs, padded to ``n`` with the map's EMPTY
    sentinel (``core.hashmap.EMPTY``), which the probe never finds."""
    return int64_limbs(_pad(np.asarray(ids, np.int64), n,
                            np.iinfo(np.int64).min))


@functools.partial(jax.jit, donate_argnums=0)
def _scatter_program(table, ids, updates):
    rows = table.reshape((-1,) + updates.shape[1:])
    return rows.at[ids].set(updates.astype(table.dtype),
                            mode="drop").reshape(table.shape)


def embedding_scatter(table, ids, updates):
    """Row scatter-SET ``table[ids[i]] = updates[i]`` into a DONATED device
    table, in place — the device mirror's incremental upload. The table is
    viewed as rows shaped like ``updates[i]`` (a ``(R, 128)`` key-limb
    table takes flat slot positions). ``ids`` (unique) and ``updates`` are
    host arrays of any length; out-of-range ids are dropped."""
    updates = np.asarray(updates)
    nb = _bucket(len(updates))
    # padding indexes one row past the end, where mode="drop" discards it
    past_end = table.size // max(1, int(np.prod(updates.shape[1:])))
    return _scatter_program(table, _pad(np.asarray(ids, np.int32), nb,
                                        past_end), _pad(updates, nb, 0))


def _probe(keys_lo, keys_hi, ids_lo, ids_hi, *, shift, placement):
    """Placement-dispatched probe (traced): ``"vmem"`` puts the whole key
    table in VMEM per call (cheapest for small maps), ``"hbm"`` keeps it
    in HBM and copies probe windows (no VMEM capacity bound), ``"auto"``
    picks by capacity against ``VMEM_SLOT_BOUND``."""
    if placement == "auto":
        cap = 1 << (64 - int(shift))
        placement = "hbm" if cap > _hm.VMEM_SLOT_BOUND else "vmem"
    return _hm.hashmap_probe(keys_lo, keys_hi, ids_lo, ids_hi, shift=shift,
                             placement=placement, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("shift", "placement"))
def hashmap_probe(keys_lo, keys_hi, ids_lo, ids_hi, *, shift,
                  placement="auto"):
    """Jitted ``kernels.hashmap_probe.hashmap_probe`` with ``"auto"``
    placement by capacity: ``(pos, found)`` for each id."""
    return _probe(keys_lo, keys_hi, ids_lo, ids_hi, shift=shift,
                  placement=placement)


@functools.partial(jax.jit, static_argnames=("shift", "placement"))
def _lookup_program(keys_lo, keys_hi, slot_of, arena, ids_lo, ids_hi, *,
                    shift, placement="auto"):
    pos, found = _probe(keys_lo, keys_hi, ids_lo, ids_hi, shift=shift,
                        placement=placement)
    slot = jnp.where(found, jnp.take(slot_of, pos, mode="clip"), 0)
    rows = jnp.take(arena, slot, axis=0, mode="clip")
    return (jnp.where(found[:, None], rows, jnp.zeros((), rows.dtype)),
            found, slot)


def fused_lookup(keys_lo, keys_hi, slot_of, arena, ids, *, shift,
                 placement="auto"):
    """Fused probe→gather: serve-path lookup of host int64 ``ids`` (any
    length) against a device-resident table mirror, one jit — no host hop
    between the probe and the row gather. ``slot_of`` is the map's value
    table (key-slot → arena slot, int32). Missing rows come back as zeros.
    Returns (rows (N, D) device array, found (N,) bool, slot (N,) int32 —
    arena slots, 0 where not found — both host arrays): the found mask
    lets callers count cache misses straight off the device probe, the
    slots let them update host-side LRU stats, neither costs a host
    re-probe."""
    n = len(ids)
    limbs = _id_limbs(ids, _bucket(n))
    count_h2d(*limbs)
    rows, found, slot = _lookup_program(
        keys_lo, keys_hi, slot_of, arena, *limbs,
        shift=shift, placement=placement)
    found, slot = to_host(found, slot)
    return rows[:n], found[:n], slot[:n]


def fused_gather(keys_lo, keys_hi, slot_of, arena, ids, *, shift,
                 placement="auto"):
    """``fused_lookup``'s rows as a host array. Sliced on the host: a
    device slice compiles a program for every id count."""
    limbs = _id_limbs(ids, _bucket(len(ids)))
    count_h2d(*limbs)
    rows, _, _ = _lookup_program(
        keys_lo, keys_hi, slot_of, arena, *limbs,
        shift=shift, placement=placement)
    return to_host(rows)[0][:len(ids)]


@functools.partial(jax.jit,
                   static_argnames=("shift", "alpha", "beta", "l1", "l2",
                                    "placement"),
                   donate_argnums=(3, 4, 5))
def _ftrl_program(keys_lo, keys_hi, slot_of, z_arena, n_arena, w_arena,
                  ids_lo, ids_hi, grads, *, shift, alpha, beta, l1, l2,
                  placement="auto"):
    pos, found = _probe(keys_lo, keys_hi, ids_lo, ids_hi, shift=shift,
                        placement=placement)
    # not-found ids scatter out of range, where mode="drop" discards them
    slot = jnp.where(found, jnp.take(slot_of, pos, mode="clip"),
                     z_arena.shape[0])
    z = jnp.take(z_arena, slot, axis=0, mode="clip")
    n = jnp.take(n_arena, slot, axis=0, mode="clip")
    z2, n2, w2 = _ftrl.ftrl_row_update(z, n, grads, alpha=alpha, beta=beta,
                                       l1=l1, l2=l2,
                                       interpret=_interpret())
    z_arena = z_arena.at[slot].set(z2, mode="drop")
    n_arena = n_arena.at[slot].set(n2, mode="drop")
    w_arena = w_arena.at[slot].set(w2.astype(w_arena.dtype), mode="drop")
    return z_arena, n_arena, w_arena, z2, n2, w2, found


def fused_ftrl_apply(keys_lo, keys_hi, slot_of, z_arena, n_arena, w_arena,
                     ids, grads, *, shift, alpha, beta, l1, l2,
                     placement="auto"):
    """The fused sparse training hot path, one jit end to end:
    probe → gather (z, n) → FTRL row update → scatter (z', n', w') back
    into the arenas. No stage output ever leaves the device.

    ``ids`` (host int64, any length) must be UNIQUE and PRESENT in the
    map (``MasterShard`` runs ``ensure`` before engaging the fused path;
    ``found`` is returned so the caller can assert that — rows of ids not
    found are not written); ``grads`` are their (N, D) host rows. The
    three arenas are donated and updated in place — callers rebind them
    from the outputs (the device mirror keeps them resident across
    batches). The row outputs (z', n', w') and ``found`` come back as host
    arrays, so the host-authoritative arrays can be updated without
    re-downloading whole arenas."""
    n = len(ids)
    nb = _bucket(n)
    ins = (*_id_limbs(ids, nb), _pad(np.asarray(grads, np.float32), nb, 0))
    count_h2d(*ins)
    z_a, n_a, w_a, z2, n2, w2, found = _ftrl_program(
        keys_lo, keys_hi, slot_of, z_arena, n_arena, w_arena, *ins,
        shift=shift, alpha=alpha, beta=beta, l1=l1, l2=l2,
        placement=placement)
    z2, n2, w2, found = to_host(z2, n2, w2, found)
    return z_a, n_a, w_a, z2[:n], n2[:n], w2[:n], found[:n]


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "l1", "l2"))
def ftrl_row_update(z, n, g, *, alpha=0.05, beta=1.0, l1=1.0, l2=1.0):
    return _ftrl.ftrl_row_update(z, n, g, alpha=alpha, beta=beta, l1=l1,
                                 l2=l2, interpret=_interpret())


@jax.jit
def _quantize_program(x):
    return _dc.quantize_rows(x, interpret=_interpret())


@jax.jit
def _dequantize_program(q, scale):
    return _dc.dequantize_rows(q, scale, interpret=_interpret())


def quantize_rows(x):
    """Row-wise absmax int8 of an (N, D) f32 array of any length: host
    (q (N, D) int8, scale (N, 1) f32). Rows are padded to a power of
    two."""
    x = np.asarray(x, np.float32)
    nb = _bucket(len(x))
    xp = x if len(x) == nb else _pad(x, nb, 0)
    count_h2d(xp)
    q, scale = to_host(*_quantize_program(xp))
    return q[:len(x)], scale[:len(x)]


@jax.jit
def _quantize_tensor_program(x):
    """``_quantize_program`` over a dense tensor by its rows
    (``delta_codec.tensor_rows``): the rows are padded to whole kernel
    blocks inside the program and sliced off again, so only the real
    rows' codes come out."""
    rows = _dc.tensor_rows(x)
    n = len(rows)
    # up to one block, a block of whole 8-row tiles; past it, full blocks
    block = min(_dc.BLOCK_ROWS, -(-n // 8) * 8)
    xp = jnp.pad(rows, ((0, -n % block), (0, 0)))
    q, scale = _dc.quantize_rows(xp, block_rows=block,
                                 interpret=_interpret())
    return q[:n], scale[:n]


def quantize_tensors(xs) -> list:
    """Row-wise absmax int8 of dense tensors, each by its rows at its own
    shape (one program a shape, so no padding to a power of two): host
    ``(q (rows, D) int8, scale (rows, 1) f32)`` for each, all read back in
    one blocking read. A tensor that lives on the device is coded there,
    so its f32 values never cross; a host tensor goes up as it is."""
    xs = [x if isinstance(x, jax.Array) else np.asarray(x, np.float32)
          for x in xs]
    count_h2d(*xs)
    outs = [a for x in xs for a in _quantize_tensor_program(x)]
    for a in outs:                  # every copy under way before the wait
        a.copy_to_host_async()
    host = to_host(*outs)
    return list(zip(host[0::2], host[1::2]))


def dequantize_rows(q, scale):
    """Inverse of ``quantize_rows``: host (N, D) f32."""
    q = np.asarray(q)
    nb = _bucket(len(q))
    ins = (_pad(q, nb, 0), _pad(scale, nb, 1))
    count_h2d(*ins)
    return to_host(_dequantize_program(*ins))[0][:len(q)]


POOL_CHUNK = 16384       # rows a PooledLookup upload or read moves at once


@functools.partial(jax.jit, donate_argnums=0)
def _put_rows(buf, rows, start):
    return jax.lax.dynamic_update_slice(buf, rows, (start, 0))


@jax.jit
def _get_rows(buf, start):
    return jax.lax.dynamic_slice(buf, (start, 0), (POOL_CHUNK, buf.shape[1]))


@functools.partial(jax.jit, static_argnames=("sizes",))
def _pooled_lookup(rows, inv, *, sizes):
    """(B, len(sizes), D): row ``inv[b, s]`` of ``rows`` summed over each
    field's static slot range (``sizes`` slots a field, in order)."""
    g = jnp.take(rows, inv, axis=0)
    out, lo = [], 0
    for n in sizes:
        out.append(g[:, lo:lo + n].sum(axis=1))
        lo += n
    return jnp.stack(out, axis=1)


@functools.partial(jax.jit, static_argnames=("rows",))
def _pooled_grad(g, src, dst, *, rows):
    """The transpose of ``_pooled_lookup``: slot ``k``'s gradient (its
    field's pooled gradient, row ``src[k]`` of ``g`` flattened) summed
    into unique row ``dst[k]``; ``dst`` ascending, so the scatter-add is
    a sorted segment sum."""
    part = jnp.take(g.reshape(-1, g.shape[-1]), src, axis=0)
    return jnp.zeros((rows, g.shape[-1]), g.dtype).at[dst].add(
        part, indices_are_sorted=True)


class PooledLookup:
    """Sum-pooled multi-hot lookup on the device, and its transpose.

    A batch's unique rows ``(U, D)`` go up once, in ``POOL_CHUNK``-row
    pieces, into a device buffer of ``max_rows`` rows (rounded up to whole
    chunks); with the ``(B, S)`` inverse (slot ``s`` of example ``b``
    reads unique row ``inv[b, s]``), ``lookup`` gathers and sums each
    field's static slot range, ``(B, F, D)``, and ``grad`` takes a
    ``(B, F, D)`` gradient back to ``(U, D)`` unique-row gradients,
    reduced on the device and read back in chunks. Neither the ``(B, S,
    D)`` rows nor their gradients leave the device; the programs compile
    once per ``(B, S)`` shape. A batch with more unique rows than the
    buffer holds grows it (and compiles again)."""

    def __init__(self, sizes, dim: int, max_rows: int):
        self.sizes = tuple(int(n) for n in sizes)
        self.dim = int(dim)
        self.field_of = np.repeat(np.arange(len(self.sizes), dtype=np.int32),
                                  self.sizes)
        self.cap = 0
        self._grow(max_rows)

    def _grow(self, rows: int) -> None:
        cap = -(-max(int(rows), 1) // POOL_CHUNK) * POOL_CHUNK
        if cap > self.cap:
            self.cap = cap
            self._buf = jnp.zeros((cap, self.dim), jnp.float32)

    def lookup(self, rows: np.ndarray, inv: np.ndarray):
        """Pooled ``(B, F, D)`` device rows of host unique ``rows`` and the
        host int32 inverse ``inv`` ``(B, S)``."""
        rows = np.asarray(rows, np.float32)
        self._grow(len(rows))
        for lo in range(0, len(rows), POOL_CHUNK):
            part = rows[lo:lo + POOL_CHUNK]
            if len(part) < POOL_CHUNK:
                part = _pad(part, POOL_CHUNK, 0)
            count_h2d(part)
            self._buf = _put_rows(self._buf, part, np.int32(lo))
        inv = np.asarray(inv, np.int32)
        count_h2d(inv)
        return _pooled_lookup(self._buf, inv, sizes=self.sizes)

    def grad(self, g, order: np.ndarray, dst: np.ndarray,
             n: int) -> np.ndarray:
        """Host ``(n, D)`` gradients of the last ``lookup``'s unique rows
        from the device gradient ``g`` ``(B, F, D)`` of its pooled rows.
        ``order`` lists every slot (flat ``b * S + s``) once, sorted by
        the unique row it read, and ``dst`` that row (ascending)."""
        s = len(self.field_of)
        order = np.asarray(order)
        src = ((order // s) * len(self.sizes)
               + self.field_of[order % s]).astype(np.int32)
        dst = np.asarray(dst, np.int32)
        count_h2d(src, dst)
        full = _pooled_grad(g, src, dst, rows=self.cap)
        parts = to_host(*(_get_rows(full, np.int32(lo))
                          for lo in range(0, n, POOL_CHUNK)))
        return np.concatenate(parts)[:n]


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, block_q=128, block_k=128):
    return _fa.flash_attention(q, k, v, causal=causal, block_q=block_q,
                               block_k=block_k, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_k",))
def decode_attention(q, k, v, lengths, *, block_k=512):
    return _da.decode_attention(q, k, v, lengths, block_k=block_k,
                                interpret=_interpret())
