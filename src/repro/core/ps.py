"""Parameter-server storage: row-addressable sparse tables (arena-backed)
and dense banks, composed into master (training) and slave (serving) shards.

Master shards hold *training* state: parameter rows plus optimizer slots
(FTRL ``z,n``, Adam ``m,v``, ...). Slave shards hold *serving* state only:
the transformed inference weights — the paper's heterogeneous-parameter
split (§1.2.1).

The row hot path is fully batched: ID→slot resolution goes through a
vectorized open-addressing hash map (``core.hashmap.IdHashMap``) and row
gather/update/scatter are single fancy-indexed (or Pallas-kernel) passes —
no per-row Python anywhere. ``backend`` selects the row engine:

  * ``"numpy"``  — NumPy fancy indexing; the reference path, and the fast
    path on CPU-only hosts.
  * ``"pallas"`` — serve lookups and FTRL updates run on the device
    against a mirror of the table (``_DeviceMirror``): the ``hashmap_probe``
    kernel resolves ids, XLA gathers and scatters the rows, and FTRL row
    updates fuse through ``ftrl_row_update`` (interpret mode on the CPU
    backend, Mosaic on TPU).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import numpy as np

from repro.core.hashmap import EMPTY as _NO_ID
from repro.core.hashmap import IdHashMap
from repro.kernels.device_io import to_host
from repro.obs import trace as obs_trace
from repro.optim import Optimizer
from repro.optim.optimizers import FTRL

PS_BACKENDS = ("numpy", "pallas")


class _DeviceMirror:
    """Lazily-synced device copy of a ``SparseTable``'s probe state (key
    limbs + slot map) and arenas — what lets the ``pallas`` backend run
    probe→gather→update→scatter entirely on device (``ops.fused_lookup``
    / ``ops.fused_ftrl_apply``) while the host NumPy arrays stay
    authoritative for snapshots, deltas, and the numpy paths.

    Staleness is cheap to detect, never scanned for: the hash map's
    structural ``version`` covers the probe state, and the table's
    mutation clock covers the arenas — rows with ``row_version`` past the
    last synced clock are re-uploaded incrementally through the scatter
    kernel (bulk re-upload when most of the table moved). The key limbs
    sync the same way: the map's dirty-slot journal
    (``IdHashMap.track_dirty_slots``) names the slots each version bump
    touched, so steady-state inserts upload a few slots, not the whole
    table. Fused updates write both sides with the same kernel outputs,
    then ``mark_synced`` — steady-state training batches upload nothing
    but ids and grads.

    The key limbs are uploaded in the probe's row layout
    (``hashmap_probe.wrap_pad_limbs``). Placement: maps up to
    ``VMEM_SLOT_BOUND`` slots are probed with the key table in VMEM,
    larger ones (or any, when the table pins ``device_placement``) with
    it in HBM. Upload traffic is counted (``sync_metrics`` surfaces it)."""

    def __init__(self, table: "SparseTable"):
        self._t = table
        self._map_version = -1
        self._synced_mut = -1
        self.keys_lo = self.keys_hi = self.slot_of = None
        self.arenas: dict = {}
        self._placement: Optional[str] = None   # resolved at key sync
        self._cap = 0                           # capacity at last key sync
        self.syncs = 0
        self.key_full_uploads = 0
        self.key_incremental_uploads = 0
        self.key_bytes_uploaded = 0
        self.arena_bytes_uploaded = 0
        table._map.track_dirty_slots()

    @property
    def shift(self) -> int:
        return int(self._t._map.shift)

    @property
    def placement(self) -> str:
        """Key-table placement at the last sync ("vmem" | "hbm") — the
        static arg fused kernel calls must pass so the probe matches the
        uploaded layout."""
        assert self._placement is not None, "sync() before placement"
        return self._placement

    def _resolve_placement(self, cap: int) -> str:
        forced = self._t.device_placement
        if forced != "auto":
            return forced
        from repro.kernels.hashmap_probe import VMEM_SLOT_BOUND
        return "hbm" if cap > VMEM_SLOT_BOUND else "vmem"

    def _sync_keys(self, m) -> None:
        import jax.numpy as jnp

        from repro.kernels import hashmap_probe as _hm
        from repro.kernels import ops
        cap = m.capacity
        slots = None
        if self.keys_lo is not None and cap == self._cap:
            slots = m.dirty_slots_since(self._map_version)
        if slots is None:
            # full upload: first sync, realloc/rehash, clear, or journal
            # overflow
            klo, khi = _hm.wrap_pad_limbs(*ops.int64_limbs(m.key_table),
                                          cap=cap)
            self.keys_lo = jnp.asarray(klo)
            self.keys_hi = jnp.asarray(khi)
            self.slot_of = jnp.asarray(m.val_table.astype(np.int32))
            self.key_full_uploads += 1
            self.key_bytes_uploaded += klo.nbytes + khi.nbytes + cap * 4
        else:
            if len(slots):
                # every position of the padded layout that mirrors a
                # dirty slot
                span = self.keys_lo.size
                pos = (slots[:, None]
                       + cap * np.arange(-(-span // cap))).ravel()
                pos = pos[pos < span]
                klo, khi = ops.int64_limbs(m.key_table[pos % cap])
                self.keys_lo = ops.embedding_scatter(self.keys_lo, pos, klo)
                self.keys_hi = ops.embedding_scatter(self.keys_hi, pos, khi)
                self.slot_of = ops.embedding_scatter(
                    self.slot_of, slots, m.val_table[slots].astype(np.int32))
                self.key_bytes_uploaded += len(pos) * 8 + len(slots) * 4
            self.key_incremental_uploads += 1
        self._placement = self._resolve_placement(cap)
        self._cap = cap
        self._map_version = m.version
        m.trim_dirty_log(m.version)

    def sync(self) -> None:
        import jax.numpy as jnp

        from repro.kernels import ops
        t = self._t
        m = t._map
        self.syncs += 1
        if self._map_version != m.version:
            self._sync_keys(m)
        host = {"w": t._w, **t._slots}
        row_bytes = sum(v.itemsize * v.shape[1] for v in host.values())
        if not self.arenas or self.arenas["w"].shape != t._w.shape:
            self.arenas = {k: jnp.asarray(v) for k, v in host.items()}
            self.arena_bytes_uploaded += sum(v.nbytes for v in host.values())
        elif self._synced_mut != t._mut:
            top = t._top
            dirty = np.flatnonzero(t.row_version[:top] > self._synced_mut)
            if len(dirty) * 4 > top:
                self.arenas = {k: jnp.asarray(v) for k, v in host.items()}
                self.arena_bytes_uploaded += sum(v.nbytes
                                                 for v in host.values())
            elif len(dirty):
                self.arenas = {k: ops.embedding_scatter(a, dirty,
                                                        host[k][dirty])
                               for k, a in self.arenas.items()}
                self.arena_bytes_uploaded += len(dirty) * row_bytes
        self._synced_mut = t._mut

    def mark_synced(self) -> None:
        """Record that the device arenas already hold the table's state at
        the current clock (a fused kernel just wrote both sides)."""
        self._synced_mut = self._t._mut

    def metrics(self) -> dict:
        return {"syncs": self.syncs,
                "placement": self._placement or "unsynced",
                "key_full_uploads": self.key_full_uploads,
                "key_incremental_uploads": self.key_incremental_uploads,
                "key_bytes_uploaded": self.key_bytes_uploaded,
                "arena_bytes_uploaded": self.arena_bytes_uploaded}


class SparseTable:
    """Row-addressable table over a huge hashed ID space; only touched rows
    exist. Arena storage: a growable (capacity, dim) array + a vectorized
    id→slot hash map, so batched ``ensure``/``lookup``/``evict`` and
    gather/scatter run with no per-row loops."""

    def __init__(self, dim: int, slot_names: tuple[str, ...] = (),
                 init_capacity: int = 1024, dtype=np.float32,
                 backend: str = "numpy"):
        assert backend in PS_BACKENDS, f"backend must be one of {PS_BACKENDS}"
        self.dim = dim
        self.dtype = dtype
        self.backend = backend
        # device key-table placement for the pallas backend: "auto" routes
        # by capacity (VMEM up to VMEM_SLOT_BOUND slots, HBM above);
        # "vmem"/"hbm" pin it (tests and benchmarks exercise the HBM path
        # at small capacities this way)
        self.device_placement = "auto"
        self.slot_names = tuple(slot_names)
        self._map = IdHashMap(init_capacity)
        cap = max(1, init_capacity)
        # reverse map slot→id; _NO_ID (a reserved key sentinel, so it can
        # never collide with a real id — ids like -1 are legal) marks
        # unused slots. all_ids() scans this instead of the (4× larger)
        # hash-map key array.
        self._id_of = np.full(cap, _NO_ID, dtype=np.int64)
        self._free = np.empty(0, dtype=np.int64)
        self._top = 0                     # next never-used arena slot
        self._w = np.zeros((cap, dim), dtype=dtype)
        self._slots = {n: np.zeros((cap, dim), dtype=np.float32)
                       for n in self.slot_names}
        self.last_touch = np.zeros((cap,), dtype=np.int64)
        self.touch_count = np.zeros((cap,), dtype=np.int64)
        # dirty-row bookkeeping for incremental checkpoints: a per-table
        # mutation clock stamped onto every written row, plus a log of
        # (clock, ids) evictions so a delta can replay deletes. Same
        # pattern as the streaming plane's touch/seq tracking, but keyed
        # to the table (not the queue) so checkpoints need no queue scan.
        self.row_version = np.zeros((cap,), dtype=np.int64)
        self._mut = 0
        self._evict_log: list[tuple[int, np.ndarray]] = []
        self._dev: Optional[_DeviceMirror] = None   # pallas: lazy mirror

    def _mirror(self) -> _DeviceMirror:
        if self._dev is None:
            self._dev = _DeviceMirror(self)
        return self._dev

    # -- capacity ---------------------------------------------------------
    def __len__(self) -> int:
        return len(self._map)

    def _grow(self, need: int) -> None:
        cap = self._w.shape[0]
        # powers of two (from a power-of-two start): tables that grow
        # alike share arena shapes, and so their compiled device programs
        new_cap = max(1 << (need - 1).bit_length(), cap * 2)
        def grow(a, fill=0):
            out = np.full((new_cap,) + a.shape[1:], fill, dtype=a.dtype)
            out[:cap] = a
            return out
        self._w = grow(self._w)
        self._slots = {n: grow(a) for n, a in self._slots.items()}
        self._id_of = grow(self._id_of, fill=_NO_ID)
        self.last_touch = grow(self.last_touch)
        self.touch_count = grow(self.touch_count)
        self.row_version = grow(self.row_version)

    def _alloc_slots(self, k: int) -> np.ndarray:
        """Pop ``k`` arena slots: freed slots first (LIFO), then fresh."""
        out = np.empty(k, dtype=np.int64)
        take = min(k, len(self._free))
        if take:
            out[:take] = self._free[len(self._free) - take:][::-1]
            self._free = self._free[:len(self._free) - take]
        fresh = k - take
        if fresh:
            out[take:] = np.arange(self._top, self._top + fresh)
            self._top += fresh
            if self._top > self._w.shape[0]:
                self._grow(self._top)
        return out

    # -- id resolution (batched, no per-row Python) -----------------------
    def ensure(self, ids: np.ndarray) -> np.ndarray:
        """Arena slots for ids, creating zeroed rows as needed. Lookup-first
        so the hot path (all rows exist) is a single batched probe — no
        dedup sort, no insert machinery."""
        ids = np.asarray(ids, dtype=np.int64)
        sl, found = self._map.lookup_mask(ids)
        if not found.all():
            sl = self._fill_missing(ids, sl, found)
        return sl

    def _fill_missing(self, ids: np.ndarray, sl: np.ndarray,
                      found: np.ndarray) -> np.ndarray:
        """Create zeroed rows for the ids ``found`` marks absent, patching
        their entries in ``sl`` (callers pass the probe result they already
        hold, so the miss path costs one probe, not two)."""
        miss = ~found
        new_ids = np.unique(ids[miss])            # sorted unique
        new_sl = self._alloc_slots(len(new_ids))
        self._map.insert(new_ids, new_sl)
        self._id_of[new_sl] = new_ids
        self._w[new_sl] = 0.0
        for a in self._slots.values():
            a[new_sl] = 0.0
        self.last_touch[new_sl] = 0
        self.touch_count[new_sl] = 0
        self._mut += 1
        self.row_version[new_sl] = self._mut
        sl[miss] = new_sl[np.searchsorted(new_ids, ids[miss])]
        return sl

    def lookup(self, ids: np.ndarray) -> np.ndarray:
        """Slots for existing ids; -1 where missing."""
        return self._map.lookup(np.asarray(ids, dtype=np.int64))

    def evict(self, ids: np.ndarray) -> int:
        """Batched row removal; freed slots are reused by later ensures."""
        uniq = np.unique(np.asarray(ids, dtype=np.int64))
        sl = self._map.lookup(uniq)
        have = sl >= 0
        if have.any():
            s = sl[have]
            self._map.delete(uniq[have])
            self._id_of[s] = _NO_ID
            self._free = np.concatenate([self._free, s])
            self._mut += 1
            self._evict_log.append((self._mut, uniq[have].copy()))
        return int(have.sum())

    # -- slot-level row access (shared by gather/scatter/apply_batch) -----
    def _fetch(self, arena: np.ndarray, sl: np.ndarray) -> np.ndarray:
        # the host arrays are authoritative on both backends.
        # take(mode="clip") with in-bounds-by-construction slots: ~an order
        # faster than arena[sl] (skips the bounds-checked gather path)
        if arena.shape[1] == 1:      # dim-1 rows (LR): element gather beats
            return arena.reshape(-1).take(sl, mode="clip")[:, None]  # row memcpys
        return arena.take(sl, axis=0, mode="clip")

    def read_rows(self, sl: np.ndarray, *, want_w: bool = True,
                  slot_names: Optional[tuple] = None):
        """(w, slots) for resolved arena slots — backend-routed gather.
        ``want_w=False`` / ``slot_names`` skip columns the caller will not
        read (the pusher's transform declares its inputs: an FTRL codec
        derives w from (z, n) and never touches the stored w; a plain
        weight codec never touches the slots). Skipped w is a (n, 0)
        placeholder so row counts stay consistent."""
        names = self.slot_names if slot_names is None else slot_names
        w = self._fetch(self._w, sl) if want_w else \
            np.empty((len(sl), 0), dtype=self.dtype)
        slots = {n: self._fetch(self._slots[n], sl) for n in names}
        return w, slots

    def write_rows(self, sl: np.ndarray, w: np.ndarray,
                   slots: Optional[dict] = None, *, step: int = 0) -> None:
        self._w[sl] = w
        if slots:
            for n, v in slots.items():
                self._slots[n][sl] = v
        self.last_touch[sl] = step
        self.touch_count[sl] += 1
        self._mut += 1
        self.row_version[sl] = self._mut

    # -- access -------------------------------------------------------------
    def gather(self, ids: np.ndarray, *, create: bool = False,
               want_w: bool = True, slot_names: Optional[tuple] = None):
        """Returns (w (n,dim), slots dict name->(n,dim)). Missing rows are
        zeros unless ``create``. ``want_w``/``slot_names`` select columns
        (see ``read_rows``)."""
        ids = np.asarray(ids, dtype=np.int64)
        if create:
            sl, found = self._map.lookup_mask(ids)
            if not found.all():               # rare: rows to create
                sl = self._fill_missing(ids, sl, found)
            return self.read_rows(sl, want_w=want_w, slot_names=slot_names)
        if (self.backend == "pallas" and want_w and len(ids)
                and not (self.slot_names if slot_names is None
                         else slot_names)):
            # fused device path: probe + gather in one jit against the
            # table mirror — the serve-lookup shape (w only, no slots)
            return self._gather_device(ids), {}
        sl = self.lookup(ids)
        ok = sl >= 0
        if ok.all():
            # hot path (pusher flushes gather the master's own dirty ids,
            # which always exist): plain read, no missing-row masking —
            # the np.where passes below would add ~2x the gather's memory
            # traffic for nothing
            return self.read_rows(sl, want_w=want_w, slot_names=slot_names)
        names = self.slot_names if slot_names is None else slot_names
        safe = np.where(ok, sl, 0)
        if want_w:
            w = self._fetch(self._w, safe)
            w = np.where(ok[:, None], w, np.zeros((), dtype=self.dtype))
        else:
            w = np.empty((len(sl), 0), dtype=self.dtype)
        slots = {}
        for n in names:
            v = self._fetch(self._slots[n], safe)
            slots[n] = np.where(ok[:, None], v, np.float32(0.0))
        return w, slots

    def scatter(self, ids: np.ndarray, w: np.ndarray,
                slots: Optional[dict] = None, *, step: int = 0) -> None:
        self.write_rows(self.ensure(ids), w, slots, step=step)

    def insert_rows(self, ids: np.ndarray, w: np.ndarray,
                    slots: Optional[dict] = None, *, step: int = 0) -> None:
        """Probe-free bulk install of rows whose ids are unique and KNOWN
        absent — e.g. the miss set a ``lookup`` just reported (the serve
        cache's fill path). Equivalent end state to ``scatter`` on absent
        ids, but skips its existence probe, the miss-path ``np.unique``
        re-sort, and the zero-init write the values immediately overwrite
        — the dominant costs of a cold cache fill."""
        ids = np.asarray(ids, dtype=np.int64)
        if not len(ids):
            return
        fresh = not len(self._free)
        sl = self._alloc_slots(len(ids))
        self._map.insert(ids, sl)
        # with an empty free list (the post-reset refill) the allocated
        # slots are one contiguous run — slice writes are straight memcpys
        # where fancy-index scatters pay per-element address math
        dst = slice(int(sl[0]), int(sl[0]) + len(ids)) if fresh else sl
        self._id_of[dst] = ids
        self._w[dst] = w
        if slots:
            for n, v in slots.items():
                self._slots[n][dst] = v
        else:
            for a in self._slots.values():
                a[dst] = 0.0
        self.last_touch[dst] = step
        self.touch_count[dst] = 1
        self._mut += 1
        self.row_version[dst] = self._mut

    def reset(self) -> None:
        """Empty the table but KEEP its allocations (map capacity, arena).
        A reset-and-refill consumer (serve-cache flush) then re-inserts
        into a presized map — no growth rehashes, and cold probes resolve
        on the EMPTY-home fast path. Arena contents are left stale: rows
        are unreachable once the map is cleared, and every (re)insert path
        writes before exposing a slot."""
        self._map.clear()
        self._id_of[:self._top] = _NO_ID
        self._free = np.empty(0, dtype=np.int64)
        self._top = 0
        self._mut += 1
        self._evict_log.clear()

    def lookup_device(self, ids: np.ndarray):
        """Serve-path rows via the device-resident mirror: one jitted
        probe→gather chain (``ops.fused_lookup``), missing rows zeros.
        Bit-equal to the host probe + gather (``tests/test_ps_backend``).

        Returns ``(rows, found, slot)`` where ``rows`` is the DEVICE
        array (callers that feed a jitted predict keep it on device — no
        host round-trip) and ``found``/``slot`` are small host arrays:
        the found mask comes off the device probe (the serve cache counts
        misses from it instead of re-probing on host) and ``slot`` lets
        LRU stats update without a host lookup."""
        from repro.kernels import ops
        mir = self._mirror()
        mir.sync()
        return ops.fused_lookup(mir.keys_lo, mir.keys_hi, mir.slot_of,
                                mir.arenas["w"], ids, shift=mir.shift,
                                placement=mir.placement)

    def _gather_device(self, ids: np.ndarray) -> np.ndarray:
        from repro.kernels import ops
        mir = self._mirror()
        mir.sync()
        rows = ops.fused_gather(mir.keys_lo, mir.keys_hi, mir.slot_of,
                                mir.arenas["w"], ids, shift=mir.shift,
                                placement=mir.placement)
        return rows.astype(self.dtype, copy=False)

    def mirror_metrics(self) -> Optional[dict]:
        """Device-mirror upload counters (None until a pallas path has
        touched this table) — aggregated into ``cluster.sync_metrics``."""
        return self._dev.metrics() if self._dev is not None else None

    def fused_ftrl_update(self, ids: np.ndarray, sl: np.ndarray,
                          grads: np.ndarray, *, alpha: float, beta: float,
                          l1: float, l2: float, step: int = 0) -> np.ndarray:
        """The fused sparse training hot path (pallas backend): one jitted
        probe→gather→FTRL→scatter chain over the device mirror — no host
        hop between stages. ``ids`` must be unique and already resolved to
        arena slots ``sl`` (``ensure`` ran: row creation stays host-side).
        The kernel's row outputs are written back to the host arrays at
        ``sl`` — both sides hold identical bits, so the mirror marks
        itself synced and the next batch uploads nothing but ids+grads.
        Returns the new serve weights ``w'`` for the rows."""
        from repro.kernels import ops
        tr = obs_trace.get_tracer()
        mir = self._mirror()
        with tr.span("ps.mirror_sync"):
            mir.sync()
        with tr.span("ps.ftrl"):
            z_a, n_a, w_a, z2, n2, w2, found = ops.fused_ftrl_apply(
                mir.keys_lo, mir.keys_hi, mir.slot_of,
                mir.arenas["z"], mir.arenas["n"], mir.arenas["w"], ids,
                grads, shift=mir.shift, alpha=alpha, beta=beta, l1=l1,
                l2=l2, placement=mir.placement)
        mir.arenas["z"], mir.arenas["n"], mir.arenas["w"] = z_a, n_a, w_a
        if not found.all():
            raise RuntimeError("fused_ftrl_update on ids absent from the "
                               "map (run ensure first)")
        w_np = w2.astype(self.dtype, copy=False)
        with tr.span("ps.write_back"):
            self.write_rows(sl, w_np, {"z": z2, "n": n2}, step=step)
        mir.mark_synced()
        return w_np

    def all_ids(self) -> np.ndarray:
        live = self._id_of[:self._top]
        return live[live != _NO_ID]

    def nbytes(self) -> int:
        live = len(self)
        per_row = self._w.itemsize * self.dim * (1 + len(self._slots))
        return live * per_row

    # -- snapshot (checkpointing) -------------------------------------------
    @property
    def version(self) -> int:
        """Mutation-clock reading; rows with ``row_version > v`` are dirty
        relative to a snapshot taken at clock ``v``."""
        return self._mut

    def snapshot(self) -> dict:
        ids = self.all_ids()
        sl = self.lookup(ids)                     # one probe for everything
        w, slots = self.read_rows(sl)
        return {"ids": ids, "w": w, "slots": slots,
                "last_touch": self.last_touch[sl].copy(),
                "touch_count": self.touch_count[sl].copy(),
                "version": self._mut}

    def delta_snapshot(self, since: int) -> dict:
        """Columnar snapshot of ONLY the rows written after clock ``since``
        plus the ids evicted after it — the payload of an incremental
        checkpoint. One vectorized scan of the reverse map + row_version;
        no hash probes."""
        live = self._id_of[:self._top] != _NO_ID
        sl = np.flatnonzero(live & (self.row_version[:self._top] > since))
        w, slots = self.read_rows(sl)
        dead = [ids for mut, ids in self._evict_log if mut > since]
        deleted = np.unique(np.concatenate(dead)) if dead else \
            np.empty(0, np.int64)
        return {"ids": self._id_of[sl].copy(), "w": w, "slots": slots,
                "last_touch": self.last_touch[sl].copy(),
                "touch_count": self.touch_count[sl].copy(),
                "deleted": deleted, "since": since, "version": self._mut}

    def trim_evict_log(self, before: int) -> None:
        """Drop eviction entries at or below clock ``before`` — safe once
        every future delta will be taken against a mark >= ``before``."""
        self._evict_log = [(m, i) for m, i in self._evict_log if m > before]

    def load_rows(self, rows: dict) -> None:
        """Bulk-insert snapshot rows whose ids are unique and NOT yet
        present — the restore hot path (tables start cleared). Skips the
        ensure probe, the miss-path np.unique sort, and the zero-init
        write that ``ensure`` + ``write_rows`` would pay."""
        ids = np.asarray(rows["ids"], dtype=np.int64)
        if not len(ids):
            return
        sl = self._alloc_slots(len(ids))
        self._map.insert(ids, sl)
        self._id_of[sl] = ids
        self._w[sl] = rows["w"]
        for n, v in rows["slots"].items():
            self._slots[n][sl] = v
        self.last_touch[sl] = rows["last_touch"]
        self.touch_count[sl] = rows["touch_count"]
        self._mut += 1
        self.row_version[sl] = self._mut

    @classmethod
    def restore(cls, snap: dict, dim: int, slot_names: tuple[str, ...],
                dtype=np.float32, backend: str = "numpy") -> "SparseTable":
        t = cls(dim, slot_names, init_capacity=max(16, len(snap["ids"])),
                dtype=dtype, backend=backend)
        t.load_rows(snap)                 # probe-free insert: table is new
        return t


@dataclass
class DenseBank:
    """Named dense tensors (DNN hidden layers etc.) with version counters.
    A tensor may be a device array (a tower the training plane updates on
    the device); snapshots hold host numpy copies of every tensor."""

    tensors: dict[str, np.ndarray | jax.Array] = field(default_factory=dict)
    slots: dict[str, dict[str, np.ndarray | jax.Array]] = field(
        default_factory=dict)
    versions: dict[str, int] = field(default_factory=dict)

    def put(self, name: str, value: np.ndarray | jax.Array,
            slots: Optional[dict] = None) -> None:
        self.tensors[name] = value
        if slots is not None:
            self.slots[name] = slots
        self.versions[name] = self.versions.get(name, 0) + 1

    def snapshot(self) -> dict:
        return self._host_copy(list(self.tensors))

    def snapshot_delta(self, since: dict[str, int]) -> dict:
        """Same format as ``snapshot`` but holding only tensors whose
        version counter moved past ``since[name]``."""
        return self._host_copy([k for k, v in self.versions.items()
                                if v > since.get(k, -1)])

    def _host_copy(self, names: list) -> dict:
        """Host numpy copies of the named tensors and their slots: host
        arrays copied, device arrays read back together in one blocking
        read."""
        flat = {(k, None): self.tensors[k] for k in names}
        flat.update({(k, n): a for k in names
                     for n, a in self.slots.get(k, {}).items()})
        dev = [key for key, a in flat.items() if isinstance(a, jax.Array)]
        read = dict(zip(dev, to_host(*(flat[key] for key in dev)))
                    if dev else ())
        host = {key: read[key] if key in read else a.copy()
                for key, a in flat.items()}
        return {
            "tensors": {k: host[k, None] for k in names},
            "slots": {k: {n: host[k, n] for n in self.slots[k]}
                      for k in names if k in self.slots},
            "versions": {k: self.versions[k] for k in names},
        }

    @classmethod
    def restore(cls, snap: dict) -> "DenseBank":
        return cls(tensors=dict(snap["tensors"]),
                   slots={k: dict(v) for k, v in snap["slots"].items()},
                   versions=dict(snap["versions"]))


class MasterShard:
    """Training-side PS shard: sparse groups with optimizer slots + a dense
    bank. Gradient pushes update rows through the optimizer and notify the
    collector (dirty IDs only — paper §4.1.1)."""

    def __init__(self, shard_id: int, groups: dict[str, int],
                 optimizer: Optimizer, collector=None,
                 backend: str = "numpy"):
        """groups: {group_name: row_dim}"""
        self.shard_id = shard_id
        self.optimizer = optimizer
        self.backend = backend
        self.tables = {
            g: SparseTable(dim, tuple(sorted(
                optimizer.init_slots(np.zeros((dim,), np.float32)).keys())),
                backend=backend)
            for g, dim in groups.items()
        }
        self.dense = DenseBank()
        self.collector = collector
        self.step = 0
        self.fused_batches = 0      # pushes taken by the fused device path
        self.alive = True

    def add_group(self, group: str, dim: int) -> None:
        """Create a new sparse group online (multi-scenario training: an
        isolated scenario's namespaced tables appear after construction).
        Idempotent for an existing group of the same dim."""
        if group in self.tables:
            assert self.tables[group].dim == dim, \
                f"group {group!r} exists with dim {self.tables[group].dim}"
            return
        self.tables[group] = SparseTable(dim, tuple(sorted(
            self.optimizer.init_slots(
                np.zeros((dim,), np.float32)).keys())), backend=self.backend)

    def pull(self, group: str, ids: np.ndarray, *, create: bool = True):
        """Trainer pull: returns current *training* weights for ids."""
        assert self.alive, f"master shard {self.shard_id} is down"
        w, _ = self.tables[group].gather(ids, create=create)
        return w

    def apply_batch(self, group: str, ids: np.ndarray, grads: np.ndarray,
                    *, step: Optional[int] = None) -> np.ndarray:
        """The fused PS hot path: one batched hash → gather → optimizer
        update → scatter pass for a whole minibatch. Duplicate ids are
        deduplicated with their gradients summed (the correct sparse-grad
        semantics). Returns the unique ids touched. The pass is a
        ``ps.apply`` span of ``repro.obs.trace``, its stages ``ps.*``
        spans under it."""
        with obs_trace.get_tracer().span("ps.apply"):
            return self._apply_batch(group, ids, grads, step=step)

    def _apply_batch(self, group: str, ids: np.ndarray, grads: np.ndarray,
                     *, step: Optional[int]) -> np.ndarray:
        assert self.alive, f"master shard {self.shard_id} is down"
        tr = obs_trace.get_tracer()
        t = self.tables[group]
        st = self.step if step is None else step
        ids = np.asarray(ids, dtype=np.int64)
        grads = np.asarray(grads, dtype=np.float32)
        with tr.span("ps.dedup"):
            uniq, inv, counts = np.unique(ids, return_inverse=True,
                                          return_counts=True)
            if len(uniq) != len(ids):
                # segment-sum duplicate-id grads (sort + reduceat: orders
                # of magnitude faster than np.add.at's buffered
                # scatter-add)
                order = np.argsort(inv, kind="stable")
                starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
                grads = np.add.reduceat(
                    grads.take(order, axis=0, mode="clip"), starts, axis=0)
            elif len(ids) > 1 and not (ids[1:] >= ids[:-1]).all():
                # unique but unsorted: slots are resolved for sorted
                # ``uniq``, so grad rows must be permuted to match
                grads = grads.take(np.argsort(inv, kind="stable"), axis=0,
                                   mode="clip")
        with tr.span("ps.ensure"):
            sl = t.ensure(uniq)
        if (self.backend == "pallas" and isinstance(self.optimizer, FTRL)
                and t.slot_names == ("n", "z")):
            # fused device route: ensure resolved/created the rows on the
            # host (authoritative side), then probe→gather→FTRL→scatter
            # runs as one jitted chain over the table's device mirror
            o = self.optimizer
            t.fused_ftrl_update(uniq, sl, grads, alpha=o.alpha, beta=o.beta,
                                l1=o.l1, l2=o.l2, step=st)
            self.fused_batches += 1
        else:
            w, slots = t.read_rows(sl)
            new_w, new_slots = self.optimizer.update_rows(
                w, slots, grads, st, backend=self.backend)
            t.write_rows(sl, new_w.astype(t.dtype, copy=False), new_slots,
                         step=st)
        self.step = st + 1
        if self.collector is not None:
            self.collector.record(group, uniq, "upsert")
        return uniq

    def push_grad(self, group: str, ids: np.ndarray, grads: np.ndarray,
                  *, step: Optional[int] = None) -> None:
        """Apply gradient rows through the optimizer; record dirty IDs."""
        self.apply_batch(group, ids, grads, step=step)

    def push_dense(self, name: str, value: np.ndarray | jax.Array,
                   slots: Optional[dict] = None) -> None:
        assert self.alive
        self.dense.put(name, value, slots)
        if self.collector is not None:
            self.collector.record_dense(name)

    def delete_rows(self, group: str, ids: np.ndarray) -> None:
        """Feature-filter expiry: remove rows and emit delete records."""
        self.tables[group].evict(ids)
        if self.collector is not None:
            self.collector.record(group, ids, "delete")

    def register_metrics(self, reg, prefix: str = "") -> None:
        """Publish this shard's counters into a
        ``repro.obs.metrics.MetricsRegistry`` (dotted under ``prefix``
        when given; per-table ``_DeviceMirror`` sync counters under
        ``<prefix>device_mirror.<group>``)."""
        from repro.obs.metrics import join
        reg.register(join(prefix, "step"), lambda: self.step)
        reg.register(join(prefix, "fused_batches"),
                     lambda: self.fused_batches)
        reg.register(join(prefix, "rows"),
                     lambda: {g: len(t) for g, t in self.tables.items()})
        reg.register(join(prefix, "device_mirror"),
                     lambda: {g: m for g, t in self.tables.items()
                              if (m := t.mirror_metrics()) is not None})

    # -- fault tolerance ---------------------------------------------------
    def snapshot(self) -> dict:
        return {
            "shard_id": self.shard_id,
            "step": self.step,
            "kind": "full",
            "tables": {g: t.snapshot() for g, t in self.tables.items()},
            "dense": self.dense.snapshot(),
        }

    def delta_snapshot(self, marks: dict[str, int],
                       dense_marks: dict[str, int]) -> dict:
        """Incremental snapshot: per group, only the rows written after
        ``marks[group]`` (the table's mutation clock at the previous
        checkpoint) plus the ids evicted since; dense tensors only where
        the version counter moved."""
        return {
            "shard_id": self.shard_id,
            "step": self.step,
            "kind": "delta",
            "tables": {g: t.delta_snapshot(marks.get(g, 0))
                       for g, t in self.tables.items()},
            "dense": self.dense.snapshot_delta(dense_marks),
        }

    def load_table_rows(self, group: str, rows: dict) -> None:
        """Bulk-load columnar rows (ids/w/slots + touch stats) into one
        group — the unit the vectorized recovery router emits. An empty
        table takes the probe-free ``SparseTable.load_rows`` insert; a
        live table (merging load) falls back to ensure + write."""
        if not len(rows["ids"]):
            return
        t = self.tables[group]
        if len(t) == 0:
            t.load_rows(rows)
            return
        sl = t.ensure(rows["ids"])
        t.write_rows(sl, rows["w"], rows["slots"])
        t.last_touch[sl] = rows["last_touch"]
        t.touch_count[sl] = rows["touch_count"]

    def load_snapshot(self, snap: dict, *, ids_filter=None) -> None:
        self.step = snap["step"]
        for g, tsnap in snap["tables"].items():
            rows = {k: tsnap[k] for k in
                    ("ids", "w", "slots", "last_touch", "touch_count")}
            if ids_filter is not None:
                keep = ids_filter(rows["ids"])
                rows = {"slots": {k: v[keep]
                                  for k, v in rows["slots"].items()},
                        **{k: rows[k][keep] for k in
                           ("ids", "w", "last_touch", "touch_count")}}
            self.load_table_rows(g, rows)
        # a filtered load is a partial/routed restore — table rows only;
        # dense tensors follow the unfiltered owner-shard load
        if ids_filter is None and snap.get("dense") is not None:
            self.dense = DenseBank.restore(snap["dense"])

    def kill(self) -> None:
        self.alive = False

    def clear(self) -> None:
        for g, t in list(self.tables.items()):
            self.tables[g] = SparseTable(t.dim, t.slot_names, dtype=t.dtype,
                                         backend=t.backend)
        self.dense = DenseBank()


class SlaveShard:
    """Serving-side PS shard: inference weights only, idempotent versioned
    application of stream records (last-writer-wins by ``seq``)."""

    def __init__(self, shard_id: int, groups: dict[str, int],
                 backend: str = "numpy"):
        self.shard_id = shard_id
        self.backend = backend
        self.tables = {g: SparseTable(dim, backend=backend)
                       for g, dim in groups.items()}
        self.dense: dict[str, np.ndarray] = {}
        self.dense_versions: dict[str, int] = {}
        # (group, producer, partition) -> last applied seq, for LWW
        # idempotence. Keyed per partition stream: ids route to
        # partitions deterministically, so partitions are independent
        # ordered streams — a flush that touches only partition p must
        # not mark another partition's in-flight records stale.
        self._applied_seq: dict[tuple[str, int, int], int] = {}
        # serving-plane invalidation hook: called with (group, ids, op)
        # for every applied sparse batch, so predictor-side caches can
        # drop rows the stream just rewrote (deletes included). Dense
        # records need no hook — they carry a version counter the dense
        # cache compares directly.
        self.on_apply = None
        self.alive = True
        self.applied_records = 0
        self.skipped_records = 0

    def add_group(self, group: str, dim: int) -> None:
        """Create a new serve group online (mirrors
        ``MasterShard.add_group`` so scenario tables stream through the
        scatter like any other group)."""
        if group in self.tables:
            assert self.tables[group].dim == dim, \
                f"group {group!r} exists with dim {self.tables[group].dim}"
            return
        self.tables[group] = SparseTable(dim, backend=self.backend)

    @staticmethod
    def _seq_key(record) -> tuple[str, int, int]:
        return (record.group, record.producer,
                record.meta.get("partition", -1))

    def apply(self, record) -> bool:
        """Apply one stream record; returns False if skipped (stale)."""
        assert self.alive, f"slave shard {self.shard_id} is down"
        key = self._seq_key(record)
        last = self._applied_seq.get(key, -1)
        # strictly-older records are stale (LWW). Equal-seq records are
        # sibling chunks of the SAME flush covering disjoint IDs (or exact
        # redeliveries, which are idempotent full-value upserts) — apply.
        if record.seq < last:
            self.skipped_records += 1
            return False
        from repro.core.transform import decode_record
        if record.group.startswith("dense/"):
            name = record.group[len("dense/"):]
            ver = int(record.ids[0])
            if self.dense_versions.get(name, -1) < ver:
                self.dense[name] = decode_record(record).reshape(
                    record.meta.get("shape", (1, -1)))
                self.dense_versions[name] = ver
        elif record.op == "delete":
            self.tables[record.group].evict(record.ids)
            if self.on_apply is not None:
                self.on_apply(record.group, record.ids, "delete")
        else:
            values = decode_record(record)
            self.tables[record.group].scatter(record.ids, values)
            if self.on_apply is not None:
                self.on_apply(record.group, record.ids, "upsert")
        self._applied_seq[key] = max(last, record.seq)
        self.applied_records += 1
        return True

    def apply_batch(self, records: list) -> list:
        """Batched idempotent application of a poll's worth of records:
        sparse upserts are coalesced per group into ONE decoded value block
        and ONE ``SparseTable.scatter`` (concatenation preserves arrival
        order, so overlapping ids within the batch resolve last-writer-wins
        exactly like sequential ``apply`` — numpy fancy assignment writes
        the later occurrence). Dense records and deletes are versioned /
        destructive and keep the singleton ``apply`` path. Returns the
        records actually applied (stale ones are skipped and counted)."""
        assert self.alive, f"slave shard {self.shard_id} is down"
        from repro.core.transform import decode_record
        applied: list = []
        rows: dict[str, tuple[list, list]] = {}

        def flush(group) -> None:
            ids_l, val_l = rows.pop(group)
            ids = ids_l[0] if len(ids_l) == 1 else np.concatenate(ids_l)
            vals = val_l[0] if len(val_l) == 1 else \
                np.concatenate(val_l, axis=0)
            self.tables[group].scatter(ids, vals)
            if self.on_apply is not None:
                self.on_apply(group, ids, "upsert")

        for rec in records:
            if rec.group.startswith("dense/") or rec.op == "delete":
                # a delete must not overtake coalesced-but-unwritten
                # upserts for its group (the deferred scatter would
                # resurrect the evicted rows) — flush those first
                if rec.op == "delete" and rec.group in rows:
                    flush(rec.group)
                if self.apply(rec):
                    applied.append(rec)
                continue
            key = self._seq_key(rec)
            last = self._applied_seq.get(key, -1)
            if rec.seq < last:
                self.skipped_records += 1
                continue
            ids_l, val_l = rows.setdefault(rec.group, ([], []))
            ids_l.append(rec.ids)
            val_l.append(decode_record(rec))
            self._applied_seq[key] = max(last, rec.seq)
            self.applied_records += 1
            applied.append(rec)
        for group in list(rows):
            flush(group)
        return applied

    def lookup(self, group: str, ids: np.ndarray) -> np.ndarray:
        """Latency-path query: serve weights (missing rows -> zeros)."""
        assert self.alive, f"slave shard {self.shard_id} is down"
        w, _ = self.tables[group].gather(ids, create=False)
        return w

    def register_metrics(self, reg, prefix: str = "") -> None:
        """Publish this shard's apply counters into a
        ``repro.obs.metrics.MetricsRegistry``."""
        from repro.obs.metrics import join
        reg.register(join(prefix, "applied"), lambda: self.applied_records)
        reg.register(join(prefix, "skipped"), lambda: self.skipped_records)
        reg.register(join(prefix, "rows"),
                     lambda: {g: len(t) for g, t in self.tables.items()})

    # -- hot backup ----------------------------------------------------------
    def full_sync_from(self, other: "SlaveShard") -> None:
        """Bootstrap a fresh replica: full copy then streaming catch-up."""
        for g, t in other.tables.items():
            snap = t.snapshot()
            self.tables[g] = SparseTable.restore(
                snap, t.dim, (), dtype=t.dtype, backend=self.backend)
        self.dense = {k: v.copy() for k, v in other.dense.items()}
        self.dense_versions = dict(other.dense_versions)
        self._applied_seq = dict(other._applied_seq)

    def kill(self) -> None:
        self.alive = False

    def revive(self) -> None:
        self.alive = True
