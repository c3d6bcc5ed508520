"""Version-aware serve caches (predictor side).

``ServeCache`` short-circuits the shard pull for hot ids: one
``IdHashMap``-backed arena (reusing ``SparseTable`` — the same vectorized
probe/gather engine the shards run) stores, per row id, the columns of
EVERY group the scenario reads side by side, so a cached request costs
ONE probe + ONE gather regardless of group count.  Entries are
invalidated by the scatter stream's applied-id batches (upserts AND
streamed deletes — wired through ``SlaveShard.on_apply``), which keeps
cached reads bit-equal to direct replica reads once the stream has been
polled: a row the stream rewrote is dropped here before any predictor
can read it stale.

``DenseCache`` memoizes dense tensors by their sync version counter —
the predict path re-reshapes a dense tensor only when a newer version
actually streamed in, instead of re-pulling every tensor per request.
"""

from __future__ import annotations

from typing import Callable, Optional

import numpy as np

from repro.core.ps import SparseTable
from repro.obs import trace as obs_trace


class ServeCache:
    """Combined-group row cache keyed by id, invalidated by the stream."""

    def __init__(self, groups: dict[str, int], max_rows: int = 1 << 20,
                 backend: str = "numpy"):
        self.groups = dict(groups)
        self.offsets: dict[str, tuple[int, int]] = {}
        lo = 0
        for g, dim in self.groups.items():
            self.offsets[g] = (lo, lo + dim)
            lo += dim
        self.width = lo
        self.max_rows = max_rows
        self.table = SparseTable(self.width, backend=backend,
                                 init_capacity=1024)
        self._tick = 0
        self.hits = 0
        self.misses = 0
        self.invalidated = 0
        self.trims = 0
        # counter snapshot at the last window_stats() read — windowed
        # deltas without ever resetting the lifetime counters
        self._window_mark = {"hits": 0, "misses": 0, "invalidated": 0,
                             "trims": 0}

    def __len__(self) -> int:
        return len(self.table)

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    def lookup(self, ids: np.ndarray) -> tuple[Optional[np.ndarray],
                                               np.ndarray]:
        """(block (n, width), hit mask). Rows where the mask is False are
        zeros — the caller pulls them from the shards and ``fill``s.
        ``block`` is None when NOTHING hit (the fully-cold caller builds
        its own block from the pull; allocating one here would be pure
        waste on exactly the cold path)."""
        self._tick += 1
        if not len(self.table):
            # cold cache (just cleared / first request): every id misses.
            # Skip the whole-request probe against an all-EMPTY map — on
            # the cold-pull path this probe is pure overhead the seed
            # (cacheless) never pays.
            self.misses += len(ids)
            return None, np.zeros(len(ids), dtype=bool)
        sl = self.table.lookup(ids)
        hit = sl >= 0
        n_hit = int(hit.sum())
        self.hits += n_hit
        self.misses += len(ids) - n_hit
        if n_hit == len(ids):
            # hot path (steady-state serving): every id cached — straight
            # gather, no zeros allocation, no masked scatter-copy
            w, _ = self.table.read_rows(sl)
            self.table.last_touch[sl] = self._tick      # LRU signal
            return w, hit
        if n_hit == 0:
            return None, hit
        block = np.zeros((len(ids), self.width), np.float32)
        s = sl[hit]
        w, _ = self.table.read_rows(s)
        block[hit] = w
        self.table.last_touch[s] = self._tick
        return block, hit

    def lookup_device(self, ids: np.ndarray):
        """Device-resident twin of ``lookup`` (pallas backend): ONE jitted
        probe→gather against the cache table's device mirror returns the
        combined-group block as a DEVICE array plus the probe's found
        mask — misses are counted straight off that mask instead of
        re-probing on host, and the block never round-trips through host
        numpy on its way to the jitted predict. Rows where the mask is
        False are zeros (the caller pulls and ``fill``s them, then
        overlays — see ``ServingPlane._pull_request_device``). Returns
        ``(block | None, hit)`` with the same cold-path contract as
        ``lookup``. Hits/misses feed the same lifetime + window counters
        as the host path."""
        self._tick += 1
        if not len(self.table):
            self.misses += len(ids)
            return None, np.zeros(len(ids), dtype=bool)
        rows, hit, slot = self.table.lookup_device(ids)
        n_hit = int(hit.sum())
        self.hits += n_hit
        self.misses += len(ids) - n_hit
        if n_hit == 0:
            return None, hit
        self.table.last_touch[slot[hit]] = self._tick       # LRU signal
        return rows, hit

    def fill(self, ids: np.ndarray, block: np.ndarray) -> None:
        """Install pulled rows — the UNIQUE MISS SET of the ``lookup``
        that preceded this call, so the ids are known absent and the
        install is a probe-free ``insert_rows`` (the cold-pull fix: no
        re-probe, no re-sort, no zero-init of rows the block overwrites).
        Trims least-recently-touched rows once the arena outgrows
        ``max_rows`` — the cache stays bounded no matter how wide the
        request id distribution is."""
        if not len(ids):
            return
        self.table.insert_rows(ids, block, step=self._tick)
        if len(self.table) > self.max_rows:
            self._trim()

    def _trim(self) -> None:
        ids = self.table.all_ids()
        drop = len(ids) - self.max_rows // 2
        if drop <= 0:
            return
        sl = self.table.lookup(ids)
        oldest = np.argpartition(self.table.last_touch[sl], drop)[:drop]
        self.table.evict(ids[oldest])
        self.table.trim_evict_log(self.table.version)
        self.trims += 1

    def invalidate(self, ids: np.ndarray) -> int:
        """Drop rows the stream just rewrote or deleted."""
        if not len(self.table):
            return 0        # nothing cached: keep the training-only
            #                 sync_tick path free of probe work
        tr = obs_trace.get_tracer()
        if tr.enabled:
            # nests under the sync.apply span via the tracer's implicit
            # context (SlaveShard.on_apply fires inside the apply) —
            # this is the cache-visible end of the update's causal chain
            with tr.span("cache.invalidate", ids=len(ids)):
                return self._invalidate(ids)
        return self._invalidate(ids)

    def _invalidate(self, ids: np.ndarray) -> int:
        n = self.table.evict(ids)
        if n:
            # a cache is never checkpointed: its table's eviction log
            # (delta-checkpoint machinery) would otherwise grow with
            # every stream invalidation, forever
            self.table.trim_evict_log(self.table.version)
        self.invalidated += n
        return n

    def clear(self) -> None:
        """Full flush — hot switch / downgrade rebuilds serving state
        wholesale, so every cached row is suspect. Keeps the grown arena
        and map capacity (``SparseTable.reset``): the refill after a flush
        re-installs roughly the same working set, so reallocating at 1024
        rows only re-pays every growth step."""
        self.table.reset()

    def split(self, block: np.ndarray) -> dict[str, np.ndarray]:
        """Carve a combined block back into per-group column views."""
        return {g: block[:, lo:hi] for g, (lo, hi) in self.offsets.items()}

    def stats(self) -> dict:
        return {"rows": len(self), "hits": self.hits, "misses": self.misses,
                "hit_rate": self.hit_rate, "invalidated": self.invalidated,
                "trims": self.trims}

    def register_metrics(self, reg, prefix: str = "cache") -> None:
        """Publish the lifetime counters under ``prefix`` in a
        ``repro.obs.metrics.MetricsRegistry``."""
        reg.register(prefix, self.stats)

    def window_stats(self) -> dict:
        """Counter deltas since the previous ``window_stats`` call, then
        start a new window. Lifetime counters (``stats``) are untouched,
        so per-window hit rates and end-of-run totals can both be read."""
        cur = {"hits": self.hits, "misses": self.misses,
               "invalidated": self.invalidated, "trims": self.trims}
        out = {k: cur[k] - self._window_mark[k] for k in cur}
        n = out["hits"] + out["misses"]
        out["hit_rate"] = out["hits"] / n if n else 0.0
        out["rows"] = len(self)
        self._window_mark = cur
        return out


class DenseCache:
    """Dense tensors memoized by sync version — one reshape per version,
    not one pull per predict (the seed re-read every tensor per call)."""

    def __init__(self):
        self._cached: dict[str, tuple[int, np.ndarray]] = {}
        self.hits = 0
        self.refreshes = 0
        self.invalidated = 0        # clear() calls (hot switch / downgrade)
        self._window_mark = {"hits": 0, "refreshes": 0, "invalidated": 0}

    def get(self, name: str, shape: tuple[int, ...], version: int,
            fetch: Callable[[], Optional[np.ndarray]]) -> np.ndarray:
        cur = self._cached.get(name)
        # >= : with round-robin replica picks, a lagging replica may
        # report an OLDER version than what is cached — serving the
        # cached newer tensor is both fresher and stable (versions only
        # move backwards on hot switch, which clear()s this cache)
        if cur is not None and cur[0] >= version:
            self.hits += 1
            return cur[1]
        v = fetch()
        arr = (np.asarray(v, np.float32).reshape(shape) if v is not None
               else np.zeros(shape, np.float32))
        self._cached[name] = (version, arr)
        self.refreshes += 1
        return arr

    def clear(self) -> None:
        self._cached = {}
        self.invalidated += 1

    def stats(self) -> dict:
        """Same shape family as ``ServeCache.stats`` so sparse and dense
        cache health surface uniformly: a dense "miss" is a refresh
        (version moved → re-fetch)."""
        n = self.hits + self.refreshes
        return {"rows": len(self._cached), "hits": self.hits,
                "misses": self.refreshes,
                "hit_rate": self.hits / n if n else 0.0,
                "invalidated": self.invalidated}

    def window_stats(self) -> dict:
        cur = {"hits": self.hits, "refreshes": self.refreshes,
               "invalidated": self.invalidated}
        out = {"hits": cur["hits"] - self._window_mark["hits"],
               "misses": cur["refreshes"] - self._window_mark["refreshes"],
               "invalidated": (cur["invalidated"]
                               - self._window_mark["invalidated"]),
               "rows": len(self._cached)}
        n = out["hits"] + out["misses"]
        out["hit_rate"] = out["hits"] / n if n else 0.0
        self._window_mark = cur
        return out
