"""ModelSyncEngine: the WeiPS streaming-sync mechanism applied to the
architecture zoo — second-level deployment of a training LM/MoE/SSM state
to a serving replica through the partitioned queue.

Granularity per parameter kind (DESIGN.md §4):
  * ``embed``             — token-ID rows (dirty = unique tokens seen in the
                            gather window; embedding grads are row-sparse);
  * MoE expert tensors    — (layer, repeat, expert) granularity, dirty =
                            experts actually routed-to in the window (from
                            ``expert_counts_per_layer``);
  * everything else       — tensor granularity with version counters
                            (every train step bumps versions; the gather
                            window dedups them — the paper's ≥90 %%
                            repetition effect).

Beyond-paper extension (§Perf): ``delta_threshold`` — the pusher keeps a
shadow of the last-pushed value and skips tensors/rows whose relative
change is below the threshold, with a periodic full refresh. This is a
bandwidth/staleness trade the paper's full-value-per-ID consistency
contract makes safe (skipped pushes are never *wrong*, only stale).

Backends: ``SyncConfig.codec_backend="pallas"`` routes the int8 codec's
quantize through the ``delta_codec`` kernel (``docs/KERNELS.md``) and
dequantizes on the host — bit-identical to the numpy mirror, so producer
and consumer may run different backends. The model states synced here
are dense jax pytrees, not PS tables, so the sparse fused path
(probe→gather→update→scatter, ``ClusterConfig.ps_backend``) does not
apply; rows enter the queue already device-materialized.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MOE, ModelConfig
from repro.core.queue import Consumer, PartitionedQueue, Record
from repro.core.streaming import Gatherer
from repro.core.transform import Transform, decode_record, make_transform

PyTree = Any


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _is_expert_leaf(cfg: ModelConfig, path: str, leaf) -> bool:
    """MoE expert tensors: segments/*/pos*/ffn/w_* with (R, E, ...) shape."""
    if cfg.num_experts == 0 or "/ffn/" not in path:
        return False
    name = path.rsplit("/", 1)[-1]
    return name in ("w_gate", "w_up", "w_down") and leaf.ndim >= 3 \
        and leaf.shape[1] == cfg.num_experts


@dataclass
class SyncConfig:
    num_partitions: int = 8
    num_slaves: int = 1
    gather_mode: str = "period"
    period: float = 1.0
    threshold: int = 1 << 20
    codec: str = "cast16"
    codec_backend: str = "numpy"      # numpy | pallas (delta_codec kernel)
    delta_threshold: float = 0.0      # 0 = push every dirty item
    full_refresh_every: int = 0       # flushes between forced full pushes
    embed_row_chunk: int = 65536
    # "window": dirty embed rows = tokens in the gather window (exact for
    # momentum-free optimizers: sgd/adagrad/ftrl/adafactor leave untouched
    # rows unchanged). "cumulative": Adam/Momentum keep decaying previously
    # touched rows every step, so every ever-touched row is dirty.
    embed_dirty: str = "auto"         # auto | window | cumulative


class ServeReplica:
    """Slave-side full-model state: applies stream records into a host
    param tree; ``device_params`` materializes it (possibly onto a serving
    mesh with different shardings — model routing for the dense plane)."""

    def __init__(self, cfg: ModelConfig, params_like: PyTree,
                 bootstrap: bool = True, codec_backend: str = "numpy"):
        """``bootstrap`` performs the paper's full synchronization (replica
        attach = checkpoint copy); streaming covers deltas thereafter."""
        self.cfg = cfg
        self.codec_backend = codec_backend
        leaves, self.treedef = jax.tree_util.tree_flatten_with_path(
            params_like)
        self.paths = [_path_str(p) for p, _ in leaves]
        self.host: dict[str, np.ndarray] = {
            path: (np.array(leaf, dtype=np.float32, copy=True) if bootstrap
                   else np.zeros(leaf.shape, np.float32))
            for path, (_, leaf) in zip(self.paths, leaves)}
        self._applied_seq: dict[tuple[str, int], int] = {}
        self.applied = 0
        self.versions: dict[str, int] = {}

    def apply(self, rec: Record) -> bool:
        key = (rec.group, rec.producer)
        if rec.seq < self._applied_seq.get(key, -1):    # strictly older only
            return False
        values = decode_record(rec, backend=self.codec_backend)
        kind = rec.meta["kind"]
        path = rec.meta["path"]
        if kind == "dense":
            ver = int(rec.ids[0])
            if self.versions.get(path, -1) < ver:
                self.host[path] = values.reshape(self.host[path].shape)
                self.versions[path] = ver
        elif kind == "rows":                      # embed rows
            self.host[path][rec.ids] = values
        elif kind == "experts":                   # ids = rep * E + expert
            arr = self.host[path]
            r_idx, e_idx = rec.ids // self.cfg.num_experts, \
                rec.ids % self.cfg.num_experts
            arr[r_idx, e_idx] = values.reshape(
                (len(rec.ids),) + arr.shape[2:])
        self._applied_seq[key] = rec.seq
        self.applied += 1
        return True

    def apply_batch(self, recs: list) -> int:
        """Batched application of a poll's worth of records: row-kind
        records are coalesced per path into ONE fancy-indexed write
        (concatenation preserves arrival order, so overlapping ids resolve
        last-writer-wins exactly like sequential ``apply``); dense/expert
        records keep the singleton path. Returns #records applied."""
        applied = 0
        rows_by_path: dict[str, tuple[list, list]] = {}
        for rec in recs:
            if rec.meta.get("kind") == "rows":
                key = (rec.group, rec.producer)
                if rec.seq < self._applied_seq.get(key, -1):
                    continue
                ids_l, val_l = rows_by_path.setdefault(
                    rec.meta["path"], ([], []))
                ids_l.append(rec.ids)
                val_l.append(decode_record(rec, backend=self.codec_backend))
                self._applied_seq[key] = rec.seq
                self.applied += 1
                applied += 1
            else:
                applied += int(self.apply(rec))
        for path, (ids_l, val_l) in rows_by_path.items():
            ids = np.concatenate(ids_l)
            vals = np.concatenate(val_l, axis=0)
            self.host[path][ids] = vals
        return applied

    def device_params(self, dtype: str = "bfloat16",
                      shardings: Optional[PyTree] = None) -> PyTree:
        dt = jnp.dtype(dtype)
        leaves = [jnp.asarray(self.host[p], dtype=dt) for p in self.paths]
        tree = jax.tree_util.tree_unflatten(self.treedef, leaves)
        if shardings is not None:
            tree = jax.device_put(tree, shardings)
        return tree

    def staleness(self, train_params: PyTree) -> float:
        """Max relative L2 distance to the (transformed) training params —
        the eventual-consistency measure the tests assert goes to ~0."""
        worst = 0.0
        flat, _ = jax.tree_util.tree_flatten_with_path(train_params)
        for p, leaf in flat:
            path = _path_str(p)
            a = np.asarray(leaf, dtype=np.float32)
            b = self.host[path]
            denom = max(float(np.linalg.norm(a)), 1e-9)
            worst = max(worst, float(np.linalg.norm(a - b)) / denom)
        return worst


class ModelSyncEngine:
    """Master-side collect/gather/push + slave replicas, full-model scale."""

    _MOMENTUM_OPTS = ("adam", "momentum")

    def __init__(self, cfg: ModelConfig, params: PyTree,
                 sync: Optional[SyncConfig] = None, queue=None):
        """``queue`` injects an external transport with the
        ``PartitionedQueue`` interface (e.g. a durable ``FileQueue``
        shared across processes); by default the engine owns an
        in-memory queue, matching the single-process wiring."""
        self.cfg = cfg
        self.sync = sync or SyncConfig()
        s = self.sync
        self._embed_mode = s.embed_dirty
        if self._embed_mode == "auto":
            self._embed_mode = ("cumulative" if cfg.optimizer in
                                self._MOMENTUM_OPTS else "window")
        self._embed_touched: set[int] = set()
        # momentum optimizers keep updating previously-routed experts too
        self._expert_touched: dict[str, set[int]] = {}
        if queue is not None:
            assert queue.num_partitions == s.num_partitions, \
                "injected queue partition count must match SyncConfig"
        self.queue = queue if queue is not None else \
            PartitionedQueue(s.num_partitions)
        self.transform = make_transform(s.codec, backend=s.codec_backend)
        self.gatherer = Gatherer(s.gather_mode, threshold=s.threshold,
                                 period=s.period)
        leaves, self.treedef = jax.tree_util.tree_flatten_with_path(params)
        self.paths = [_path_str(p) for p, _ in leaves]
        self.kinds: dict[str, str] = {}
        for path, (_, leaf) in zip(self.paths, leaves):
            if path == "embed":
                # tied embeddings double as the LM head, whose CE gradient
                # is dense over the whole vocab -> tensor granularity.
                self.kinds[path] = "dense" if cfg.tie_embeddings else "rows"
            elif _is_expert_leaf(cfg, path, leaf):
                self.kinds[path] = "experts"
            else:
                self.kinds[path] = "dense"
        self._path_ids = {p: i for i, p in enumerate(self.paths)}
        self.versions = {p: 0 for p in self.paths}
        self._seq = -1
        self._shadow: dict[str, np.ndarray] = {}
        self._flushes = 0
        self.pushed_bytes = 0
        self.skipped_dense = 0
        self.replicas = [ServeReplica(cfg, params,
                                      codec_backend=s.codec_backend)
                         for _ in range(s.num_slaves)]
        self.consumers = [
            Consumer(self.queue, range(s.num_partitions))
            for _ in self.replicas]

    # -- collect -----------------------------------------------------------
    def collect_step(self, tokens: np.ndarray,
                     metrics: Optional[dict] = None) -> None:
        """Record dirty IDs after a train step: unique token rows, routed
        experts per layer, and version bumps for every dense tensor."""
        events = []
        uniq = np.unique(np.asarray(tokens).reshape(-1)).astype(np.int64)
        self._embed_touched.update(uniq.tolist())
        for path, kind in self.kinds.items():
            if kind == "rows":
                events.append((path, uniq, "upsert"))
            elif kind == "dense":
                self.versions[path] += 1
                events.append((f"dense::{path}", np.zeros(1, np.int64),
                               "upsert"))
        if metrics and "expert_counts_per_layer" in metrics and \
                self.cfg.num_experts:
            e = self.cfg.num_experts
            for si, seg_counts in enumerate(metrics["expert_counts_per_layer"]):
                for pos, counts in seg_counts.items():
                    c = np.asarray(counts)                  # (R, E)
                    reps, experts = np.nonzero(c > 0)
                    ids = reps.astype(np.int64) * e + experts
                    for name in ("w_gate", "w_up", "w_down"):
                        path = f"segments/{si}/{pos}/ffn/{name}"
                        if path in self.kinds and \
                                self.kinds[path] == "experts":
                            if self._embed_mode == "cumulative":
                                tset = self._expert_touched.setdefault(
                                    path, set())
                                tset.update(ids.tolist())
                            events.append((path, ids, "upsert"))
        self.gatherer.offer(events)

    # -- push ---------------------------------------------------------------
    def _next_seq(self) -> int:
        self._seq += 1
        return self._seq

    def _changed_enough(self, path: str, value: np.ndarray) -> bool:
        thr = self.sync.delta_threshold
        if thr <= 0:
            return True
        if self.sync.full_refresh_every and \
                self._flushes % self.sync.full_refresh_every == 0:
            return True
        old = self._shadow.get(path)
        if old is None:
            return True
        num = float(np.linalg.norm(value - old))
        den = max(float(np.linalg.norm(old)), 1e-9)
        return (num / den) >= thr

    def tick(self, params: PyTree, now: float, *,
             scatter: bool = True) -> int:
        """Gather-window flush: read full current values for dirty IDs from
        the live training params, transform, produce; replicas consume."""
        n = 0
        if self.gatherer.ready(now):
            flat = dict(zip(self.paths, jax.tree_util.tree_leaves(params)))
            gathered = self.gatherer.flush(now)
            self._flushes += 1
            for (group, op), ids in gathered.items():
                path = group[len("dense::"):] if group.startswith("dense::") \
                    else group
                leaf = np.asarray(flat[path], dtype=np.float32)
                kind = self.kinds[path]
                if kind == "dense":
                    if not self._changed_enough(path, leaf):
                        self.skipped_dense += 1
                        continue
                    self._shadow[path] = leaf.copy()
                    # copy: queued payloads must not alias leaf (identity
                    # encode passes arrays through uncopied, and leaf can
                    # alias the caller's live params when they are numpy)
                    payload = self.transform.encode(
                        leaf.reshape(1, -1).copy(), {})
                    rec = Record(group=group, op=op,
                                 ids=np.array([self.versions[path]],
                                              np.int64),
                                 payload=payload, seq=self._next_seq(),
                                 producer=0,
                                 meta={"codec": self.transform.name,
                                       "kind": "dense", "path": path,
                                       "t": now})
                    part = self._path_ids[path] % self.queue.num_partitions
                    self.queue.produce(part, rec)
                    self.pushed_bytes += rec.nbytes()
                    n += 1
                elif kind == "rows":
                    if self._embed_mode == "cumulative":
                        ids = np.fromiter(self._embed_touched, dtype=np.int64,
                                          count=len(self._embed_touched))
                        ids.sort()
                    for i in range(0, len(ids), self.sync.embed_row_chunk):
                        chunk = ids[i:i + self.sync.embed_row_chunk]
                        vals = leaf[chunk]
                        payload = self.transform.encode(vals, {})
                        rec = Record(group=group, op=op, ids=chunk,
                                     payload=payload, seq=self._next_seq(),
                                     producer=0,
                                     meta={"codec": self.transform.name,
                                           "kind": "rows", "path": path,
                                           "t": now})
                        part = int(chunk[0]) % self.queue.num_partitions
                        self.queue.produce(part, rec)
                        self.pushed_bytes += rec.nbytes()
                        n += 1
                elif kind == "experts":
                    e = self.cfg.num_experts
                    if self._embed_mode == "cumulative" and \
                            path in self._expert_touched:
                        tset = self._expert_touched[path]
                        ids = np.fromiter(tset, dtype=np.int64,
                                          count=len(tset))
                        ids.sort()
                    vals = leaf[ids // e, ids % e]
                    vals2 = vals.reshape(len(ids), -1)
                    payload = self.transform.encode(vals2, {})
                    rec = Record(group=group, op=op, ids=ids,
                                 payload=payload, seq=self._next_seq(),
                                 producer=0,
                                 meta={"codec": self.transform.name,
                                       "kind": "experts", "path": path,
                                       "t": now})
                    part = self._path_ids[path] % self.queue.num_partitions
                    self.queue.produce(part, rec)
                    self.pushed_bytes += rec.nbytes()
                    n += 1
        if scatter:
            self.scatter()
        return n

    def scatter(self) -> int:
        n = 0
        for replica, consumer in zip(self.replicas, self.consumers):
            n += replica.apply_batch(list(consumer.poll()))
        return n

    def metrics(self) -> dict:
        return {
            "pushed_bytes": self.pushed_bytes,
            "queue_bytes": self.queue.produced_bytes,
            "dedup_ratio": self.gatherer.stats.dedup_ratio,
            "flushes": self._flushes,
            "skipped_dense": self.skipped_dense,
        }
