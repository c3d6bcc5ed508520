"""Streaming synchronization (paper §4.1): collect → gather → push → scatter.

  Collector  — per master shard; captures dirty IDs + op type only (no
               values, no increments) into a lock-free-queue stand-in.
  Gatherer   — deduplicating aggregation window with the paper's three
               trigger modes: real-time, threshold-based, period-based.
               Dedup ratio is tracked (the paper observes ≥90 % repetition
               of updates within 10 s).
  Pusher     — reads *current full values* for the gathered IDs (eventual
               consistency at ID granularity: never increments), applies the
               model transform (FTRL z,n→w, dtype cast, int8 quant),
               serializes, and produces to the ID-routed queue partition.
  Scatter    — per slave shard; consumes its partitions and applies records
               idempotently (LWW by seq). Its consumer offsets are embedded
               in every checkpoint and ``seek``-able, so recovery, replica
               bootstrap, and domino downgrade replay the stream exactly
               from the restored state (core/fault_tolerance.py).

The push and scatter stages are fully batched (no per-partition/per-chunk
Python): one gather + one encode per (group, op), vectorized argsort
routing to partitions, and one ownership filter + one coalesced scatter
per poll — see ``Pusher.push`` / ``Scatter.poll``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

from repro.obs import trace as obs_trace
from repro.core.ps import MasterShard, SlaveShard
from repro.core.queue import Consumer, PartitionedQueue, Record
from repro.core.routing import RoutingPlan
from repro.core.transform import Transform


class Collector:
    """Dirty-ID capture. The paper's lock-free multi-producer queue guards
    multi-threaded trainers; in the SPMD/JAX adaptation collection happens
    post-step on device-computed unique IDs, so a list suffices — the
    *semantics* kept are: IDs + op only, never values (§4.1.1)."""

    def __init__(self):
        self._events: list[tuple[str, np.ndarray, str]] = []
        self.collected_ids = 0

    def record(self, group: str, ids: np.ndarray, op: str = "upsert") -> None:
        ids = np.asarray(ids, dtype=np.int64)
        self._events.append((group, ids, op))
        self.collected_ids += len(ids)

    def record_dense(self, name: str) -> None:
        self._events.append((f"dense/{name}", np.zeros(1, np.int64), "upsert"))

    def drain(self) -> list[tuple[str, np.ndarray, str]]:
        out, self._events = self._events, []
        return out


@dataclass
class GatherStats:
    raw_ids: int = 0          # ids entering the window (with repetition)
    pushed_ids: int = 0       # unique ids actually pushed
    flushes: int = 0

    @property
    def dedup_ratio(self) -> float:
        """Fraction of raw updates absorbed by deduplication."""
        if self.raw_ids == 0:
            return 0.0
        return 1.0 - self.pushed_ids / self.raw_ids


class Gatherer:
    """Aggregation window with the three trigger modes (§4.1.2)."""

    def __init__(self, mode: str = "period", *, threshold: int = 4096,
                 period: float = 1.0):
        assert mode in ("realtime", "threshold", "period")
        self.mode = mode
        self.threshold = threshold
        self.period = period
        # window state: (group, op) -> list of per-offer unique id arrays.
        # Offers are O(batch log batch); the cross-offer merge happens once
        # at flush (amortized-linear, vs per-offer union1d's quadratic
        # re-merging of the whole window).
        self._pending: dict[tuple[str, str], list[np.ndarray]] = {}
        self._pending_count = 0      # pre-merge upper bound on unique ids
        self._last_flush = 0.0
        self.stats = GatherStats()

    def offer(self, events: list[tuple[str, np.ndarray, str]]) -> None:
        for group, ids, op in events:
            ids = np.asarray(ids, dtype=np.int64)
            self.stats.raw_ids += len(ids)
            u = np.unique(ids)
            self._pending.setdefault((group, op), []).append(u)
            # upper bound: cross-offer repeats are only collapsed at flush,
            # so threshold mode can fire slightly early — never late
            self._pending_count += len(u)

    def ready(self, now: float) -> bool:
        if self._pending_count == 0 and not self._pending:
            return False
        if self.mode == "realtime":
            return True
        if self.mode == "threshold":
            return self._pending_count >= self.threshold
        return (now - self._last_flush) >= self.period

    def flush(self, now: float) -> dict[tuple[str, str], np.ndarray]:
        out = {}
        for k, chunks in self._pending.items():
            merged = chunks[0] if len(chunks) == 1 else \
                np.unique(np.concatenate(chunks))
            if len(merged):
                out[k] = merged
        self._pending = {}
        self._pending_count = 0
        self._last_flush = now
        self.stats.pushed_ids += sum(len(v) for v in out.values())
        self.stats.flushes += 1
        return out


def _slice_payload(payload: dict, lo: int, hi: int, n: int) -> dict:
    """Row-slice every per-row array of an encoded payload (arrays whose
    leading dim is the row count ``n``); scalars/metadata pass through."""
    out = {}
    for k, v in payload.items():
        a = np.asarray(v)
        out[k] = a[lo:hi] if a.ndim >= 1 and a.shape[0] == n else v
    return out


class Pusher:
    """Master-side: full-current-value reads + transform + partitioned
    produce. ``seq`` is per (group, producer) monotonic.

    The sparse hot path is batched end-to-end: ONE ``table.gather`` and
    ONE ``transform.encode`` cover every id of a (group, op) flush — the
    encode amortizes JAX dispatch (FTRL z,n→w) and runs the codec kernel
    over the full row block — then ids are routed to partitions with a
    single argsort and the encoded payload is *sliced*, never re-encoded,
    per partition-chunk record."""

    def __init__(self, shard: MasterShard, queue: PartitionedQueue,
                 plan: RoutingPlan, transform: Transform,
                 max_ids_per_record: int = 65536):
        self.shard = shard
        self.queue = queue
        self.plan = plan
        self.transform = transform
        self.max_ids_per_record = max_ids_per_record
        self._seq: dict[str, int] = {}
        self.pushed_bytes = 0
        self.pushed_records = 0
        # trace metadata stamped into every record of the current flush
        # while a sync.push span is open (None when tracing is off, so
        # the disabled path produces byte-identical records)
        self._tmeta: Optional[dict] = None

    def _next_seq(self, group: str) -> int:
        s = self._seq.get(group, -1) + 1
        self._seq[group] = s
        return s

    def seqs(self) -> dict[str, int]:
        """Per-group sequence counters for the checkpoint cut. A restored
        pusher re-emits the SAME seq for a replayed flush, which is what
        lets slaves LWW-skip (or idempotently re-apply) replayed records
        instead of treating them as fresh writes."""
        return dict(self._seq)

    def restore_seqs(self, seqs: dict[str, int]) -> None:
        self._seq = dict(seqs)

    def push(self, gathered: dict[tuple[str, str], np.ndarray],
             now: float = 0.0) -> int:
        """Returns number of records produced."""
        tr = obs_trace.get_tracer()
        sp = None
        if tr.enabled and gathered:
            # one flush == one trace: every record produced below carries
            # this (trace, span, t_push), which crosses the FileQueue
            # inside the pickled frame and lets the consumer reconstruct
            # queue dwell + parent its apply under this span
            sp = tr.begin("sync.push", trace=tr.new_trace(),
                          producer=self.shard.shard_id,
                          groups=len(gathered))
            self._tmeta = {"trace": sp.trace, "span": sp.id,
                           "t_push": sp.t0}
        n_rec = 0
        try:
            dense = self._encode_dense([g for g, _ in gathered
                                        if g.startswith("dense/")])
            for (group, op), ids in gathered.items():
                if group.startswith("dense/"):
                    n_rec += self._push_dense(group, dense.get(group), now)
                else:
                    n_rec += self._push_sparse(group, op, ids, now)
        finally:
            if sp is not None:
                tr.end(sp)
                self._tmeta = None
        self.pushed_records += n_rec
        return n_rec

    def _encode_dense(self, groups: list) -> dict:
        """{group: (version, shape, payload)} of the flush's dense groups
        whose tensor the bank holds, coded together: tensors that live on
        the device are coded there and come back in one read."""
        bank = self.shard.dense
        have = [(g, g[len("dense/"):]) for g in groups]
        have = [(g, n) for g, n in have if n in bank.tensors]
        if not have:
            return {}
        values = [bank.tensors[n] for _, n in have]
        with obs_trace.get_tracer().span("sync.encode"):
            payloads = self.transform.encode_tensors(values)
        return {g: (bank.versions[n], tuple(v.shape), p)
                for (g, n), v, p in zip(have, values, payloads)}

    def _push_dense(self, group: str, coded: Optional[tuple],
                    now: float) -> int:
        if coded is None:
            return 0
        ver, shape, payload = coded
        # the replica restores the tensor's shape from ``meta``
        meta = {"codec": self.transform.name, "t": now, "shape": shape}
        if self._tmeta is not None:
            meta.update(self._tmeta)
        rec = Record(group=group, op="upsert",
                     ids=np.array([ver], np.int64), payload=payload,
                     seq=self._next_seq(group),
                     producer=self.shard.shard_id, meta=meta)
        n = 0
        # dense tensors go to every slave: replicate to one partition per
        # slave shard
        for slave in range(self.plan.num_slave):
            p = self.plan.partitions_for_slave(slave)[0]
            self.queue.produce(p, rec)
            self.pushed_bytes += rec.nbytes()
            n += 1
        return n

    def _push_sparse(self, group: str, op: str, ids: np.ndarray,
                     now: float) -> int:
        if len(ids) == 0:
            return 0
        table = self.shard.tables[group]
        seq = self._next_seq(group)
        # vectorized routing: one argsort groups ids into contiguous
        # partition segments (vs. the pre-refactor num_partitions boolean
        # masks over the whole id set)
        part = self.plan.partition(ids)
        order = np.argsort(part, kind="stable")
        ids = ids.take(order, mode="clip")
        part = part.take(order, mode="clip")
        seg = np.flatnonzero(np.diff(part)) + 1      # segment boundaries
        starts = np.concatenate(([0], seg))
        ends = np.concatenate((seg, [len(ids)]))
        if op == "delete":
            payload = None
        else:
            # ONE batched gather, reading only the columns the transform
            # declares (FTRL codecs read (z, n) and skip w; plain codecs
            # read w and skip the slots), then ONE encode
            w, slots = table.gather(
                ids, want_w=self.transform.requires_w,
                slot_names=self.transform.required_slots)
            with obs_trace.get_tracer().span("sync.encode"):
                payload = self.transform.encode(w, slots)
        n = 0
        for s, e in zip(starts, ends):
            p = int(part[s])
            recs = []
            for i in range(s, e, self.max_ids_per_record):
                j = min(i + self.max_ids_per_record, e)
                # partition stamp: ids route to partitions
                # deterministically, so each partition is its own
                # ordered stream — slaves key LWW staleness per
                # (group, producer, partition), not globally (a
                # global key would mis-skip a partition's records
                # when a later flush touched only other partitions)
                meta = {"codec": self.transform.name, "t": now,
                        "partition": p}
                if self._tmeta is not None:
                    meta.update(self._tmeta)
                recs.append(Record(
                    group=group, op=op, ids=ids[i:j],
                    payload={} if payload is None
                    else _slice_payload(payload, i, j, len(ids)),
                    seq=seq, producer=self.shard.shard_id, meta=meta))
            self.queue.produce_many(p, recs)
            self.pushed_bytes += sum(r.nbytes() for r in recs)
            n += len(recs)
        return n


class Scatter:
    """Slave-side consumer: poll partitions, apply idempotently.

    A poll is batched: ownership of every sparse id in the poll is
    resolved with ONE vectorized routing pass, then the surviving records
    go through ``SlaveShard.apply_batch`` — one coalesced table scatter
    per group instead of a per-record apply loop."""

    def __init__(self, shard: SlaveShard, queue: PartitionedQueue,
                 plan: RoutingPlan,
                 offsets: Optional[dict[int, int]] = None):
        self.shard = shard
        self.plan = plan
        self.consumer = Consumer(queue, plan.partitions_for_slave(
            shard.shard_id), offsets)
        self.applied = 0
        self.last_record_time = 0.0
        # event→deployed staleness per applied record: the pusher stamps
        # meta["t"] at push time, the apply happens here, and the apply
        # runs SlaveShard.on_apply (serve-cache invalidation) inline — so
        # now - meta["t"] at this point IS push→scatter→cache-visible,
        # the staleness ``sync_metrics`` reports. Deferred import keeps
        # streaming.py free of a monitor dependency at module load.
        from repro.core.monitor import PercentileRing
        self.staleness = PercentileRing(1 << 12)
        # called with the polled records after the consumer advanced but
        # BEFORE any of them is applied — the crash window between fetch
        # and apply. The chaos harness kills here; a process dying at this
        # point re-polls the same records after restart (at-least-once),
        # and full-value upserts make the redelivery idempotent.
        self.pre_apply = None

    def poll(self, max_records: Optional[int] = None, *,
             now: Optional[float] = None) -> int:
        recs = self.consumer.poll(max_records)
        if not recs:
            return 0
        if self.pre_apply is not None:
            self.pre_apply(recs)
        # model routing: keep only ids owned by this slave shard — with
        # num_partitions % num_slave == 0 this filter is a no-op for
        # sparse groups (partition congruence), but guards dense
        # broadcast records and future re-partitioning. One vectorized
        # ownership pass covers the whole poll.
        sparse = [k for k, r in enumerate(recs)
                  if not r.group.startswith("dense/")]
        if sparse:
            owner = self.plan.slave_shard(
                np.concatenate([recs[k].ids for k in sparse]))
            keep_all = owner == self.shard.shard_id
            if not keep_all.all():
                off = 0
                for k in sparse:
                    r = recs[k]
                    keep = keep_all[off:off + len(r.ids)]
                    off += len(r.ids)
                    if not keep.all():
                        recs[k] = Record(
                            group=r.group, op=r.op, ids=r.ids[keep],
                            payload=_filter_payload(r.payload, keep),
                            seq=r.seq, producer=r.producer, meta=r.meta)
        tr = obs_trace.get_tracer()
        if tr.enabled:
            applied = self._apply_traced(tr, recs)
        else:
            applied = self.shard.apply_batch(recs)
        if applied:
            self.last_record_time = applied[-1].meta.get("t", 0.0)
            if now is not None:
                self.staleness.record(
                    [now - r.meta.get("t", now) for r in applied])
        self.applied += len(applied)
        return len(applied)

    def _apply_traced(self, tr, recs: list) -> list:
        """Trace-grouped apply: records stamped by one pusher flush (one
        trace id) apply together so the whole flush shows as one
        queue-dwell + apply pair under its sync.push parent. Regrouping
        preserves semantics: within a (group, producer, partition)
        stream records keep their relative order (dict groups are
        insertion-ordered), and cross-trace overlap resolves by seq
        (LWW) exactly as it would in arrival order."""
        by_trace: dict = {}
        for r in recs:
            by_trace.setdefault(r.meta.get("trace"), []).append(r)
        poll_t0 = tr.clock()
        applied: list = []
        for tid, group in by_trace.items():
            if tid is None:  # records produced before tracing turned on
                applied += self.shard.apply_batch(group)
                continue
            # queue dwell reconstructed consumer-side: produce stamp
            # (t_push, same CLOCK_MONOTONIC domain across processes on
            # Linux) → this poll
            qid = tr.record(
                "sync.queue", trace=tid,
                parent=group[0].meta.get("span", 0),
                t0=min(r.meta.get("t_push", poll_t0) for r in group),
                t1=poll_t0, records=len(group))
            # cache.invalidate spans fired by shard.on_apply nest here
            # via the tracer's implicit context
            with tr.span("sync.apply", trace=tid, parent=qid,
                         shard=self.shard.shard_id, records=len(group)):
                applied += self.shard.apply_batch(group)
        return applied

    def offsets(self) -> dict[int, int]:
        return dict(self.consumer.offsets)

    def lag(self) -> int:
        """Records produced to this shard's partitions not yet applied —
        the staleness signal the serving plane's lag-bounded replica
        selection compares (``ReplicaSet.pick(max_lag=...)``)."""
        return self.consumer.lag()

    def seek(self, offsets: dict[int, int]) -> None:
        """Rewind/forward this consumer to checkpointed queue offsets —
        the replay handle of the recovery and downgrade paths (records
        are full-value upserts, so replay is idempotent)."""
        self.consumer.seek(offsets)


def _filter_payload(payload: dict, keep: np.ndarray) -> dict:
    out = {}
    for k, v in payload.items():
        v = np.asarray(v)
        out[k] = v[keep] if v.ndim >= 1 and v.shape[0] == len(keep) else v
    return out


@dataclass
class SyncMetrics:
    sync_lag_seconds: float = 0.0
    records_in_flight: int = 0
    dedup_ratio: float = 0.0
    pushed_bytes: int = 0


class SyncPipeline:
    """Wires one master shard's collect→gather→push and all slave scatters.

    ``tick(now)`` advances the pipeline; with mode="realtime" every tick
    flushes, with "period" flushes happen every ``period`` sim-seconds —
    this is what the sync-latency benchmark sweeps."""

    def __init__(self, master: MasterShard, slaves: list[SlaveShard],
                 queue: PartitionedQueue, plan: RoutingPlan,
                 transform: Transform, gather_mode: str = "realtime",
                 threshold: int = 4096, period: float = 1.0):
        self.collector = Collector()
        master.collector = self.collector
        self.master = master
        self.gatherer = Gatherer(gather_mode, threshold=threshold,
                                 period=period)
        self.pusher = Pusher(master, queue, plan, transform)
        # consumer-side codec backend is each SlaveShard's own setting
        # (producer and consumer backends are independent — see
        # transform.py); the pipeline never overrides it
        self.scatters = [Scatter(s, queue, plan) for s in slaves]
        self.queue = queue

    def tick(self, now: float, *, scatter: bool = True) -> int:
        """collect+gather+maybe-push, then slave polls. Returns #records."""
        self.gatherer.offer(self.collector.drain())
        n = 0
        if self.gatherer.ready(now):
            n = self.pusher.push(self.gatherer.flush(now), now)
        if scatter:
            for sc in self.scatters:
                if sc.shard.alive:
                    sc.poll()
        return n

    def metrics(self, now: float) -> SyncMetrics:
        lag = max((now - sc.last_record_time) for sc in self.scatters) \
            if self.scatters else 0.0
        return SyncMetrics(
            sync_lag_seconds=lag,
            records_in_flight=sum(sc.consumer.lag() for sc in self.scatters),
            dedup_ratio=self.gatherer.stats.dedup_ratio,
            pushed_bytes=self.pusher.pushed_bytes,
        )
