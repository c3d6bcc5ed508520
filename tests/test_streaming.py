"""Streaming synchronization behaviour: gather modes, dedup, idempotent
last-writer-wins application, deletes, eventual consistency."""

import numpy as np
import pytest

from repro.core import (MasterShard, PartitionedQueue, Record, RoutingPlan,
                        SlaveShard, make_transform)
from repro.core.streaming import Collector, Gatherer, Pusher, Scatter
from repro.optim import get_optimizer


def _mk(num_master=1, num_slave=2, parts=4, codec="identity",
        optimizer="ftrl"):
    plan = RoutingPlan(num_master, num_slave, parts)
    opt = get_optimizer(optimizer)
    queue = PartitionedQueue(parts)
    transform = make_transform(codec, opt)
    master = MasterShard(0, {"w": 4}, opt)
    col = Collector()
    master.collector = col
    slaves = [SlaveShard(i, {"w": 4}) for i in range(num_slave)]
    scatters = [Scatter(s, queue, plan) for s in slaves]
    pusher = Pusher(master, queue, plan, transform)
    return plan, queue, master, col, slaves, scatters, pusher, transform


def test_gather_modes():
    g = Gatherer("realtime")
    g.offer([("w", np.array([1, 2, 3]), "upsert")])
    assert g.ready(0.0)

    g = Gatherer("threshold", threshold=5)
    g.offer([("w", np.array([1, 2, 3]), "upsert")])
    assert not g.ready(0.0)
    g.offer([("w", np.array([4, 5]), "upsert")])
    assert g.ready(0.0)

    g = Gatherer("period", period=10.0)
    g.offer([("w", np.array([1]), "upsert")])
    assert not g.ready(5.0)
    assert g.ready(10.0)


def test_gather_dedup_ratio():
    """Repeated IDs within a window are pushed once (paper's >=90 %
    repetition => ~10x bandwidth saving)."""
    g = Gatherer("period", period=1.0)
    for _ in range(10):
        g.offer([("w", np.array([1, 2, 3, 4]), "upsert")])
    out = g.flush(1.0)
    assert len(out[("w", "upsert")]) == 4
    assert g.stats.raw_ids == 40 and g.stats.pushed_ids == 4
    assert g.stats.dedup_ratio == pytest.approx(0.9)


def test_end_to_end_eventual_consistency():
    plan, queue, master, col, slaves, scatters, pusher, transform = _mk()
    rng = np.random.default_rng(0)
    gatherer = Gatherer("realtime")
    for step in range(20):
        ids = rng.integers(0, 1000, size=16).astype(np.int64)
        grads = rng.normal(size=(16, 4)).astype(np.float32)
        master.push_grad("w", ids, grads)
        gatherer.offer(col.drain())
        pusher.push(gatherer.flush(step), now=float(step))
        for sc in scatters:
            sc.poll()
    # quiescence: every slave row equals transform(master row)
    all_ids = master.tables["w"].all_ids()
    w, slots = master.tables["w"].gather(all_ids)
    serve = transform.serve_values(w, slots)
    owner = plan.slave_shard(all_ids)
    for sid, slave in enumerate(slaves):
        mask = owner == sid
        got = slave.lookup("w", all_ids[mask])
        np.testing.assert_allclose(got, serve[mask], rtol=1e-5, atol=1e-6)


def test_idempotent_last_writer_wins():
    """Replaying a stale record never overwrites a newer value."""
    plan, queue, master, col, slaves, scatters, pusher, _ = _mk()
    ids = np.array([7], dtype=np.int64)
    master.push_grad("w", ids, np.ones((1, 4), np.float32))
    g = Gatherer("realtime"); g.offer(col.drain())
    pusher.push(g.flush(0), now=0.0)
    master.push_grad("w", ids, np.ones((1, 4), np.float32))
    g.offer(col.drain())
    pusher.push(g.flush(1), now=1.0)
    for sc in scatters:
        sc.poll()
    sid = int(plan.slave_shard(ids)[0])
    after_two = slaves[sid].lookup("w", ids).copy()
    # replay the whole queue from offset 0 (at-least-once redelivery)
    replay = Scatter(slaves[sid], queue, plan,
                     offsets={p: 0 for p in range(queue.num_partitions)})
    replay.poll()
    np.testing.assert_array_equal(slaves[sid].lookup("w", ids), after_two)
    assert slaves[sid].skipped_records > 0


def test_delete_propagates():
    plan, queue, master, col, slaves, scatters, pusher, _ = _mk()
    ids = np.array([1, 2, 3], dtype=np.int64)
    master.push_grad("w", ids, np.ones((3, 4), np.float32))
    g = Gatherer("realtime"); g.offer(col.drain())
    pusher.push(g.flush(0), now=0.0)
    for sc in scatters:
        sc.poll()
    master.delete_rows("w", np.array([2], dtype=np.int64))
    g.offer(col.drain())
    pusher.push(g.flush(1), now=1.0)
    for sc in scatters:
        sc.poll()
    sid = int(plan.slave_shard(np.array([2]))[0])
    assert len(slaves[sid].tables["w"]) >= 0
    np.testing.assert_array_equal(
        slaves[sid].lookup("w", np.array([2], dtype=np.int64)),
        np.zeros((1, 4), np.float32))


def test_partition_selective_consumption():
    """A slave's scatter only reads its own partitions (paper §4.1.4)."""
    plan, queue, master, col, slaves, scatters, pusher, _ = _mk(
        num_slave=2, parts=4)
    assert scatters[0].consumer.partitions == [0, 2]
    assert scatters[1].consumer.partitions == [1, 3]


CODECS = ("identity", "cast16", "int8")


@pytest.mark.parametrize("codec", CODECS)
def test_codec_roundtrip_through_queue(codec):
    """Every registered codec survives encode → Record → partitioned
    queue → ``decode_record`` within its error bound."""
    from repro.core import decode_record
    w = (np.random.default_rng(1).normal(size=(17, 8)) * 3).astype(
        np.float32)
    t = make_transform(codec)
    q = PartitionedQueue(2)
    q.produce(0, Record(group="w", op="upsert",
                        ids=np.arange(17, dtype=np.int64),
                        payload=t.encode(w, {}), seq=0, producer=0,
                        meta={"codec": t.name}))
    (rec,), _ = q.consume(0, 0)
    got = decode_record(rec)
    if codec == "identity":
        np.testing.assert_array_equal(got, w)
    elif codec == "cast16":
        np.testing.assert_allclose(got, w, rtol=1e-3, atol=1e-4)
    else:
        bound = np.abs(w).max(axis=-1, keepdims=True) / 254.0 + 1e-6
        assert np.all(np.abs(got - w) <= bound)


@pytest.mark.parametrize("codec", CODECS)
def test_pallas_numpy_backends_bit_compatible(codec):
    """Decoded slave weights are bit-identical between the numpy
    backends and the pallas ones (the delta-codec kernel encodes, in
    interpret mode off-TPU, and the replica's tables are device-backed)
    through the full push→queue→scatter spine."""
    decoded = {}
    for backend in ("numpy", "pallas"):
        plan = RoutingPlan(1, 2, 4)
        opt = get_optimizer("ftrl")
        queue = PartitionedQueue(4)
        master = MasterShard(0, {"w": 8}, opt)
        col = Collector()
        master.collector = col
        slaves = [SlaveShard(i, {"w": 8}, backend=backend)
                  for i in range(2)]
        scatters = [Scatter(s, queue, plan) for s in slaves]
        pusher = Pusher(master, queue, plan,
                        make_transform(codec, opt, backend=backend))
        rng = np.random.default_rng(3)
        for step in range(3):
            ids = rng.integers(0, 500, size=64).astype(np.int64)
            grads = rng.normal(size=(64, 8)).astype(np.float32)
            master.push_grad("w", ids, grads)
            g = Gatherer("realtime")
            g.offer(col.drain())
            pusher.push(g.flush(step), now=float(step))
        for sc in scatters:
            sc.poll()
        all_ids = np.sort(master.tables["w"].all_ids())
        decoded[backend] = np.concatenate(
            [s.lookup("w", all_ids) for s in slaves], axis=0)
    np.testing.assert_array_equal(decoded["numpy"], decoded["pallas"])


def test_pallas_replica_poll_makes_no_device_read():
    """A replica on the pallas PS backend decodes the int8 records of a
    poll on the host: the poll passes no blocking device read, and its
    rows equal a numpy-backend replica's after the same poll."""
    from repro.kernels.device_io import DEVICE_IO
    groups = {"w": 1, "v": 8}
    plan = RoutingPlan(1, 1, 2)
    opt = get_optimizer("ftrl")
    queue = PartitionedQueue(2)
    master = MasterShard(0, groups, opt)
    col = Collector()
    master.collector = col
    pusher = Pusher(master, queue, plan,
                    make_transform("int8", opt, backend="pallas"))
    rng = np.random.default_rng(4)
    for step in range(2):
        for g, dim in groups.items():
            ids = rng.integers(0, 300, size=96).astype(np.int64)
            master.push_grad(
                g, ids, rng.normal(size=(96, dim)).astype(np.float32))
        gatherer = Gatherer("realtime")
        gatherer.offer(col.drain())
        pusher.push(gatherer.flush(step), now=float(step))
    replicas = {b: SlaveShard(0, groups, backend=b)
                for b in ("pallas", "numpy")}
    scatters = {b: Scatter(s, queue, plan) for b, s in replicas.items()}
    waits = DEVICE_IO.waits
    applied = scatters["pallas"].poll()
    assert DEVICE_IO.waits == waits
    assert applied == scatters["numpy"].poll() > 0
    recs = [r for p in range(2) for r in queue.consume(p, 0)[0]]
    assert {r.meta["codec"] for r in recs} == {"int8"}
    assert {r.group for r in recs} == set(groups)
    for g in groups:
        ids = np.sort(master.tables[g].all_ids())
        np.testing.assert_array_equal(replicas["pallas"].lookup(g, ids),
                                      replicas["numpy"].lookup(g, ids))


def test_batched_scatter_lww_within_poll():
    """Overlapping ids across records inside ONE poll resolve
    last-writer-wins by arrival order — identical to sequential apply —
    and stale redeliveries in later polls are skipped."""
    plan, queue, master, col, slaves, scatters, pusher, _ = _mk(
        num_slave=1, parts=1)
    ids = np.array([5, 6], dtype=np.int64)

    def rec(seq, fill):
        return Record(group="w", op="upsert", ids=ids,
                      payload={"values": np.full((2, 4), fill, np.float32)},
                      seq=seq, producer=0, meta={"codec": "identity"})

    queue.produce(0, rec(0, 1.0))
    queue.produce(0, rec(1, 2.0))
    assert scatters[0].poll() == 2
    np.testing.assert_array_equal(slaves[0].lookup("w", ids),
                                  np.full((2, 4), 2.0, np.float32))
    queue.produce(0, rec(0, 1.0))            # stale redelivery
    assert scatters[0].poll() == 0
    np.testing.assert_array_equal(slaves[0].lookup("w", ids),
                                  np.full((2, 4), 2.0, np.float32))
    assert slaves[0].skipped_records == 1


def test_cross_partition_seq_streams_independent():
    """LWW staleness is keyed per (group, producer, partition): a flush
    touching only partition 0 must not mark partition 1's in-flight
    lower-seq records (disjoint ids) stale."""
    plan, queue, master, col, slaves, scatters, pusher, _ = _mk(
        num_slave=1, parts=2)

    def rec(seq, part, ids, fill):
        return Record(group="w", op="upsert", ids=ids,
                      payload={"values": np.full((len(ids), 4), fill,
                                                 np.float32)},
                      seq=seq, producer=0,
                      meta={"codec": "identity", "partition": part})

    a, b = np.array([1], np.int64), np.array([2], np.int64)
    queue.produce(0, rec(0, 0, a, 1.0))     # flush 0 touched both parts
    queue.produce(1, rec(0, 1, b, 2.0))
    queue.produce(0, rec(1, 0, a, 3.0))     # flush 1 touched only part 0
    # consumer drains partition 0 first (seq 0 then 1), then partition 1's
    # seq-0 record — which must still apply
    assert scatters[0].poll() == 3
    np.testing.assert_array_equal(slaves[0].lookup("w", a),
                                  np.full((1, 4), 3.0, np.float32))
    np.testing.assert_array_equal(slaves[0].lookup("w", b),
                                  np.full((1, 4), 2.0, np.float32))
    assert slaves[0].skipped_records == 0


@pytest.mark.parametrize("method", ["apply", "apply_batch"])
def test_record_without_codec_decodes_as_identity(method):
    """A record whose meta names no codec is applied as identity: its
    ``values`` land in the replica's table unchanged, on the singleton
    and the batched path alike."""
    slave = SlaveShard(0, {"w": 4})
    ids = np.array([3, 9, 40], np.int64)
    values = np.arange(12, dtype=np.float32).reshape(3, 4) - 5.5
    rec = Record(group="w", op="upsert", ids=ids,
                 payload={"values": values}, seq=0, producer=0, meta={})
    if method == "apply":
        assert slave.apply(rec)
    else:
        assert slave.apply_batch([rec]) == [rec]
    np.testing.assert_array_equal(slave.lookup("w", ids), values)
    assert slave.applied_records == 1


def test_batched_scatter_upsert_then_delete_ordering():
    """A delete arriving after an upsert for the same id within ONE poll
    must win — the deferred coalesced scatter may not resurrect rows the
    delete evicted (matches sequential apply)."""
    plan, queue, master, col, slaves, scatters, pusher, _ = _mk(
        num_slave=1, parts=1)
    ids = np.array([9], dtype=np.int64)
    queue.produce(0, Record(group="w", op="upsert", ids=ids,
                            payload={"values": np.ones((1, 4), np.float32)},
                            seq=0, producer=0, meta={"codec": "identity"}))
    queue.produce(0, Record(group="w", op="delete", ids=ids, payload={},
                            seq=1, producer=0, meta={"codec": "identity"}))
    assert scatters[0].poll() == 2
    assert len(slaves[0].tables["w"]) == 0
    np.testing.assert_array_equal(slaves[0].lookup("w", ids),
                                  np.zeros((1, 4), np.float32))


def test_vectorized_push_chunking_consistency():
    """Partition-chunked records (small max_ids_per_record) carry
    row-aligned payload slices: slaves converge to the same state."""
    plan, queue, master, col, slaves, scatters, pusher, transform = _mk()
    pusher.max_ids_per_record = 3
    ids = np.arange(100, dtype=np.int64)
    master.push_grad("w", ids, np.ones((100, 4), np.float32))
    g = Gatherer("realtime")
    g.offer(col.drain())
    n = pusher.push(g.flush(0), now=0.0)
    assert n > len(np.unique(plan.partition(ids)))   # chunking kicked in
    for sc in scatters:
        sc.poll()
    w, slots = master.tables["w"].gather(ids)
    serve = transform.serve_values(w, slots)
    owner = plan.slave_shard(ids)
    for sid, slave in enumerate(slaves):
        mask = owner == sid
        np.testing.assert_allclose(slave.lookup("w", ids[mask]),
                                   serve[mask], rtol=1e-5, atol=1e-6)


def test_ftrl_heterogeneous_parameters():
    """Slave receives derived w, not (z, n) — and they differ."""
    plan, queue, master, col, slaves, scatters, pusher, transform = _mk(
        optimizer="ftrl")
    ids = np.array([42], dtype=np.int64)
    for step in range(5):
        master.push_grad("w", ids, np.full((1, 4), 2.0, np.float32))
        g = Gatherer("realtime"); g.offer(col.drain())
        pusher.push(g.flush(step), now=float(step))
    for sc in scatters:
        sc.poll()
    w_master, slots = master.tables["w"].gather(ids)
    sid = int(plan.slave_shard(ids)[0])
    w_slave = slaves[sid].lookup("w", ids)
    # slave value equals FTRL weights derived from z,n
    np.testing.assert_allclose(w_slave, transform.serve_values(
        w_master, slots), rtol=1e-5)
    assert not np.allclose(slots["z"], w_slave)     # z != w (heterogeneous)
