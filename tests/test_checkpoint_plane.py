"""Incremental checkpoint/recovery plane: delta capture, full+delta chain
restore bit-equality, vectorized reshard routing, retention demotion,
replica bootstrap-from-checkpoint, downgrade queue-offset replay."""

import numpy as np
import pytest

from repro.configs.weips_ctr import LR_FTRL
from repro.core import ClusterConfig, RoutingPlan, WeiPSCluster
from repro.core.fault_tolerance import (BackupPolicy, CheckpointStore,
                                        ColdBackup, checkpoint_nbytes)
from repro.core.ps import MasterShard, SlaveShard, SparseTable
from repro.data import ClickStream
from repro.optim import get_optimizer

GROUPS = {"w": 4}


def _shards(n, opt=None):
    opt = opt or get_optimizer("ftrl")
    return [MasterShard(i, GROUPS, opt) for i in range(n)]


def _push(shards, plan, rng, n=512, step=0):
    """Push one random batch of grads, routed to owner shards."""
    ids = np.sort(rng.choice(1 << 30, size=n, replace=False).astype(np.int64))
    grads = rng.normal(size=(n, GROUPS["w"])).astype(np.float32)
    for sid, sids in plan.split_by_master(ids).items():
        shards[sid].push_grad("w", sids, grads[np.searchsorted(ids, sids)],
                              step=step)
    return ids


def _sorted_state(shard, group="w"):
    snap = shard.tables[group].snapshot()
    order = np.argsort(snap["ids"])
    return {"ids": snap["ids"][order], "w": snap["w"][order],
            "slots": {k: v[order] for k, v in snap["slots"].items()},
            "last_touch": snap["last_touch"][order],
            "touch_count": snap["touch_count"][order]}


def _assert_state_equal(a, b):
    np.testing.assert_array_equal(a["ids"], b["ids"])
    np.testing.assert_array_equal(a["w"], b["w"])
    assert set(a["slots"]) == set(b["slots"])
    for k in a["slots"]:
        np.testing.assert_array_equal(a["slots"][k], b["slots"][k])
    np.testing.assert_array_equal(a["last_touch"], b["last_touch"])
    np.testing.assert_array_equal(a["touch_count"], b["touch_count"])


# ---------------------------------------------------------------------------
# delta capture at the table level
# ---------------------------------------------------------------------------
def test_delta_snapshot_captures_only_dirty_rows_and_deletes():
    t = SparseTable(2)
    rng = np.random.default_rng(0)
    base_ids = np.arange(100, dtype=np.int64)
    t.scatter(base_ids, rng.normal(size=(100, 2)).astype(np.float32))
    mark = t.version
    dirty = np.array([3, 7, 250], dtype=np.int64)       # 250 is new
    t.scatter(dirty, np.ones((3, 2), np.float32))
    t.evict(np.array([10, 11], dtype=np.int64))
    d = t.delta_snapshot(mark)
    np.testing.assert_array_equal(np.sort(d["ids"]), dirty)
    np.testing.assert_array_equal(d["deleted"], [10, 11])
    assert d["since"] == mark and d["version"] == t.version
    # full snapshot stays complete
    assert len(t.snapshot()["ids"]) == 99


def test_trim_evict_log_drops_covered_entries():
    t = SparseTable(1)
    t.scatter(np.arange(10, dtype=np.int64), np.zeros((10, 1), np.float32))
    t.evict(np.array([1], dtype=np.int64))
    mark = t.version
    t.evict(np.array([2], dtype=np.int64))
    t.trim_evict_log(mark)
    d = t.delta_snapshot(0)
    np.testing.assert_array_equal(d["deleted"], [2])    # entry 1 trimmed


def test_load_snapshot_preserves_touch_stats():
    """Recovered shards must keep eviction/collection stats (last_touch,
    touch_count) — the seed load path dropped them."""
    rng = np.random.default_rng(1)
    [src] = _shards(1)
    ids = np.arange(64, dtype=np.int64)
    for step in range(3):          # repeated pushes -> touch_count > 1
        src.push_grad("w", ids,
                      rng.normal(size=(64, GROUPS["w"])).astype(np.float32),
                      step=step)
    fresh = _shards(1)[0]
    fresh.load_snapshot(src.snapshot())
    _assert_state_equal(_sorted_state(src), _sorted_state(fresh))
    assert fresh.step == src.step
    assert _sorted_state(fresh)["touch_count"].max() > 1


# ---------------------------------------------------------------------------
# full+delta chain restore
# ---------------------------------------------------------------------------
def _chained_cluster(compress="none", rng=None):
    """3-shard cluster checkpointed as full -> delta -> delta (with an
    eviction in between) -> full; the last delta and the final full
    describe the SAME state."""
    rng = rng or np.random.default_rng(2)
    plan = RoutingPlan(3, 1, 1)
    shards = _shards(3)
    store = CheckpointStore()
    cb = ColdBackup(shards, store, BackupPolicy(incremental=True,
                                                compress=compress))
    _push(shards, plan, rng, step=0)
    v_full0 = cb.checkpoint(0.0, tier="remote")
    ids1 = _push(shards, plan, rng, step=1)
    cb.checkpoint(1.0, tier="local")
    # evict a slice of live rows on their owner shards (feature expiry)
    stale = ids1[:40]
    for sid, sids in plan.split_by_master(stale).items():
        shards[sid].delete_rows("w", sids)
    _push(shards, plan, rng, step=2)
    v_chain = cb.checkpoint(2.0, tier="local")
    v_full = cb.checkpoint(3.0, tier="remote")
    assert store.load(v_full0).kind == "full"
    assert store.load(v_chain).kind == "delta"
    assert store.load(v_full).kind == "full"
    return shards, cb, v_chain, v_full


@pytest.mark.parametrize("compress", ["none", "int8"])
def test_chain_restore_bit_equals_full_restore(compress):
    src, cb, v_chain, v_full = _chained_cluster(compress)
    a, b = _shards(3), _shards(3)
    assert cb.recover_all(a, version=v_chain) == v_chain
    assert cb.recover_all(b, version=v_full) == v_full
    for sa, sb in zip(a, b):
        _assert_state_equal(_sorted_state(sa), _sorted_state(sb))
        assert sa.step == sb.step
    if compress == "none":
        # uncompressed restore is bit-equal to the live source too
        for sa, ss in zip(a, src):
            _assert_state_equal(_sorted_state(sa), _sorted_state(ss))


def test_int8_compressed_restore_within_quant_error():
    src, cb, v_chain, _ = _chained_cluster("int8")
    rec = _shards(3)
    cb.recover_all(rec, version=v_chain)
    for s_src, s_rec in zip(src, rec):
        a, b = _sorted_state(s_src), _sorted_state(s_rec)
        np.testing.assert_array_equal(a["ids"], b["ids"])
        # row-wise absmax int8: error bound is absmax/127 per row
        for name in ("z", "n"):
            bound = np.abs(a["slots"][name]).max(axis=1, keepdims=True) \
                / 127.0 + 1e-7
            assert (np.abs(a["slots"][name] - b["slots"][name])
                    <= bound).all()


def test_delta_checkpoint_is_small_and_cheap():
    """At ~10% dirty rows a delta checkpoint is >= 5x smaller than a
    full one."""
    rng = np.random.default_rng(3)
    plan = RoutingPlan(2, 1, 1)
    shards = _shards(2)
    store = CheckpointStore()
    cb = ColdBackup(shards, store, BackupPolicy(incremental=True))
    ids = _push(shards, plan, rng, n=4096, step=0)
    v_full = cb.checkpoint(0.0, tier="remote")
    dirty = ids[:400]                                   # ~10% (ids sorted)
    grads = rng.normal(size=(len(dirty), GROUPS["w"])).astype(np.float32)
    for sid, sids in plan.split_by_master(dirty).items():
        shards[sid].push_grad("w", sids,
                              grads[np.searchsorted(dirty, sids)], step=1)
    v_delta = cb.checkpoint(1.0, tier="local")
    full_b = checkpoint_nbytes(store.load(v_full))
    delta_b = checkpoint_nbytes(store.load(v_delta))
    assert full_b >= 5 * delta_b, (full_b, delta_b)


def test_checkpoint_kind_cadence_and_rebase_after_recovery():
    shards = _shards(1)
    store = CheckpointStore()
    cb = ColdBackup(shards, store, BackupPolicy(incremental=True))
    v1 = cb.checkpoint(0.0, tier="local")
    v2 = cb.checkpoint(1.0, tier="local")
    v3 = cb.checkpoint(2.0, tier="remote")
    v4 = cb.checkpoint(3.0, tier="local")
    assert store.load(v1).kind == "full"                # nothing to chain on
    assert store.load(v2).kind == "delta"
    assert store.load(v2).base == v1
    assert store.load(v3).kind == "full"                # remote cadence
    assert store.load(v4).base == v3
    # recovery resets the mutation clocks -> next local must re-base
    cb.recover_shard(shards[0], version=v4)
    v5 = cb.checkpoint(4.0, tier="local")
    assert store.load(v5).kind == "full"
    v6 = cb.checkpoint(5.0, tier="local")
    assert store.load(v6).kind == "delta" and store.load(v6).base == v5


def test_dense_tensors_chain_through_deltas():
    opt = get_optimizer("ftrl")
    shard = MasterShard(0, GROUPS, opt)
    store = CheckpointStore()
    cb = ColdBackup([shard], store, BackupPolicy(incremental=True))
    shard.push_dense("mlp/w0", np.full((4, 2), 1.0, np.float32))
    shard.push_dense("mlp/b0", np.zeros((2,), np.float32))
    cb.checkpoint(0.0, tier="remote")
    shard.push_dense("mlp/w0", np.full((4, 2), 2.0, np.float32))
    v = cb.checkpoint(1.0, tier="local")
    delta = store.load(v)
    # the delta ships only the tensor that moved
    assert set(delta.shard_snaps[0]["dense"]["tensors"]) == {"mlp/w0"}
    fresh = MasterShard(0, GROUPS, opt)
    cb.recover_all([fresh], version=v)
    np.testing.assert_array_equal(fresh.dense.tensors["mlp/w0"],
                                  np.full((4, 2), 2.0, np.float32))
    np.testing.assert_array_equal(fresh.dense.tensors["mlp/b0"],
                                  np.zeros((2,), np.float32))


# ---------------------------------------------------------------------------
# reshard routing
# ---------------------------------------------------------------------------
def test_reshard_recovery_equals_direct_state():
    """N->M reshard through the argsort ownership router restores every
    row bit-equal to the source shard's state — values, slots, and touch
    stats — even from a delta chain tip."""
    src, cb, v_chain, _ = _chained_cluster()
    plan_dst = RoutingPlan(5, 1, 1)
    dst = _shards(5)
    cb.recover_all(dst, version=v_chain, owner_of=plan_dst.master_shard)
    # collect both sides id->row and compare
    def collect(shards):
        states = [_sorted_state(s) for s in shards]
        ids = np.concatenate([st["ids"] for st in states])
        order = np.argsort(ids)
        out = {"ids": ids[order]}
        for k in ("w", "last_touch", "touch_count"):
            out[k] = np.concatenate([st[k] for st in states],
                                    axis=0)[order]
        out["slots"] = {
            n: np.concatenate([st["slots"][n] for st in states],
                              axis=0)[order]
            for n in states[0]["slots"]}
        return out
    _assert_state_equal(collect(src), collect(dst))
    for sid, shard in enumerate(dst):
        ids = shard.tables["w"].all_ids()
        assert (plan_dst.master_shard(ids) == sid).all()


# ---------------------------------------------------------------------------
# retention
# ---------------------------------------------------------------------------
def test_retention_demotes_local_checkpoints_to_remote(tmp_path):
    shards = _shards(1)
    store = CheckpointStore(root=str(tmp_path), keep=2)
    cb = ColdBackup(shards, store, BackupPolicy(incremental=False))
    versions = [cb.checkpoint(float(i), tier="local") for i in range(5)]
    # nothing lost: evicted local checkpoints were demoted to files
    assert store.versions() == versions
    oldest = store.load(versions[0])
    assert oldest.version == versions[0] and oldest.tier == "remote"


def test_retention_drop_without_root_is_recorded():
    shards = _shards(1)
    store = CheckpointStore(keep=2)
    cb = ColdBackup(shards, store, BackupPolicy(incremental=False))
    versions = [cb.checkpoint(float(i), tier="local") for i in range(4)]
    assert store.versions() == versions[2:]
    assert store.dropped == versions[:2]
    with pytest.raises(KeyError):
        store.load(versions[0])


def test_retention_cascade_drops_orphaned_deltas():
    """Dropping a chain link must also drop the deltas that chained
    through it — versions() never lists an unmaterializable version."""
    from repro.core.fault_tolerance import Checkpoint
    store = CheckpointStore(keep=1)
    store.save(Checkpoint(version=1, created_at=0.0, shard_snaps={},
                          queue_offsets={}, num_shards=1, kind="full"))
    store.save(Checkpoint(version=2, created_at=1.0, shard_snaps={},
                          queue_offsets={}, num_shards=1, kind="delta",
                          base=1))
    assert store.versions() == []                       # both gone...
    assert store.dropped == [1, 2]                      # ...and recorded
    with pytest.raises(KeyError):
        store.load(2)


def test_incremental_default_config_stays_recoverable():
    """Regression: with no store root and the default retention window,
    long local-cadence runs must keep every *listed* version
    materializable — the chain re-bases on a full before retention
    could evict its own base."""
    rng = np.random.default_rng(7)
    plan = RoutingPlan(2, 1, 1)
    shards = _shards(2)
    store = CheckpointStore(keep=8)
    cb = ColdBackup(shards, store, BackupPolicy(incremental=True))
    _push(shards, plan, rng, n=256, step=0)
    for i in range(12):
        _push(shards, plan, rng, n=64, step=i + 1)
        cb.checkpoint(float(i), tier="local")
    assert store.versions()
    kinds = {store.load(v).kind for v in store.versions()}
    assert "delta" in kinds                             # still incremental
    for v in store.versions():
        cb.materialize(v)                               # must not raise
    rec = _shards(2)
    cb.recover_all(rec, version=store.latest())


# ---------------------------------------------------------------------------
# cluster-level: replica bootstrap + downgrade replay
# ---------------------------------------------------------------------------
def _cluster(**kw):
    defaults = dict(num_master=3, num_slave=2, num_replicas=2,
                    num_partitions=4, gather_mode="realtime",
                    local_ckpt_interval=1e9, remote_ckpt_interval=1e9)
    defaults.update(kw)
    return WeiPSCluster(LR_FTRL, ClusterConfig(**defaults))


def _run(cl, stream, steps, t0=0.0, dt=0.5):
    now = t0
    for _ in range(steps):
        ids, y = stream.batch(32)
        cl.train_on_batch(ids, y, now=now)
        cl.sync_tick(now)
        now += dt
    return now


def _master_serve_truth(cl, group="w"):
    """id -> serve weight derived straight from the master tables."""
    ids_l, serve_l = [], []
    for m in cl.masters:
        ids = m.tables[group].all_ids()
        if not len(ids):
            continue
        w, slots = m.tables[group].gather(ids)
        ids_l.append(ids)
        serve_l.append(cl.transform.serve_values(w, slots))
    ids = np.concatenate(ids_l)
    order = np.argsort(ids)
    return ids[order], np.concatenate(serve_l, axis=0)[order]


def test_replica_bootstrap_from_checkpoint_converges(monkeypatch):
    cl = _cluster()
    stream = ClickStream(feature_space=1 << 10, fields=LR_FTRL.fields)
    now = _run(cl, stream, 8)
    cl.checkpoint(now)
    now = _run(cl, stream, 4, t0=now + 1)               # post-ckpt updates
    # the peer-copy fallback must NOT be taken when a checkpoint exists
    def no_peer_copy(self, other):
        raise AssertionError("bootstrap used peer full copy")
    monkeypatch.setattr(SlaveShard, "full_sync_from", no_peer_copy)
    fresh = cl.add_slave_replica(0)
    assert fresh in cl.replica_sets[0].replicas
    assert cl.scatters[-1].shard is fresh
    assert cl.scatters[-1].consumer.lag() == 0          # caught up
    # checkpoint-restore + streaming catch-up == peer's streamed state
    peer = cl.replica_sets[0].replicas[0]
    ids = peer.tables["w"].all_ids()
    np.testing.assert_allclose(fresh.lookup("w", ids),
                               peer.lookup("w", ids), rtol=1e-6, atol=1e-7)


def test_replica_bootstrap_peer_fallback_without_checkpoint():
    cl = _cluster()
    stream = ClickStream(feature_space=1 << 10, fields=LR_FTRL.fields)
    _run(cl, stream, 5)                                 # no checkpoint taken
    fresh = cl.add_slave_replica(1)
    peer = cl.replica_sets[1].replicas[0]
    ids = peer.tables["w"].all_ids()
    if len(ids):
        np.testing.assert_allclose(fresh.lookup("w", ids),
                                   peer.lookup("w", ids), rtol=1e-6)


def test_downgrade_switch_replays_from_offsets_without_double_apply():
    cl = _cluster()
    stream = ClickStream(feature_space=1 << 10, fields=LR_FTRL.fields)
    now = _run(cl, stream, 8)
    v = cl.checkpoint(now)
    ckpt_offsets = cl.store.load(v).queue_offsets
    now = _run(cl, stream, 5, t0=now + 1)               # post-ckpt stream
    cl.sync_tick(now)                                   # drain
    cl.downgrader.execute(now + 1, version=v)
    # switch seeked every consumer back to the checkpoint offsets
    for sc in cl.scatters:
        for p, off in sc.offsets().items():
            assert off == ckpt_offsets.get(p, 0)
    # replay: full-value records bring every replica back to the live
    # master state exactly once
    replayed = sum(sc.poll() for sc in cl.scatters)
    assert replayed > 0
    ids, serve = _master_serve_truth(cl)
    owner = cl.plan.slave_shard(ids)
    for sid, rs in enumerate(cl.replica_sets):
        mask = owner == sid
        for rep in rs.replicas:
            np.testing.assert_allclose(rep.lookup("w", ids[mask]),
                                       serve[mask], rtol=1e-6, atol=1e-7)
    # no double-apply: the stream is fully consumed, nothing re-applies
    assert all(sc.poll() == 0 for sc in cl.scatters)
    assert all(sc.consumer.lag() == 0 for sc in cl.scatters)
