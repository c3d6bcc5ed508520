"""The online training plane (src/repro/training/): pipeline end-to-end
learning, pow2 bucketed train steps, multi-scenario registry isolation,
admission-gated row creation, backpressure, the streaming evaluator, and
the train→metric→degrade loop."""

import dataclasses

import numpy as np
import pytest

from repro.configs.weips_ctr import DNN_ADAM, FM_FTRL, FM_SGD, LR_FTRL
from repro.core import ClusterConfig, WeiPSCluster
from repro.core.monitor import StreamingEvaluator, auc, logloss
from repro.data import ClickStream

CC = dict(num_master=2, num_slave=2, num_replicas=1, num_partitions=4)


# ---------------------------------------------------------------------------
# pipeline end-to-end
# ---------------------------------------------------------------------------
def test_pipeline_end_to_end_learns_and_serves():
    """stream → join → train → sync → predict: the joined (windowed)
    labels are enough to learn from, and the result serves."""
    cl = WeiPSCluster(FM_FTRL, ClusterConfig(**CC, join_window=2.0))
    pipe = cl.make_train_pipeline()
    stream = ClickStream(feature_space=1 << 12, fields=FM_FTRL.fields,
                         feedback_delay=0.5, signal_scale=0.8, seed=0)
    now = 0.0
    for _ in range(50):
        pipe.ingest(stream.events_batch(128, now))
        cl.train_scheduler.tick(now)
        cl.sync_tick(now)
        now += 0.5
    cl.train_scheduler.flush(now + 10)
    cl.sync_tick(now + 10)
    scn = cl.training.scenario()
    assert scn.step > 20
    hist = [p.values["logloss"] for p in scn.validator.history]
    assert np.mean(hist[-5:]) < np.mean(hist[:5])
    # what was learned online serves through the serving plane
    ids, y = stream.batch(1024)
    assert auc(y, cl.predict(ids)) > 0.6


def test_pipeline_buckets_bound_compiled_shapes():
    """Ragged drains train through pow2 buckets: a handful of compiled
    shapes, padding accounted, metrics unaffected by the zero-weight
    padding rows."""
    cl = WeiPSCluster(LR_FTRL, ClusterConfig(
        **CC, join_window=0.5, train_buckets=(64, 128, 256)))
    pipe = cl.make_train_pipeline()
    stream = ClickStream(feature_space=1 << 10, fields=LR_FTRL.fields,
                         seed=1, feedback_delay=0.2)
    rng = np.random.default_rng(0)
    now = 0.0
    for _ in range(30):
        pipe.ingest(stream.events_batch(int(rng.integers(40, 200)), now))
        cl.train_scheduler.tick(now)
        now += 1.0
    cl.train_scheduler.flush(now + 5)
    scn = cl.training.scenario()
    assert scn.stats.batches > 0
    assert set(scn.stats.bucket_counts) <= {64, 128, 256}
    assert 0.0 < scn.stats.padding_fraction < 0.5
    assert scn.stats.dedup_ratio > 0.3        # Zipfian repetition absorbed


def test_weighted_padding_matches_unpadded_step():
    """A padded bucketed step must push the same updates as the unpadded
    step: weight-0 padding rows contribute nothing."""
    a = WeiPSCluster(FM_FTRL, ClusterConfig(**CC))
    b = WeiPSCluster(FM_FTRL, ClusterConfig(**CC))
    stream = ClickStream(feature_space=1 << 10, fields=FM_FTRL.fields,
                         seed=2)
    ids, y = stream.batch(100)
    a.training.train_batch(a.training.scenario(), ids, y, now=0.0)
    b.training.train_batch(b.training.scenario(), ids, y, now=0.0,
                           bucket=128)
    for g in a.groups:
        for ma, mb in zip(a.masters, b.masters):
            ta, tb = ma.tables[g], mb.tables[g]
            ia = ta.all_ids()
            np.testing.assert_array_equal(np.sort(ia),
                                          np.sort(tb.all_ids()))
            wa, _ = ta.gather(ia)
            wb, _ = tb.gather(ia)
            np.testing.assert_allclose(wa, wb, rtol=1e-5, atol=1e-7)


def test_negative_downsampling_correction_weights():
    """Downsampled negatives carry 1/rate weights; the weighted pCTR on
    the kept sample matches the unsampled stream's (unbiasedness)."""
    from repro.data import SampleJoiner
    rng = np.random.default_rng(0)
    full = SampleJoiner(window=1.0)
    samp = SampleJoiner(window=1.0, neg_sample_rate=0.25, seed=3)
    n = 20_000
    vids = np.arange(n, dtype=np.int64)
    feats = rng.integers(0, 100, size=(n, 4))
    y = rng.random(n) < 0.2
    for j in (full, samp):
        j.offer_exposures(0.0, vids, feats)
        j.offer_feedbacks(0.5, vids[y])
    bf = full.drain_batch(2.0)
    bs = samp.drain_batch(2.0)
    assert samp.negatives_dropped > 0
    assert len(bs) < len(bf)
    assert (bs.weights[bs.labels > 0] == 1.0).all()
    assert (bs.weights[bs.labels <= 0] == 4.0).all()
    ctr_full = bf.labels.mean()
    ctr_weighted = float((bs.weights * bs.labels).sum() / bs.weights.sum())
    assert abs(ctr_weighted - ctr_full) < 0.02


# ---------------------------------------------------------------------------
# multi-scenario registry
# ---------------------------------------------------------------------------
def test_two_scenarios_concurrent_equals_solo():
    """Registry isolation (acceptance): two scenarios training
    concurrently off ONE shared PS reach the same logloss trajectory as
    each trained alone — namespaced groups and per-scenario dense heads
    share infrastructure but never parameters."""
    def batches(seed, n=20):
        s = ClickStream(feature_space=1 << 12, fields=32, seed=seed,
                        signal_scale=0.8)
        return [s.batch(128) for _ in range(n)]

    b1, b2 = batches(11), batches(22)

    solo1 = WeiPSCluster(FM_FTRL, ClusterConfig(**CC))
    for i, (ids, y) in enumerate(b1):
        solo1.train_on_batch(ids, y, now=float(i))

    solo2 = WeiPSCluster(FM_FTRL, ClusterConfig(**CC))
    scn_s = solo2.add_train_scenario(LR_FTRL, name="iso")
    for i, (ids, y) in enumerate(b2):
        solo2.training.train_batch(scn_s, ids, y, now=float(i))

    both = WeiPSCluster(FM_FTRL, ClusterConfig(**CC))
    scn_c = both.add_train_scenario(LR_FTRL, name="iso")
    for i in range(len(b1)):
        both.train_on_batch(*b1[i], now=float(i))
        both.training.train_batch(scn_c, *b2[i], now=float(i))
        both.sync_tick(float(i))

    ll = lambda v: np.array([p.values["logloss"] for p in v.history])
    np.testing.assert_allclose(ll(both.validator), ll(solo1.validator),
                               rtol=1e-6)
    np.testing.assert_allclose(ll(scn_c.validator), ll(scn_s.validator),
                               rtol=1e-6)


def test_isolated_scenario_tables_stream_to_slaves():
    """Namespaced scenario groups ride the same sync stream: after a
    tick the slave tables hold the scenario's serve weights."""
    cl = WeiPSCluster(FM_FTRL, ClusterConfig(**CC))
    scn = cl.add_train_scenario(LR_FTRL, name="iso")
    stream = ClickStream(feature_space=1 << 10, fields=LR_FTRL.fields,
                         seed=4)
    ids, y = stream.batch(64)
    cl.training.train_batch(scn, ids, y, now=0.0)
    cl.sync_tick(0.0)
    total = sum(len(shard.tables["iso/w"]) for rs in cl.replica_sets
                for shard in rs.replicas[:1])
    assert total == sum(len(m.tables["iso/w"]) for m in cl.masters)
    assert "iso/w" in cl.serving.store_groups


def test_shared_scenario_trains_store_groups():
    """A share_groups scenario (LR head on an FM store) really writes the
    store's own ``w`` — and a non-matching optimizer is rejected."""
    cl = WeiPSCluster(FM_FTRL, ClusterConfig(**CC))
    scn = cl.add_train_scenario(LR_FTRL, name="lr-head",
                                share_groups=True)
    assert scn.group_map == {"w": "w"}
    before = sum(len(m.tables["w"]) for m in cl.masters)
    stream = ClickStream(feature_space=1 << 10, fields=LR_FTRL.fields,
                         seed=5)
    ids, y = stream.batch(64)
    cl.training.train_batch(scn, ids, y, now=0.0)
    assert sum(len(m.tables["w"]) for m in cl.masters) > before
    with pytest.raises(ValueError):
        cl.add_train_scenario(FM_SGD, name="bad-opt")


def test_train_scenarios_published_to_registry():
    cl = WeiPSCluster(FM_FTRL, ClusterConfig(**CC))
    cl.add_train_scenario(LR_FTRL, name="iso")
    names = cl.scheduler.train_scenarios(FM_FTRL.name)
    assert set(names) == {FM_FTRL.name, "iso"}
    meta = cl.scheduler.train_scenario_meta(FM_FTRL.name, "iso")
    assert meta["groups"] == ["iso/w"]


# ---------------------------------------------------------------------------
# admission, backpressure
# ---------------------------------------------------------------------------
def test_admission_gates_row_creation():
    """min_count=2: ids seen once never allocate PS rows; recurring ids
    do — and training still proceeds."""
    cl = WeiPSCluster(LR_FTRL, ClusterConfig(**CC, feature_min_count=2))
    once = np.arange(1000, 1032, dtype=np.int64).reshape(1, -1)
    twice = np.arange(2000, 2032, dtype=np.int64).reshape(1, -1)
    y = np.ones(1, np.float32)
    cl.train_on_batch(twice, y, now=0.0)
    cl.train_on_batch(np.concatenate([once, twice]),
                      np.ones(2, np.float32), now=1.0)
    rows = np.concatenate([m.tables["w"].all_ids() for m in cl.masters])
    assert np.isin(twice.reshape(-1), rows).all()
    assert not np.isin(once.reshape(-1), rows).any()


def test_backpressure_throttles_then_recovers():
    """Training cannot outrun deployment: while Scatter.lag() exceeds the
    bound the pipeline buffers (and sheds past the cap) instead of
    pushing updates; once the scatter catches up it trains again."""
    cl = WeiPSCluster(LR_FTRL, ClusterConfig(
        num_master=1, num_slave=1, num_replicas=1, num_partitions=2,
        train_max_sync_lag=0, join_window=0.5, train_buffer_cap=256))
    pipe = cl.make_train_pipeline()
    stream = ClickStream(feature_space=1 << 10, fields=LR_FTRL.fields,
                         seed=1)
    t = 0.0
    for _ in range(8):
        pipe.ingest(stream.events_batch(128, t))
        cl.train_on_batch(*stream.batch(8), now=t)
        cl.sync_tick(t, scatter=False)          # lag builds unscattered
        cl.train_scheduler.tick(t)
        t += 1.0
    assert pipe.throttled_ticks == 8
    assert pipe.shed_examples > 0
    assert pipe.buffered <= 256
    m = cl.sync_metrics(t)
    pm = m["training"]["scenarios"][LR_FTRL.name]["pipeline"]
    assert pm["throttled_ticks"] == 8 and pm["shed_examples"] > 0
    steps_before = cl.training.scenario().step
    cl.sync_tick(t)                              # scatter catches up
    cl.train_scheduler.flush(t + 5)
    assert cl.training.scenario().step > steps_before


# ---------------------------------------------------------------------------
# streaming evaluator + downgrade loop
# ---------------------------------------------------------------------------
def test_streaming_evaluator_matches_exact_metrics():
    rng = np.random.default_rng(0)
    ev = StreamingEvaluator(window=100, bins=4096)
    ys, ps = [], []
    for i in range(20):
        y = (rng.random(256) < 0.3).astype(np.float32)
        p = np.clip(rng.random(256), 0.01, 0.99).astype(np.float32)
        p = np.where(y > 0, np.clip(p + 0.1, 0, 0.999), p)
        ev.observe(float(i), i, y, p)
        ys.append(y)
        ps.append(p)
    y_all, p_all = np.concatenate(ys), np.concatenate(ps)
    assert ev.smoothed("logloss") == pytest.approx(logloss(y_all, p_all),
                                                   rel=1e-6)
    assert ev.smoothed("auc") == pytest.approx(auc(y_all, p_all), abs=2e-3)
    assert ev.smoothed("calibration") == pytest.approx(
        p_all.mean() / y_all.mean(), rel=1e-6)
    # windowed: a narrower query only sees the tail
    tail = ev.smoothed("logloss", window=5)
    assert tail == pytest.approx(
        logloss(np.concatenate(ys[-5:]), np.concatenate(ps[-5:])),
        rel=1e-6)


def test_corrupt_stream_trips_downgrade_via_pipeline():
    """The acceptance loop: train through the pipeline, checkpoint, then
    a ClickStream.corrupt() shift collapses the windowed streaming
    logloss and the domino downgrade fires off that signal."""
    cfg = dataclasses.replace(LR_FTRL, ftrl_l1=0.01, ftrl_alpha=0.3)
    cl = WeiPSCluster(cfg, ClusterConfig(
        num_master=2, num_slave=1, num_replicas=1, num_partitions=2,
        downgrade_metric="logloss", downgrade_threshold=0.72,
        downgrade_window=3, join_window=0.4))
    pipe = cl.make_train_pipeline(emit_on_feedback=False)
    stream = ClickStream(feature_space=1 << 8, fields=cfg.fields,
                         signal_scale=1.0, feedback_delay=0.1)
    now = 0.0
    for _ in range(35):
        pipe.ingest(stream.events_batch(128, now))
        cl.train_scheduler.tick(now)
        cl.sync_tick(now)
        now += 0.5
    cl.checkpoint(now)
    assert cl.downgrade_check(now) is None        # healthy
    stream.corrupt(scale=2.0)
    for _ in range(10):
        pipe.ingest(stream.events_batch(128, now))
        cl.train_scheduler.tick(now)
        now += 0.5
    cl.train_scheduler.flush(now)
    assert cl.downgrade_check(now) is not None    # trigger fired
    assert len(cl.downgrader.downgrades) == 1


def test_dnn_scenario_trains_through_pipeline():
    """DNN-Adam (the fixed seed failure) learns through the full
    pipeline path too — dead-ReLU init would show up here as AUC 0.5."""
    dnn = dataclasses.replace(DNN_ADAM, fields=8, embed_dim=4,
                              dnn_hidden=(16,))
    cl = WeiPSCluster(dnn, ClusterConfig(**CC, join_window=0.5))
    pipe = cl.make_train_pipeline(emit_on_feedback=True)
    stream = ClickStream(feature_space=1 << 10, fields=dnn.fields,
                         signal_scale=1.0, feedback_delay=0.2, seed=6)
    now = 0.0
    for _ in range(40):
        pipe.ingest(stream.events_batch(128, now))
        cl.train_scheduler.tick(now)
        cl.sync_tick(now)
        now += 0.5
    cl.train_scheduler.flush(now + 5)
    scn = cl.training.scenario()
    assert scn.evaluator.smoothed("auc", window=10) > 0.55
    assert pipe.joiner.fast_emits > 0


# ---------------------------------------------------------------------------
# spans of the train step
# ---------------------------------------------------------------------------
def test_train_batch_span_tree():
    """One pallas-backed step (interpret mode on the CPU) traces as
    train.batch ⊃ {dedup, pull, forward, grad_agg ⊃ device.wait,
    ps.apply ⊃ {dedup, ensure, mirror_sync, ftrl ⊃ device.wait,
    write_back}}, on the tracer's implicit parenting."""
    from repro.obs import trace as obs_trace
    cl = WeiPSCluster(LR_FTRL, ClusterConfig(
        num_master=2, num_slave=1, num_replicas=1, num_partitions=2,
        ps_backend="pallas"))
    scn = cl.training.scenario()
    ids = np.arange(8 * LR_FTRL.fields, dtype=np.int64).reshape(8, -1)
    cl.training.train_batch(scn, ids, np.ones(8, np.float32))  # warm
    tr = obs_trace.configure(enabled=True)
    try:
        cl.training.train_batch(scn, ids, np.zeros(8, np.float32))
        spans = tr.export()
    finally:
        obs_trace.disable()
    by_id = {s["span"]: s for s in spans}

    def ancestors(s):
        out = []
        while s["parent"] in by_id:
            s = by_id[s["parent"]]
            out.append(s["name"])
        return out

    roots = [s for s in spans if not ancestors(s)]
    assert [s["name"] for s in roots] == ["train.batch"]
    under = {}
    for s in spans:
        a = ancestors(s)
        under.setdefault(s["name"], set()).add(a[0] if a else None)
    assert under["train.dedup"] == under["train.pull"] == \
        under["train.forward"] == under["train.grad_agg"] == \
        under["ps.apply"] == {"train.batch"}
    assert under["device.wait"] == {"train.forward", "train.grad_agg",
                                    "ps.ftrl", "train.batch"}
    for name in ("ps.dedup", "ps.ensure", "ps.mirror_sync", "ps.ftrl",
                 "ps.write_back"):
        assert under[name] == {"ps.apply"}, name
    # both masters take a share of each group's rows (LR: one group)
    assert sum(s["name"] == "ps.apply" for s in spans) == 2
