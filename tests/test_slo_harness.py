"""Deterministic overload + staleness tests for the SLO machinery:
admission control in the predict scheduler, the shared PercentileRing,
event→deployed staleness through Scatter, and per-window cache counters,
under a ManualClock — every latency and staleness figure here is exact
simulated seconds."""

import numpy as np
import pytest

from repro.core.downgrade import SmoothedThresholdTrigger
from repro.core.monitor import ManualClock, PercentileRing
from repro.serving.cache import DenseCache, ServeCache
from repro.serving.scheduler import AdmissionConfig, PredictScheduler


def _echo_runner(ids, bucket):
    """Predict stub: returns each example's first id as the score —
    makes results attributable to their request."""
    return ids[:, 0].astype(np.float32)


def _req(base, n=4, fields=2):
    return np.full((n, fields), base, dtype=np.int64)


def make_sched(clock, max_pending=None, deadline=None):
    return PredictScheduler(
        _echo_runner, buckets=(4, 8, 16, 32),
        admission=AdmissionConfig(max_pending=max_pending,
                                  deadline=deadline),
        clock=clock)


# ---------------------------------------------------------------------------
# PercentileRing
# ---------------------------------------------------------------------------
class TestPercentileRing:
    def test_percentiles_and_wraparound(self):
        r = PercentileRing(size=8)
        r.record(np.arange(100, dtype=np.float64))  # keeps 92..99
        assert len(r) == 8
        assert r.count == 100
        assert list(r.values()) == [92, 93, 94, 95, 96, 97, 98, 99]
        assert r.percentiles((50,))["p50"] == pytest.approx(95.5)

    def test_scalar_and_chunked_records_match_bulk(self):
        a, b = PercentileRing(size=16), PercentileRing(size=16)
        vals = np.arange(40, dtype=np.float64)
        a.record(vals)
        for i, v in enumerate(vals):
            (b.record(v) if i % 3 else b.record([v]))
        assert np.array_equal(a.values(), b.values())

    def test_merged_percentiles(self):
        a, b = PercentileRing(4), PercentileRing(4)
        a.record([1.0, 2.0])
        b.record([100.0, 200.0])
        merged = PercentileRing.merged_percentiles([a, b], (50, 99))
        assert merged["p50"] == pytest.approx(51.0)
        assert merged["p99"] > 100

    def test_empty_ring(self):
        r = PercentileRing(4)
        assert r.percentiles() == {"p50": 0.0, "p99": 0.0}
        assert PercentileRing.merged_percentiles([r]) \
            == {"p50": 0.0, "p99": 0.0}

    def test_reset(self):
        r = PercentileRing(4)
        r.record([5.0, 6.0])
        r.reset()
        assert len(r) == 0
        assert r.percentiles()["p99"] == 0.0

    def test_trigger_duck_typing(self):
        """SmoothedThresholdTrigger fires on a latency ring's p99 exactly
        as it fires on an evaluator's logloss — same percentile
        machinery for the harness and the domino downgrade."""
        trig = SmoothedThresholdTrigger(metric="p99", threshold=0.5,
                                        window=10, min_points=5)
        ring = PercentileRing(32)
        ring.record([0.01] * 8)             # healthy latencies
        assert not trig.check(ring)
        ring.record([2.0] * 8)              # overload tail
        assert trig.check(ring)


# ---------------------------------------------------------------------------
# admission control (deterministic, ManualClock)
# ---------------------------------------------------------------------------
class TestAdmission:
    def test_zero_sheds_below_depth_bound(self):
        clk = ManualClock()
        s = make_sched(clk, max_pending=16)
        for i in range(4):                   # 16 examples == the bound
            s.submit(_req(i))
        out = s.flush()
        assert s.adm.shed_requests == 0
        assert all(p is not None for p in out)
        assert s.adm.executed_requests == 4

    def test_shed_drops_oldest_first(self):
        clk = ManualClock()
        s = make_sched(clk, max_pending=8)   # room for 2 live requests
        for i in range(4):
            s.submit(_req(i))
        out = s.flush()
        # tickets 0 and 1 (oldest) shed; 2 and 3 executed
        assert out[0] is None and out[1] is None
        assert float(out[2][0]) == 2.0 and float(out[3][0]) == 3.0
        assert s.adm.shed_depth_requests == 2

    def test_newest_request_always_admitted(self):
        clk = ManualClock()
        s = make_sched(clk, max_pending=2)   # below even one request
        s.submit(_req(7))
        out = s.flush()
        assert s.adm.shed_requests == 0
        assert float(out[0][0]) == 7.0

    def test_counters_balance_offered(self):
        clk = ManualClock()
        s = make_sched(clk, max_pending=12, deadline=1.0)
        rng = np.random.default_rng(0)
        for i in range(25):
            s.submit(_req(i, n=int(rng.integers(1, 6))))
            if i % 4 == 3:
                clk.advance(0.7)
                s.flush(budget=8)
        clk.advance(5.0)
        s.flush()                            # drain everything left
        a = s.adm
        assert a.executed_requests + a.shed_requests == a.offered_requests
        assert a.executed_examples + a.shed_examples == a.offered_examples
        assert s.pending_examples == 0

    def test_deadline_shed(self):
        clk = ManualClock()
        s = make_sched(clk, deadline=1.0)
        s.submit(_req(0))
        clk.advance(2.0)                     # ticket is now 2s old
        s.submit(_req(1))
        out = s.flush()
        assert out[0] is None
        assert float(out[1][0]) == 1.0
        assert s.adm.shed_deadline_requests == 1

    def test_budgeted_flush_leaves_remainder_pending(self):
        clk = ManualClock()
        s = make_sched(clk)
        for i in range(3):
            s.submit(_req(i))                # 12 examples
        out = s.flush(budget=8)
        assert len(out) == 2                 # 2 requests fit the budget
        assert s.pending_examples == 4
        out2 = s.flush()
        assert float(out2[0][0]) == 2.0

    def test_budget_progress_guarantee(self):
        clk = ManualClock()
        s = make_sched(clk)
        s.submit(_req(0, n=10))              # larger than the budget
        out = s.flush(budget=4)
        assert out[0] is not None and len(out[0]) == 10

    def test_queueing_latency_is_simulated_seconds(self):
        clk = ManualClock()
        s = make_sched(clk)
        s.submit(_req(0))
        clk.advance(3.0)
        s.submit(_req(1))
        clk.advance(1.0)
        s.flush()
        lat = sorted(s.latency.values())
        assert lat == [pytest.approx(1.0), pytest.approx(4.0)]

    def test_no_admission_default_unchanged(self):
        """Without an AdmissionConfig the scheduler behaves exactly like
        the pre-admission one: everything queues, everything executes."""
        s = PredictScheduler(_echo_runner, buckets=(4, 8))
        for i in range(50):
            s.submit(_req(i))
        out = s.flush()
        assert len(out) == 50 and all(p is not None for p in out)
        assert s.adm.shed_requests == 0


# ---------------------------------------------------------------------------
# event→deployed staleness
# ---------------------------------------------------------------------------
class TestStaleness:
    def _cluster(self):
        from repro.configs.weips_ctr import LR_FTRL
        from repro.core.cluster import ClusterConfig, WeiPSCluster
        return WeiPSCluster(LR_FTRL, ClusterConfig(
            num_master=1, num_slave=1, num_replicas=1, num_partitions=2,
            gather_mode="realtime"))

    def test_staleness_matches_scripted_schedule(self):
        """Hand-computable: updates pushed at t=1.0, scatter-applied at
        t=3.5 → every applied record reports staleness 2.5."""
        cl = self._cluster()
        ids = np.arange(8, dtype=np.int64).reshape(2, 4)
        y = np.array([1.0, 0.0], np.float32)
        cl.train_on_batch(ids, y, now=1.0)
        cl.sync_tick(1.0, scatter=False)      # push stamps meta["t"]=1.0
        for sc in cl.scatters:
            sc.poll(now=3.5)
        stale = cl.sync_metrics(3.5)["staleness"]
        assert stale["p50"] == pytest.approx(2.5)
        assert stale["p99"] == pytest.approx(2.5)

    def test_pushed_update_cache_visible_after_poll(self):
        """The staleness metric's 'deployed' endpoint is real: a pushed
        update invalidates the serve cache during the poll, and the NEXT
        predict reflects the new weights."""
        cl = self._cluster()
        ids = np.arange(4, dtype=np.int64).reshape(1, 4)
        p0 = cl.predict(ids)                  # caches (zero) rows
        assert float(p0[0]) == pytest.approx(0.5)   # untrained LR
        for _ in range(30):                   # train the same ids hard
            cl.train_on_batch(ids, np.ones(1, np.float32), now=1.0)
        cl.sync_tick(1.0, scatter=False)
        p_stale = cl.predict(ids)             # not yet deployed: cached
        assert float(p_stale[0]) == pytest.approx(0.5)
        for sc in cl.scatters:
            sc.poll(now=2.0)                  # deploy -> invalidate
        p_fresh = cl.predict(ids)
        assert float(p_fresh[0]) > 0.5
        stale = cl.sync_metrics(2.0)["staleness"]
        assert stale["p99"] == pytest.approx(1.0)

    def test_poll_without_now_records_nothing(self):
        cl = self._cluster()
        ids = np.arange(8, dtype=np.int64).reshape(2, 4)
        cl.train_on_batch(ids, np.ones(2, np.float32), now=1.0)
        cl.sync_tick(1.0, scatter=False)
        for sc in cl.scatters:
            sc.poll()                         # legacy call: no timestamp
        assert all(len(sc.staleness) == 0 for sc in cl.scatters)


# ---------------------------------------------------------------------------
# cache window counters
# ---------------------------------------------------------------------------
class TestCacheWindows:
    def test_serve_cache_window_deltas_and_reset(self):
        c = ServeCache({"w": 2}, max_rows=64)
        ids = np.arange(8, dtype=np.int64)
        c.lookup(ids)                                   # 8 misses
        c.fill(ids, np.ones((8, 2), np.float32))
        c.lookup(ids)                                   # 8 hits
        w1 = c.window_stats()
        assert w1["hits"] == 8 and w1["misses"] == 8
        assert w1["hit_rate"] == pytest.approx(0.5)
        # new window starts empty; lifetime counters are untouched
        w2 = c.window_stats()
        assert w2["hits"] == 0 and w2["misses"] == 0
        assert w2["hit_rate"] == 0.0
        assert c.stats()["hits"] == 8 and c.stats()["misses"] == 8
        c.invalidate(ids[:3])
        w3 = c.window_stats()
        assert w3["invalidated"] == 3 and w3["hits"] == 0

    def test_dense_cache_uniform_stats(self):
        d = DenseCache()
        fetch = lambda: np.zeros(4, np.float32)  # noqa: E731
        d.get("h", (1, 4), 1, fetch)                    # refresh
        d.get("h", (1, 4), 1, fetch)                    # hit
        s = d.stats()
        assert s["hits"] == 1 and s["misses"] == 1
        assert s["hit_rate"] == pytest.approx(0.5)
        w = d.window_stats()
        assert w["hits"] == 1 and w["misses"] == 1
        assert d.window_stats()["hits"] == 0            # window reset
        d.clear()
        assert d.window_stats()["invalidated"] == 1

    def test_admission_and_caches_in_sync_metrics(self):
        """The harness-facing contract: sync_metrics()["serving"] carries
        admission totals, latency percentiles, and uniform per-scenario
        cache stats."""
        from repro.configs.weips_ctr import LR_FTRL
        from repro.core.cluster import ClusterConfig, WeiPSCluster
        cl = WeiPSCluster(LR_FTRL, ClusterConfig(
            num_master=1, num_slave=1, num_replicas=1, num_partitions=2,
            serve_max_pending=8))
        ids = np.arange(4, dtype=np.int64).reshape(1, 4)
        cl.predict(ids)
        reqs = np.repeat(ids, 4, axis=0)      # 4 examples per submit
        for _ in range(4):                    # 16 examples > bound of 8
            cl.serving.submit(reqs)
        cl.serving.flush()
        serving = cl.sync_metrics(0.0)["serving"]
        adm = serving["admission"]
        assert adm["offered_requests"] == 5
        assert adm["executed_requests"] + adm["shed_requests"] == 5
        assert adm["shed_depth_requests"] > 0
        assert set(serving["latency"]) == {"p50", "p99"}
        scn = serving["scenarios"][LR_FTRL.name]
        for key in ("cache", "dense_cache"):
            assert {"hits", "misses", "hit_rate",
                    "invalidated"} <= set(scn[key])
        assert scn["admission"]["offered_requests"] == 5
