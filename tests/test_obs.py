"""Unit tests for the observability subsystem (`repro.obs`): tracer
ring semantics, Perfetto round-trip, metrics registry, and the
in-process causal chain through the streaming update path
(push -> queue -> apply -> cache-invalidate)."""

import numpy as np
import pytest

from repro.obs import metrics as obs_metrics
from repro.obs import perfetto
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import Tracer


@pytest.fixture(autouse=True)
def _isolated_tracer():
    """Every test starts and ends with the module tracer disabled —
    the global is process-wide state."""
    obs_trace.disable()
    yield
    obs_trace.disable()


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ---------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------
class TestTracer:
    def test_disabled_is_noop(self):
        tr = Tracer(enabled=False, capacity=4)
        sp = tr.begin("x", foo=1)
        assert sp is obs_trace._NULL_SPAN
        with sp:
            pass
        assert tr.record("y", t0=0.0, t1=1.0) == 0
        assert tr.instant("z") == 0
        assert tr.export() == []

    def test_nesting_and_parenting(self):
        clk = FakeClock()
        tr = Tracer(clock=clk, process="p0")
        root = tr.begin("outer", trace=tr.new_trace())
        clk.advance(1.0)
        with tr.span("inner", k=2) as inner:
            assert inner.trace == root.trace
            assert inner.parent == root.id
            clk.advance(0.5)
        tr.end(root)
        spans = tr.export()
        assert [s["name"] for s in spans] == ["inner", "outer"]
        inner_d, outer_d = spans
        assert inner_d["parent"] == outer_d["span"]
        assert inner_d["trace"] == outer_d["trace"]
        assert inner_d["args"] == {"k": 2}
        assert outer_d["t1"] - outer_d["t0"] == pytest.approx(1.5)
        assert all(s["proc"] == "p0" for s in spans)

    def test_ids_are_pid_salted_and_unique(self):
        import os
        tr = Tracer()
        ids = {tr.new_trace() for _ in range(100)}
        assert len(ids) == 100
        assert all(i >> 32 == (os.getpid() & 0xFFFF) for i in ids)

    def test_ring_wrap_drops_oldest(self):
        clk = FakeClock()
        tr = Tracer(capacity=4, clock=clk)
        for i in range(7):
            tr.record(f"s{i}", t0=float(i), t1=float(i) + 0.5)
        assert tr.dropped == 3
        assert [s["name"] for s in tr.export()] == \
            ["s3", "s4", "s5", "s6"]

    def test_record_and_instant(self):
        tr = Tracer(clock=FakeClock(5.0))
        sid = tr.record("q", t0=1.0, t1=2.0, trace=9, parent=3, n=4)
        spans = tr.export()
        assert spans[0] == {"name": "q", "proc": "main", "trace": 9,
                            "span": sid, "parent": 3, "t0": 1.0,
                            "t1": 2.0, "args": {"n": 4}}
        tr.instant("mark", kind="kill")
        inst = tr.export()[-1]
        assert inst["t1"] is None and inst["t0"] == 5.0

    def test_end_pops_only_own_frame(self):
        tr = Tracer(clock=FakeClock())
        a = tr.begin("a", trace=tr.new_trace())
        b = tr.begin("b")
        tr.end(a)              # out-of-order: must not pop b's frame
        assert tr.current()[1] == b.id
        tr.end(b)
        assert tr.current() == (0, 0)

    def test_export_includes_open_spans(self):
        clk = FakeClock()
        tr = Tracer(clock=clk, process="m0")
        root = tr.begin("sync.push", trace=tr.new_trace(), groups=2)
        clk.advance(0.25)
        # export mid-span (what the pre-kill dump hook sees): the open
        # span appears, clipped at now and flagged partial, so children
        # already carrying its id don't orphan
        spans = tr.export()
        assert [s["name"] for s in spans] == ["sync.push"]
        d = spans[0]
        assert d["span"] == root.id and d["trace"] == root.trace
        assert d["t1"] == pytest.approx(d["t0"] + 0.25)
        assert d["args"] == {"groups": 2, "partial": True}
        # once ended normally it exports from the ring, unflagged
        tr.end(root)
        spans = tr.export()
        assert [s["name"] for s in spans] == ["sync.push"]
        assert spans[0]["args"] == {"groups": 2}
        tr.clear()
        assert tr.export() == []

    def test_configure_disable_roundtrip(self):
        assert not obs_trace.get_tracer().enabled
        tr = obs_trace.configure(enabled=True, capacity=8, process="w")
        assert obs_trace.get_tracer() is tr and tr.enabled
        assert tr.capacity == 8
        obs_trace.disable()
        assert not obs_trace.get_tracer().enabled


# ---------------------------------------------------------------------
# spans on the profiler's clock (annotate=True)
# ---------------------------------------------------------------------
def _profile_events(tmp_path, body) -> list:
    """Run ``body`` inside a ``jax.profiler`` session; the host events
    of the written ``.xplane.pb`` as (name, start_ns, end_ns)."""
    import jax
    from jax.profiler import ProfileData
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in ProfileData.from_file(str(path)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


class TestAnnotate:
    def test_nested_and_out_of_order_spans_reach_the_profile(self, tmp_path):
        tr = obs_trace.configure(enabled=True, annotate=True)

        def body():
            with tr.span("test.outer", rows=3):
                with tr.span("test.inner"):
                    pass
            a = tr.begin("test.first")
            b = tr.begin("test.second")
            tr.end(a)                       # ends before its child
            tr.end(b)

        ev = {n: (t0, t1) for n, t0, t1 in _profile_events(tmp_path, body)
              if n.startswith("test.")}
        # bare names: attributes stay in the ring, not in the profile
        assert set(ev) == {"test.outer", "test.inner", "test.first",
                           "test.second"}
        assert ev["test.outer"][0] <= ev["test.inner"][0]
        assert ev["test.inner"][1] <= ev["test.outer"][1]
        assert ev["test.first"][0] <= ev["test.second"][0]
        assert ev["test.first"][1] <= ev["test.second"][1]
        ring = {s["name"]: s for s in tr.export()}
        assert ring["test.outer"]["args"] == {"rows": 3}
        assert ring["test.second"]["parent"] == ring["test.first"]["span"]

    @pytest.mark.parametrize("enabled,annotate", [(False, True),
                                                  (True, False)])
    def test_no_annotation_unless_enabled_and_asked(self, monkeypatch,
                                                    enabled, annotate):
        import jax.profiler
        made = []
        monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                            lambda name: made.append(name))
        tr = obs_trace.configure(enabled=enabled, annotate=annotate)
        with tr.span("x"):
            pass
        tr.end(tr.begin("y"))
        assert made == []
        assert (tr.begin("z") is obs_trace._NULL_SPAN) == (not enabled)


# ---------------------------------------------------------------------
# perfetto
# ---------------------------------------------------------------------
class TestPerfetto:
    def _spans(self):
        clk = FakeClock(100.0)
        tr = Tracer(clock=clk, process="master-0")
        t = tr.new_trace()
        with tr.span("sync.push", trace=t, groups=1):
            clk.advance(0.010)
        tr.instant("fault.kill", trace=t, point="mid_flush")
        return tr.export()

    def test_chrome_structure(self):
        doc = perfetto.to_chrome(self._spans())
        phs = [e["ph"] for e in doc["traceEvents"]]
        assert phs.count("M") == 1        # one process track
        assert phs.count("X") == 1 and phs.count("i") == 1
        assert phs.count("s") == 1 and phs.count("t") == 1  # flow
        x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
        assert x["dur"] == pytest.approx(10_000.0)  # 10ms in us
        assert x["args"]["groups"] == 1

    def test_write_load_roundtrip(self, tmp_path):
        spans = self._spans()
        path = str(tmp_path / "t.json")
        n = perfetto.write_trace(path, spans)
        assert n == 2
        back = perfetto.load_spans(path)
        assert len(back) == len(spans)
        for a, b in zip(sorted(back, key=lambda s: s["span"]),
                        sorted(spans, key=lambda s: s["span"])):
            assert a["name"] == b["name"]
            assert a["proc"] == b["proc"]
            assert (a["trace"], a["span"], a["parent"]) == \
                (b["trace"], b["span"], b["parent"])
            assert a["t0"] == pytest.approx(b["t0"], abs=1e-6)
            assert (a["t1"] is None) == (b["t1"] is None)

    def test_merge_dedups_and_sorts(self):
        spans = self._spans()
        merged = perfetto.merge_spans(spans, spans, None, [])
        assert len(merged) == len(spans)
        assert merged == sorted(merged, key=lambda s: s["t0"])

    def test_viewer_summary(self, tmp_path, capsys):
        path = str(tmp_path / "t.json")
        perfetto.write_trace(path, self._spans())
        assert obs_trace.main([path]) == 0
        out = capsys.readouterr().out
        assert "sync.push" in out and "fault.kill" in out


# ---------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------
class TestMetrics:
    def test_primitives(self):
        reg = MetricsRegistry()
        c = reg.counter("a.count")
        c.inc()
        c.inc(2)
        g = reg.gauge("a.depth")
        g.set(7.0)
        h = reg.histogram("a.lat", window=8)
        for v in (1.0, 2.0, 3.0, 4.0):
            h.record(v)
        t = reg.tree()
        assert t["a"]["count"] == 3
        assert t["a"]["depth"] == 7.0
        assert t["a"]["lat"]["count"] == 4
        assert t["a"]["lat"]["p50"] == pytest.approx(2.5)

    def test_providers_arity(self):
        reg = MetricsRegistry()
        reg.register("x", lambda: {"a": 1})
        reg.register("y", lambda now: now * 2)
        t = reg.tree(3.0)
        assert t == {"x": {"a": 1}, "y": 6.0}

    def test_collect_flattens(self):
        reg = MetricsRegistry()
        reg.register("s.l", lambda: {"p50": 0.1, "p99": 0.9})
        assert reg.collect() == {"s.l.p50": 0.1, "s.l.p99": 0.9}
        assert reg.names() == ["s.l.p50", "s.l.p99"]

    def test_duplicate_name_raises(self):
        reg = MetricsRegistry()
        reg.counter("dup")
        with pytest.raises(ValueError):
            reg.register("dup", lambda: 1)

    def test_provider_merge_at_shared_prefix(self):
        reg = MetricsRegistry()
        reg.register("s.a", lambda: 1)
        reg.register("s", lambda: {"b": 2})
        assert reg.tree() == {"s": {"a": 1, "b": 2}}

    def test_join(self):
        assert obs_metrics.join("", "x") == "x"
        assert obs_metrics.join("a", "x") == "a.x"


# ---------------------------------------------------------------------
# in-process causal chain through the streaming update path
# ---------------------------------------------------------------------
class TestStreamingTraceChain:
    def _cluster(self):
        from repro.configs.weips_ctr import FM_FTRL
        from repro.core import ClusterConfig, WeiPSCluster
        return WeiPSCluster(FM_FTRL, ClusterConfig(
            num_master=1, num_slave=2, num_replicas=1,
            num_partitions=2))

    @staticmethod
    def _push_records():
        from repro.core.ps import MasterShard
        from repro.core.queue import Consumer, PartitionedQueue
        from repro.core.routing import RoutingPlan
        from repro.core.streaming import Pusher
        from repro.core.transform import make_transform
        from repro.optim import get_optimizer
        opt = get_optimizer("ftrl")
        master = MasterShard(0, {"w": 4}, opt)
        ids = np.arange(256, dtype=np.int64)
        master.apply_batch("w", ids, np.ones((256, 4), np.float32))
        q = PartitionedQueue(2)
        Pusher(master, q, RoutingPlan(1, 1, 2),
               make_transform("identity", opt)).push(
            {("w", "upsert"): ids}, now=0.0)
        return list(Consumer(q, (0, 1)).poll())

    def test_disabled_records_carry_no_trace_meta(self):
        recs = self._push_records()
        assert recs
        for r in recs:
            assert "trace" not in r.meta and "span" not in r.meta
        assert obs_trace.get_tracer().export() == []

    def test_enabled_records_stamp_trace_meta(self):
        obs_trace.configure(enabled=True, process="test")
        recs = self._push_records()
        assert recs
        tids = {r.meta["trace"] for r in recs}
        assert len(tids) == 1 and 0 not in tids
        for r in recs:
            assert r.meta["span"] and "t_push" in r.meta

    def test_enabled_chain_push_queue_apply_invalidate(self):
        obs_trace.configure(enabled=True, process="test")
        cl = self._cluster()
        ids = np.arange(64, dtype=np.int64).reshape(8, 8)
        cl.train_on_batch(ids, np.zeros(8, np.float32), now=0.0)
        cl.sync_tick(0.0)
        cl.predict(ids)                   # warm the serve cache
        cl.train_on_batch(ids, np.ones(8, np.float32), now=1.0)
        cl.sync_tick(1.0)                 # invalidates warm rows
        spans = obs_trace.get_tracer().export()
        names = {s["name"] for s in spans}
        assert {"sync.push", "sync.queue", "sync.apply",
                "cache.invalidate"} <= names

        # one causal tree: queue's parent is the push span, apply's
        # parent is the queue span, invalidate nests under apply
        pushes = {s["span"]: s for s in spans
                  if s["name"] == "sync.push"}
        queues = [s for s in spans if s["name"] == "sync.queue"]
        applies = {s["span"]: s for s in spans
                   if s["name"] == "sync.apply"}
        assert queues
        for q in queues:
            assert q["parent"] in pushes
            assert q["trace"] == pushes[q["parent"]]["trace"]
        for a in applies.values():
            parent_q = next(q for q in queues if q["span"] == a["parent"])
            assert parent_q["trace"] == a["trace"]
        invs = [s for s in spans if s["name"] == "cache.invalidate"]
        assert invs
        for inv in invs:
            assert inv["parent"] in applies
            assert inv["trace"] == applies[inv["parent"]]["trace"]

        # no orphans: every non-zero parent resolves to an exported span
        all_ids = {s["span"] for s in spans}
        for s in spans:
            assert s["parent"] == 0 or s["parent"] in all_ids

    def test_queue_span_measures_dwell(self):
        obs_trace.configure(enabled=True, process="test")
        cl = self._cluster()
        ids = np.arange(32, dtype=np.int64).reshape(4, 8)
        cl.train_on_batch(ids, np.zeros(4, np.float32), now=0.0)
        cl.sync_tick(0.0)
        queues = [s for s in obs_trace.get_tracer().export()
                  if s["name"] == "sync.queue"]
        assert queues
        for q in queues:
            assert q["t1"] >= q["t0"]     # push stamp precedes poll
