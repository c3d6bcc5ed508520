"""Builds the system under test for a cell, fills its tables, warms every
shape the cell's traffic uses, and drives the measured window.

The program gets only generated inputs. The loops call the entry points
a user calls: ``make_train_pipeline().ingest``, ``train_scheduler.tick``,
the push half of ``sync_tick`` then ``Scatter.poll`` (train), and
``serving.submit`` / ``serving.flush`` (serve). Each call is a harness
span (host clock; a ``jax.profiler.TraceAnnotation`` too in a traced
run). The train batches the program forms are recorded at the training
plane's entry, in order, for the reference to replay.
"""

from __future__ import annotations

import contextlib
import time
from collections import defaultdict

import numpy as np

from harness import generate as gen


class Spans:
    """Host-clock spans by name, kept for the measured window."""

    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.t: dict = defaultdict(list)

    def reset(self) -> None:
        self.t = defaultdict(list)

    @contextlib.contextmanager
    def __call__(self, name: str):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax
            ann = jax.profiler.TraceAnnotation("bench." + name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.t[name].append((t0, time.perf_counter()))


class CompileCounter:
    """Counts executables JAX builds or loads from its cache (the backend
    compile event), so the window's count can be read apart from set-up's."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring
        self.count = 0
        self._cb = self._on_event
        jax.monitoring.register_event_duration_secs_listener(self._cb)

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.count += 1

    def close(self) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._cb)


# --------------------------------------------------------------------------
# the system under test
# --------------------------------------------------------------------------

def make_cluster(cfg: dict, seed: int):
    from repro.configs.weips_ctr import CTRConfig
    from repro.core import ClusterConfig, WeiPSCluster
    o = cfg["ftrl"]
    model = CTRConfig(name=cfg["name"], model_type=cfg["model_type"],
                      feature_space=int(sum(cfg["field_vocab"])),
                      fields=len(cfg["field_vocab"]),
                      embed_dim=int(cfg["groups"].get("v", 1)),
                      optimizer="ftrl", ftrl_alpha=o["alpha"],
                      ftrl_beta=o["beta"], ftrl_l1=o["l1"], ftrl_l2=o["l2"])
    c = cfg["cluster"]
    return WeiPSCluster(model, ClusterConfig(
        num_master=c["num_master"], num_slave=c["num_slave"],
        num_replicas=c["num_replicas"],
        num_partitions=c["num_partitions"], codec=c["codec"],
        codec_backend=c["codec_backend"], ps_backend=c["ps_backend"],
        join_window=c["join_window_s"],
        serve_cache_rows=c["serve_cache_rows"],
        seed=seed % (2 ** 31 - 1)))


def preseed(cl, cfg: dict, vocab: gen.Vocab, seed: int, *,
            masters: bool, replicas: bool) -> int:
    """Load every id of the vocabulary into the master tables (FTRL z, n,
    w) and/or the serving replicas (int8-coded w) with the probe-free
    bulk insert. Returns the rows loaded."""
    ids = vocab.all_ids()
    zeros = np.zeros(len(ids), np.int64)
    loaded = 0
    m_owner = cl.plan.master_shard(ids) if masters else None
    s_owner = cl.plan.slave_shard(ids) if replicas else None
    for gi, (g, dim) in enumerate(cfg["groups"].items()):
        z, n = gen.ftrl_state(ids, dim, seed, gi)
        w = gen.ftrl_w(z, n, cfg["ftrl"])
        if masters:
            for m in cl.masters:
                sel = np.flatnonzero(m_owner == m.shard_id)
                m.load_table_rows(g, {
                    "ids": ids[sel], "w": w[sel],
                    "slots": {"z": z[sel], "n": n[sel]},
                    "last_touch": zeros[sel], "touch_count": zeros[sel]})
                loaded += len(sel)
        del z, n
        if replicas:
            ws = gen.int8_roundtrip(w)
            for rs in cl.replica_sets:
                for shard in rs.replicas:
                    sel = np.flatnonzero(s_owner == shard.shard_id)
                    shard.tables[g].load_rows({
                        "ids": ids[sel], "w": ws[sel], "slots": {},
                        "last_touch": zeros[sel],
                        "touch_count": zeros[sel]})
                    loaded += len(sel)
            del ws
        del w
    return loaded


# --------------------------------------------------------------------------
# train stream
# --------------------------------------------------------------------------

class TrainDriver:
    """Closed loop: each tick ingests one batch of click events (simulated
    time advances ``tick_s``), trains what the join emitted, pushes the
    updates, then every serving replica polls them."""

    def __init__(self, cl, cfg: dict, traffic: dict, vocab: gen.Vocab,
                 seed: int, spans: Spans):
        from repro.data.streams import EventBatch
        self._batch_cls = EventBatch
        self.cl = cl
        self.cfg = cfg
        self.traffic = traffic
        self.vocab = vocab
        self.seed = seed
        self.spans = spans
        self.stream = gen.TrainStream(vocab, traffic, seed)
        self.pipe = cl.make_train_pipeline(emit_on_feedback=True)
        self.scn = cl.training.scenario()
        self.k = 0
        self.batches: list = []          # (ids, labels, weights) in order
        self.stream_from = 0             # batches[stream_from:]: the join's
        self.events: list = []           # (t, tick's events) offered
        self.staleness: list = []        # (seconds, records) per poll
        self.window_batches = 0
        orig = cl.training.train_batch

        def recorded(scn, ids, y, *, now=0.0, weights=None, bucket=None):
            ids = np.asarray(ids, np.int64)
            y = np.asarray(y, np.float32)
            w = np.ones(len(ids), np.float32) if weights is None else \
                np.asarray(weights, np.float32)
            self.batches.append((ids.copy(), y.copy(), w.copy()))
            return orig(scn, ids, y, now=now, weights=weights, bucket=bucket)

        cl.training.train_batch = recorded

    def _sync(self, stamp: float) -> None:
        with self.spans("push"):
            self.cl.sync_tick(stamp, scatter=False)
        for sc in self.cl.scatters:
            with self.spans("apply"):
                n = sc.poll()
            if n:
                self.staleness.append((time.perf_counter() - stamp, n))

    def tick(self) -> None:
        t = self.k * float(self.traffic["tick_s"])
        ev = self.stream.tick()
        self.events.append((t, ev))
        batch = self._batch_cls(
            t=t, view_ids=ev["view_ids"], feature_ids=ev["feature_ids"],
            labels=ev["labels"], fb_view_ids=ev["fb_view_ids"],
            fb_t=t + ev["fb_delay"])
        with self.spans("ingest"):
            self.pipe.ingest(batch)
        stamp = time.perf_counter()
        with self.spans("train_tick"):
            self.cl.train_scheduler.tick(t)
        self._sync(stamp)
        self.k += 1

    def warm(self) -> None:
        """Every train bucket once with zero-weight batches, of hot ids and
        of ids spread over the vocabulary (so the per-call id counts reach
        every power-of-two the window's calls can take), each pushed and
        polled; then ``warm_ticks`` ticks of the stream itself."""
        r = gen.rng(self.seed, 7)
        for b in self.pipe.buckets:
            for spread in (False, True):
                if spread:
                    ids = (r.integers(0, self.vocab.total,
                                      (b, self.vocab.fields))
                           .astype(np.int64))
                else:
                    ids = self.vocab.sample(r, b, float(self.traffic["zipf_a"]))
                self.cl.training.train_batch(
                    self.scn, ids, np.zeros(b, np.float32),
                    weights=np.zeros(b, np.float32), bucket=b)
                self._sync(time.perf_counter())
        self.stream_from = len(self.batches)
        for _ in range(int(self.traffic["warm_ticks"])):
            self.tick()

    def window(self, seconds: float) -> dict:
        ex0 = self.scn.stats.examples
        shed0 = self.pipe.shed_examples
        b0 = len(self.batches)
        self.staleness = []
        self.spans.reset()
        ticks = 0
        t0 = time.perf_counter()
        with self.spans("window"):
            while True:
                self.tick()
                ticks += 1
                if time.perf_counter() - t0 >= seconds:
                    break
        t1 = time.perf_counter()
        self.window_batches = len(self.batches) - b0
        trained = self.scn.stats.examples - ex0
        shed = self.pipe.shed_examples - shed0
        stale = np.concatenate([np.full(n, s) for s, n in self.staleness]) \
            if self.staleness else np.empty(0)
        return {"window_s": t1 - t0, "ticks": ticks,
                "examples": trained, "attempted": trained + shed,
                "failed": shed, "staleness_s": stale}

    def collect(self) -> dict:
        """Master rows and replica rows of every id the batches touched, as
        the program holds them after the window."""
        ids = np.unique(np.concatenate([b[0].reshape(-1)
                                        for b in self.batches]))
        owner = self.cl.plan.master_shard(ids)
        sowner = self.cl.plan.slave_shard(ids)
        masters, reps = {}, {}
        for g, dim in self.cfg["groups"].items():
            out = {k: np.empty((len(ids), dim), np.float32)
                   for k in ("z", "n", "w")}
            for m in self.cl.masters:
                sel = np.flatnonzero(owner == m.shard_id)
                t = m.tables[g]
                w, slots = t.read_rows(t.lookup(ids[sel]))
                out["w"][sel], out["z"][sel], out["n"][sel] = \
                    w, slots["z"], slots["n"]
            masters[g] = out
            rep = []
            for rs in self.cl.replica_sets:
                for shard in rs.replicas:
                    sel = np.flatnonzero(sowner == shard.shard_id)
                    t = shard.tables[g]
                    w, _ = t.read_rows(t.lookup(ids[sel]))
                    rep.append((sel, w))
            reps[g] = rep
        return {"ids": ids, "masters": masters, "replicas": reps}


# --------------------------------------------------------------------------
# open-loop predict
# --------------------------------------------------------------------------

class ServeDriver:
    """Open loop: requests are due on a fixed schedule whatever the server
    does; one process offers each due request (submit) and answers it
    (flush), one request per flush, oldest first. A request's latency runs
    from its due time to the return of the flush that answered it."""

    def __init__(self, cl, cfg: dict, traffic: dict, vocab: gen.Vocab,
                 seed: int, spans: Spans, rate: float):
        self.cl = cl
        self.cfg = cfg
        self.traffic = traffic
        self.vocab = vocab
        self.seed = seed
        self.spans = spans
        self.rate = rate
        self.scn = cl.serving.scenario()
        self.sample: list = []

    def _request(self, r, size: int) -> np.ndarray:
        return self.vocab.sample(r, int(size), float(self.traffic["zipf_a"]))

    def warm(self) -> None:
        """Answer ``warm_requests`` requests of the cell's own traffic
        (sizes as the window's, ids from another stream), one at a time:
        this fills the serve cache and compiles what those sizes and miss
        counts need."""
        r = gen.rng(self.seed, 8)
        n = int(self.traffic["warm_requests"])
        for size in gen.request_sizes(self.traffic, n)[r.permutation(n)]:
            self.cl.serving.submit(self._request(r, size))
            self.cl.serving.flush()

    def window(self, seconds: float) -> dict:
        due, sizes = gen.serve_schedule(self.traffic, self.rate, seconds,
                                        self.seed)
        r = gen.rng(self.seed, 9)
        reqs = [self._request(r, s) for s in sizes]
        n = len(reqs)
        pick = set(gen.rng(self.seed, 10).choice(
            n, size=min(n, int(self.traffic["check_requests"])),
            replace=False).tolist())
        pick.add(int(np.argmax(sizes)))
        lat = np.empty(n)
        failed = 0
        self.sample = []
        self.spans.reset()
        self.scn.cache.window_stats()
        serving = self.cl.serving
        t0 = time.perf_counter() + 0.001
        with self.spans("window"):
            for i in range(n):
                t_due = t0 + due[i]
                wait = t_due - time.perf_counter()
                if wait > 0:
                    with self.spans("idle"):
                        time.sleep(wait)
                with self.spans("submit"):
                    serving.submit(reqs[i])
                with self.spans("flush"):
                    out = serving.flush()
                lat[i] = time.perf_counter() - t_due
                p = out[-1] if out else None
                if p is None or len(p) != len(reqs[i]):
                    failed += 1
                elif i in pick:
                    self.sample.append((reqs[i], np.asarray(p)))
        t1 = time.perf_counter()
        return {"window_s": t1 - t0, "requests": n, "attempted": n,
                "failed": failed, "latency_s": lat,
                "examples": int(sizes.sum()),
                "cache": self.scn.cache.window_stats()}
