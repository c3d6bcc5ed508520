"""Builds the system under test for a cell, fills its tables, warms every
shape the cell's traffic uses, and drives the measured window.

The program gets only generated inputs. The loops call the entry points
a user calls: ``make_train_pipeline().ingest``, ``train_scheduler.tick``,
the push half of ``sync_tick`` then ``Scatter.poll`` (train), and
``serving.submit`` / ``serving.flush`` (serve). Each call is a harness
span (host clock; a ``jax.profiler.TraceAnnotation`` too in a traced
run). Every ``train_batch`` call the program makes is recorded at the
training plane's entry, in order, with a copy of each of its arguments,
for the reference to replay.

Nothing here knows the model: the configuration's family
(``perfbench/families/<family>.py``) builds and fills the system, makes
each tick's events and batch, the warm batches and the predict requests,
and reads back what the program holds.
"""

from __future__ import annotations

import contextlib
import inspect
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
# train stream
# --------------------------------------------------------------------------

class TrainDriver:
    """Closed loop: each tick ingests one batch of click events (simulated
    time advances ``tick_s``), trains what the join emitted, pushes the
    updates, then every serving replica polls them. ``stream`` is the
    family's train stream."""

    def __init__(self, cl, stream, traffic: dict, spans: Spans):
        self.cl = cl
        self.stream = stream
        self.traffic = traffic
        self.spans = spans
        self.pipe = cl.make_train_pipeline(emit_on_feedback=True)
        self.scn = cl.training.scenario()
        self.k = 0
        self.batches: list = []          # train_batch arguments, in order
        self.stream_from = 0             # batches[stream_from:]: the join's
        self.events: list = []           # (t, tick's events) offered
        self.staleness: list = []        # (seconds, records) per poll
        self.window_batches = 0
        orig = cl.training.train_batch
        sig = inspect.signature(orig)

        def recorded(*args, **kw):
            call = sig.bind(*args, **kw)
            call.apply_defaults()
            # arrays copied; the scenario and scalars as given
            self.batches.append({
                k: v.copy() if isinstance(v, np.ndarray) else v
                for k, v in call.arguments.items()})
            return orig(*args, **kw)

        cl.training.train_batch = recorded

    def _sync(self, stamp: float) -> None:
        with self.spans("push"):
            self.cl.sync_tick(stamp, scatter=False)
        for sc in self.cl.scatters:
            with self.spans("apply"):
                records = sc.poll()
            if records:
                self.staleness.append((time.perf_counter() - stamp, records))

    def tick(self) -> None:
        t = self.k * float(self.traffic["tick_s"])
        ev, batch = self.stream.tick(t)
        self.events.append((t, ev))
        with self.spans("ingest"):
            self.pipe.ingest(batch)
        stamp = time.perf_counter()
        with self.spans("train_tick"):
            self.cl.train_scheduler.tick(t)
        self._sync(stamp)
        self.k += 1

    def warm(self) -> None:
        """The stream's warm batches (zero weights, every train bucket),
        each trained, pushed and polled; then ``warm_ticks`` ticks of the
        stream itself."""
        for args, kw in self.stream.warm_batches(self.pipe.buckets):
            self.cl.training.train_batch(self.scn, *args, **kw)
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
        stale = np.concatenate([np.full(k, s) for s, k in self.staleness]) \
            if self.staleness else np.empty(0)
        return {"window_s": t1 - t0, "ticks": ticks,
                "examples": trained, "attempted": trained + shed,
                "failed": shed, "staleness_s": stale}


# --------------------------------------------------------------------------
# open-loop predict
# --------------------------------------------------------------------------

class ServeDriver:
    """Open loop: requests are due on a fixed schedule whatever the server
    does; one process offers each due request (submit) and answers it
    (flush), one request per flush, oldest first. A request's latency runs
    from its due time to the return of the flush that answered it."""

    def __init__(self, cl, draw, traffic: dict, seed: int, spans: Spans,
                 rate: float):
        self.cl = cl
        self.draw = draw                 # the family's request(rng, size)
        self.traffic = traffic
        self.seed = seed
        self.spans = spans
        self.rate = rate
        self.scn = cl.serving.scenario()
        self.sample: list = []

    def warm(self) -> None:
        """Answer ``warm_requests`` requests of the cell's own traffic
        (sizes as the window's, ids from another stream), one at a time:
        this fills the serve cache and compiles what those sizes and miss
        counts need."""
        r = gen.rng(self.seed, 8)
        count = int(self.traffic["warm_requests"])
        for size in gen.request_sizes(self.traffic, count)[
                r.permutation(count)]:
            self.cl.serving.submit(self.draw(r, size))
            self.cl.serving.flush()

    def window(self, seconds: float) -> dict:
        due, sizes = gen.serve_schedule(self.traffic, self.rate, seconds,
                                        self.seed)
        r = gen.rng(self.seed, 9)
        reqs = [self.draw(r, s) for s in sizes]
        count = len(reqs)
        pick = set(gen.rng(self.seed, 10).choice(
            count, size=min(count, int(self.traffic["check_requests"])),
            replace=False).tolist())
        pick.add(int(np.argmax(sizes)))
        lat = np.empty(count)
        failed = 0
        self.sample = []
        self.spans.reset()
        self.scn.cache.window_stats()
        serving = self.cl.serving
        t0 = time.perf_counter() + 0.001
        with self.spans("window"):
            for i in range(count):
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
        return {"window_s": t1 - t0, "requests": count, "attempted": count,
                "failed": failed, "latency_s": lat,
                "examples": int(sizes.sum()),
                "cache": self.scn.cache.window_stats()}
