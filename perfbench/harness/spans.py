"""The program's own spans in a profiler trace, reduced to the numbers
the staged per-layer metrics read.

With ``repro.obs.trace.configure(annotate=True)`` on in the window, the
host planes of the ``.xplane.pb`` hold the program's spans (``train.*``,
``ps.*``, ``device.wait``, ``sync.*``, ``cache.*``, ``serve.*``) beside
the harness's ``bench.*`` ones, on the device's clock. From them:

- Self time by span name in the window: a span's time less that of the
  spans nested in it on the same thread (profiler line). Each thread's
  spans are flattened into disjoint stretches, each owned by the
  innermost span over it; a span that outlives its parent is cut at the
  parent's end.
- Idle attribution: each stretch of a device idle gap goes to the
  innermost program span over it; what no program span covers goes to
  the harness span over it, else to ``other``.
- Cover: for each harness span name, the share of its time under some
  program span.
- Waits by site: ``device.wait`` time by the name of the span it sits in.

``tracer_on``/``tracer_off`` and ``device_io`` are the window's hooks:
the first two turn the program's tracer on with annotations and off
again, the third reads its host<->device counters. Each does nothing,
or returns None, where the program has no such option or counter.
"""

from __future__ import annotations

from collections import defaultdict
from dataclasses import dataclass, field

from harness.trace import OPS_LINE, WINDOW_SPAN, clip, gaps, union

PREFIXES = ("train.", "ps.", "device.", "sync.", "cache.", "serve.")
BENCH = "bench."
WAIT = "device.wait"


@dataclass
class ProgramSpans:
    self_ns: dict = field(default_factory=dict)      # name -> ns
    count: dict = field(default_factory=dict)        # name -> spans
    idle_ns: dict = field(default_factory=dict)      # label -> ns a device
    idle_total_ns: float = 0.0
    cover: dict = field(default_factory=dict)        # bench name -> share
    wait_under: dict = field(default_factory=dict)   # parent -> wait ns

    def self_ms(self, names=(), prefixes=()) -> float:
        return 1e-6 * sum(v for k, v in self.self_ns.items()
                          if k in names or k.startswith(tuple(prefixes)))

    @property
    def idle_program_share(self) -> float:
        """Share of the device's idle time put down to a program span."""
        if self.idle_total_ns <= 0:
            return 0.0
        prog = sum(v for k, v in self.idle_ns.items()
                   if k.startswith(PREFIXES))
        return prog / self.idle_total_ns

    def breakdown(self, top: int = 12) -> dict:
        own = sorted(self.self_ns.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.idle_ns.items(), key=lambda kv: -kv[1])[:top]
        return {"self_s": [[k, v * 1e-9, self.count.get(k, 0)]
                           for k, v in own],
                "idle_gaps": [[k, v * 1e-9] for k, v in idle],
                "idle_program_share": self.idle_program_share,
                "cover": dict(sorted(self.cover.items())),
                "wait_under_s": {k: v * 1e-9 for k, v in sorted(
                    self.wait_under.items(), key=lambda kv: -kv[1])}}


def flatten(spans) -> list:
    """``spans``: (name, start, end) of one thread. Disjoint sorted
    (start, end, name) stretches, each owned by the innermost span."""
    out = []
    stack: list = []                 # (end, name), innermost last
    cur = None

    def close_to(t):
        nonlocal cur
        while stack and stack[-1][0] <= t:
            end, name = stack.pop()
            if end > cur:
                out.append((cur, end, name))
                cur = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        if cur is not None:
            close_to(s)
            if stack and s > cur:
                out.append((cur, s, stack[-1][1]))
        cur = s if cur is None else max(cur, s)
        if stack:
            e = min(e, stack[-1][0])
        if e > s:
            stack.append((e, name))
    if stack:
        close_to(float("inf"))
    return out


def waits_under(spans, lo: float, hi: float) -> dict:
    """``device.wait`` time in [lo, hi) of one thread's spans, by the name
    of the innermost span it sits in (``-`` where none)."""
    out = defaultdict(float)
    stack: list = []                 # (end, name), innermost last
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        if name == WAIT:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                out[stack[-1][1] if stack else "-"] += d
        stack.append((e, name))
    return out


def disjoint(segs) -> list:
    """Sorted stretches of several threads made disjoint: a stretch that
    overlaps an earlier one keeps only its later part."""
    out, upto = [], float("-inf")
    for s, e, n in sorted(segs):
        s = max(s, upto)
        if e > s:
            out.append((s, e, n))
            upto = e
    return out


def overlaps(pieces, segs) -> tuple:
    """Disjoint sorted ``pieces`` (start, end) against disjoint sorted
    ``segs`` (start, end, name): the overlaps as (piece index, name, ns),
    and the parts of the pieces no stretch covers."""
    hits, left = [], []
    k = 0
    for i, (gs, ge) in enumerate(pieces):
        while k < len(segs) and segs[k][1] <= gs:
            k += 1
        cur, j = gs, k
        while j < len(segs) and segs[j][0] < ge:
            s, e, n = segs[j]
            if s > cur:
                left.append((cur, s))
            hi = min(e, ge)
            if hi > max(s, cur):
                hits.append((i, n, hi - max(s, cur)))
                cur = hi
            j += 1
        if ge > cur:
            left.append((cur, ge))
    return hits, left


def attribute(pieces, segs, into: dict) -> list:
    """Add each piece's overlaps with ``segs`` to ``into`` by name; return
    the uncovered parts of the pieces."""
    hits, left = overlaps(pieces, segs)
    for _, n, ns in hits:
        into[n] += ns
    return left


def reduce_planes(planes) -> ProgramSpans:
    """``planes`` as ``harness.trace.reduce_planes`` takes them."""
    prog_lines, bench_lines, dev_lines = [], [], []
    win = None
    for plane in planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            dev_lines.append({ln.name: list(ln.events) for ln in lines})
            continue
        for ln in lines:
            prog, bench = [], []
            for e in ln.events:
                t = (e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns))
                if e.name == WINDOW_SPAN:
                    win = win or t[1:]
                elif e.name.startswith(BENCH):
                    bench.append(t)
                elif e.name.startswith(PREFIXES):
                    prog.append(t)
            if prog:
                prog_lines.append(prog)
            if bench:
                bench_lines.append(bench)
    if win is None:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = win
    out = ProgramSpans()
    self_ns, count = defaultdict(float), defaultdict(int)
    psegs, wait_under = [], defaultdict(float)
    for spans in prog_lines:
        for n, s, _ in spans:
            if lo <= s < hi:
                count[n] += 1
        for k, v in waits_under(spans, lo, hi).items():
            wait_under[k] += v
        for s, e, n in flatten(spans):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                self_ns[n] += e - s
                psegs.append((s, e, n))
    psegs = disjoint(psegs)
    bsegs = disjoint([seg for spans in bench_lines
                      for seg in flatten(spans)])
    total, cover = defaultdict(float), defaultdict(float)
    for s, e, n in bsegs:
        total[n] += e - s
    for i, _, ns in overlaps([(s, e) for s, e, _ in bsegs], psegs)[0]:
        cover[bsegs[i][2]] += ns
    out.cover = {n: cover[n] / total[n] for n in total if total[n] > 0}

    idle, ran, idle_total = defaultdict(float), 0, 0.0
    for lines in dev_lines:
        ops = lines.get(OPS_LINE, [])
        iv = clip(union((float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for e in ops), lo, hi)
        if not iv:
            continue
        ran += 1
        g = gaps(iv, lo, hi)
        idle_total += sum(e - s for s, e in g)
        rest = attribute(g, psegs, idle)
        rest = attribute(rest, bsegs, idle)
        idle["other"] += sum(e - s for s, e in rest)
    n = max(ran, 1)
    out.self_ns = dict(self_ns)
    out.count = dict(count)
    out.wait_under = dict(wait_under)
    out.idle_ns = {k: v / n for k, v in idle.items() if v > 0}
    out.idle_total_ns = idle_total / n
    return out


def per_tick_ms(ctx, names=(), prefixes=()):
    """Self milliseconds a tick of the named spans in a traced window with
    the program's spans, or None where the window has none of them."""
    p = getattr(ctx.trace, "program_spans", None)
    ticks = ctx.stats.get("ticks") or 0
    if p is None or not ticks or not any(
            k in names or k.startswith(tuple(prefixes)) for k in p.self_ns):
        return None
    return p.self_ms(names, prefixes) / ticks


def bytes_per_example(ctx, key: str):
    """A ``device_io`` counter's window delta over the examples trained,
    or None where the window did not read the counters."""
    io = ctx.stats.get("device_io")
    ex = ctx.stats.get("examples") or 0
    if not io or not ex:
        return None
    return io[key] / ex


def load(path: str) -> list:
    from jax.profiler import ProfileData
    return list(ProfileData.from_file(path).planes)


# --------------------------------------------------------------------------
# the window's hooks into the program
# --------------------------------------------------------------------------

def tracer_on() -> bool:
    """The program's tracer on, its spans mirrored into the profiler
    trace; False where the program cannot annotate."""
    from repro.obs import trace as obs_trace
    try:
        obs_trace.configure(enabled=True, annotate=True, capacity=1 << 16)
    except TypeError:
        return False
    return True


def tracer_off() -> None:
    from repro.obs import trace as obs_trace
    obs_trace.disable()


def device_io():
    """The program's host<->device counters, or None where it has none."""
    try:
        from repro.kernels.device_io import DEVICE_IO
    except ImportError:
        return None
    return DEVICE_IO.metrics()


def io_delta(before, after):
    if before is None or after is None:
        return None
    return {k: after[k] - before[k] for k in after}
