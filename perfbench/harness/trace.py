"""Reduction of a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the numbers the per-layer metrics read.

- The window is the harness's ``bench.window`` annotation.
- Device busy time is the union of the intervals of the ops on each
  device plane's ``XLA Ops`` line, clipped to the window, averaged over
  the device planes that ran anything; idle is the rest of the window.
- Program time is the summed duration of ``XLA Modules`` events by
  program name (``jit__ftrl_program(1234)`` -> ``jit__ftrl_program``).
- Op time by pattern sums the ``XLA Ops`` events whose name matches.
- Each idle gap is put to the harness span (``bench.*``) that overlaps
  it most, or to ``other``.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from dataclasses import dataclass, field

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


@dataclass
class Reduction:
    window_ns: tuple = (0, 0)
    busy_ns: float = 0.0                 # mean over devices that ran
    devices: int = 0
    modules_ns: dict = field(default_factory=dict)
    op_events: list = field(default_factory=list)    # (name, start, dur)
    idle_by_span_ns: dict = field(default_factory=dict)

    @property
    def window_s(self) -> float:
        return (self.window_ns[1] - self.window_ns[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        return self.busy_ns * 1e-9

    def op_ns(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return float(sum(d for n, _, d in self.op_events if rx.search(n)))

    def module_ns(self, pattern: str) -> float:
        rx = re.compile(pattern)
        return float(sum(v for k, v in self.modules_ns.items()
                         if rx.search(k)))

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.modules_ns.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.idle_by_span_ns.items(),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v * 1e-9] for k, v in ops],
                "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


def union(intervals) -> list:
    """Merge (start, end) intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: list, lo: float, hi: float) -> list:
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


def reduce_planes(planes) -> Reduction:
    """``planes``: iterable of objects with ``name`` and ``lines``; lines
    with ``name`` and ``events``; events with ``name``, ``start_ns`` and
    ``duration_ns`` (``ProfileData``'s shape, or a test's stand-in)."""
    spans = []
    dev_lines = []
    for plane in planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:"):
            dev_lines.append({ln.name: list(ln.events) for ln in lines})
            continue
        for ln in lines:
            for e in ln.events:
                if e.name.startswith("bench."):
                    spans.append((e.name, float(e.start_ns),
                                  float(e.start_ns + e.duration_ns)))
    win = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    lo, hi = win[0]
    red = Reduction(window_ns=(lo, hi))
    # the harness's spans inside the window follow one another (one
    # thread), so sorted by start they are sorted by end too
    inner = sorted((t for t in spans if t[0] != WINDOW_SPAN),
                   key=lambda t: t[1])
    ends = [e for _, _, e in inner]
    busy_total, ran = 0.0, 0
    idle = defaultdict(float)
    modules = defaultdict(float)
    for lines in dev_lines:
        ops = lines.get(OPS_LINE, [])
        iv = clip(union((float(e.start_ns), float(e.start_ns + e.duration_ns))
                        for e in ops), lo, hi)
        if not iv:
            continue
        ran += 1
        busy_total += sum(e - s for s, e in iv)
        for e in ops:
            s = float(e.start_ns)
            if lo <= s < hi:
                red.op_events.append((e.name, s, float(e.duration_ns)))
        for e in lines.get(MODULES_LINE, []):
            s = float(e.start_ns)
            if lo <= s < hi:
                modules[_module_name(e.name)] += float(e.duration_ns)
        for gs, ge in gaps(iv, lo, hi):
            best, label = 0.0, "other"
            k = bisect.bisect_right(ends, gs)
            while k < len(inner) and inner[k][1] < ge:
                n, s, e = inner[k]
                ov = min(e, ge) - max(s, gs)
                if ov > best:
                    best, label = ov, n
                k += 1
            idle[label] += ge - gs
    red.devices = ran
    red.busy_ns = busy_total / ran if ran else 0.0
    red.modules_ns = dict(modules)
    red.idle_by_span_ns = {k: v / max(ran, 1) for k, v in idle.items()}
    return red


def reduce_file(path: str) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_planes(ProfileData.from_file(path).planes)
