"""One run of one cell: find its files by name, check the device, build
and fill the system, warm up, measure the window (traced with
``--trace 1``), check the result against the plain reference, and print
the result line.

A cell ``<name>`` of ``BENCHMARK.json`` names a configuration and a
traffic mix; the harness reads the configuration's file,
``traffic/<traffic>.json`` and ``cells/<name>.json`` (the cell's own
numbers: its offered rate, its limits), and every metric's reader
``metrics/<metric>.py``, all under this directory. The configuration's
``family`` names ``families/<family>.py``, the module that knows its
model: it builds and fills the system, makes its traffic, reads back
what the program holds and gives the numbers compared with the plain
reference.
"""

from __future__ import annotations

import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

from harness import check, counts, peaks as peaks_mod

HERE = Path(__file__).resolve().parents[1]          # perfbench/
ROOT = HERE.parent


class Refused(Exception):
    """The run cannot be made here (device, spec): exit non-zero, print
    no result."""


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_spec(workload: str) -> dict:
    bench = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise Refused(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    confs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(ROOT / confs[w["config"]]["file"])
    traffic = load_json(HERE / "traffic" / f"{w['traffic']}.json")
    cell = load_json(HERE / "cells" / f"{workload}.json")
    return {"bench": bench, "workload": w, "cfg": cfg, "traffic": traffic,
            "cell": cell, "family": load_family(cfg.get("family"))}


def load_family(name: str):
    """The module ``families/<name>.py``: a configuration's ``family``.
    It gives ``build``, ``preseed``, ``train_stream``, ``requests``,
    ``collect``, ``judge``, ``readings`` and ``tiny``; ``families/
    ctr_ftrl.py`` shows what each takes and returns."""
    path = HERE / "families" / f"{name}.py"
    if not name or not path.is_file():
        raise Refused(f"no family module families/{name}.py")
    return _load(path, "perfbench_family_" + name)


def _load(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metrics_for(spec: dict, trace: bool) -> list:
    """The cell's end-to-end metrics (``trace`` False) or per-layer ones."""
    bench, name = spec["bench"], spec["workload"]["name"]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    if not trace:
        return e2e
    mine = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in mine)]


def reader(metric: str):
    return _load(HERE / "metrics" / f"{metric}.py",
                 "perfbench_metric_" + metric.replace(".", "_")).read


def device_info(chips: int, allow_cpu: bool = False) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    if d.platform != "tpu" and not allow_cpu:
        raise Refused(f"needs a TPU; JAX found platform {d.platform!r} "
                      f"({d.device_kind}, {len(devs)} devices)")
    if len(devs) < chips:
        raise Refused(f"needs {chips} chips; found {len(devs)}")
    return info


def execute(spec: dict, seed: int, seconds: float, trace: bool, *,
            allow_cpu: bool = False, log=print) -> SimpleNamespace:
    """Build, fill, warm, measure; then keep what the comparison and the
    metrics need and free the program's state."""
    import jax
    cfg, traffic, cell = spec["cfg"], spec["traffic"], spec["cell"]
    fam = spec["family"]
    dev = device_info(int(spec["workload"]["chips"]), allow_cpu)
    try:
        pk = peaks_mod.for_kind(dev["kind"])
    except peaks_mod.UnknownDevice:
        if not allow_cpu:
            raise
        pk = None
    from harness import drive

    t0 = time.perf_counter()
    compiles = drive.CompileCounter()
    cl = fam.build(cfg, seed)
    kind = traffic["kind"]
    train = kind == "train_stream"
    loaded = fam.preseed(cl, cfg, seed, masters=train, replicas=True)
    t_seed = time.perf_counter() - t0
    spans = drive.Spans(annotate=trace)
    if train:
        drv = drive.TrainDriver(cl, fam.train_stream(cfg, traffic, seed),
                                traffic, spans)
    elif kind == "open_loop_predict":
        drv = drive.ServeDriver(cl, fam.requests(cfg, traffic), traffic,
                                seed, spans, float(cell["rate_per_s"]))
    else:
        raise Refused(f"unknown traffic kind {kind!r}")
    drv.warm()
    # what set-up made stays alive all run: keep it out of the window's
    # garbage collections
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.3f} s (tables {t_seed:.3f} s, {loaded} rows)",
        file=sys.stderr)

    c0 = compiles.count
    tdir = None
    if trace:
        tdir = tempfile.mkdtemp(prefix="perfbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(tdir, profiler_options=opts)
    try:
        stats = drv.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    window_compiles = compiles.count - c0
    compiles.close()
    mem = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"window {stats['window_s']:.3f} s, compiles in window "
        f"{window_compiles}, peak bytes {mem}", file=sys.stderr)

    red = None
    if trace:
        from harness import trace as trace_mod
        paths = sorted(Path(tdir).glob("**/*.xplane.pb"))
        red = trace_mod.reduce_file(str(paths[-1])) if paths else None
        shutil.rmtree(tdir, ignore_errors=True)

    st = SimpleNamespace(dev=dev, peaks=pk, setup_s=setup_s, stats=stats,
                         compiles_in_window=window_compiles, mem=mem,
                         trace=red, train=train, spans=spans.t, out=None,
                         batches=None, events=None, stream_from=0,
                         sample=None, unique_per_batch=None)
    if train:
        st.out = fam.collect(cl, cfg, drv.batches)
        st.batches = drv.batches
        st.events = drv.events
        st.stream_from = drv.stream_from
        st.unique_per_batch = [
            drv.stream.unique_per_batch(b)
            for b in drv.batches[len(drv.batches) - drv.window_batches:]]
    else:
        st.sample = drv.sample
    del drv, cl
    gc.unfreeze()
    gc.collect()
    return st


def judge(spec: dict, seed: int, st: SimpleNamespace) -> dict:
    """The numbers compared with the plain reference (the family's)."""
    return spec["family"].judge(spec, seed, st)


def run(spec: dict, seed: int, seconds: float, trace: bool, *,
        allow_cpu: bool = False, log=print) -> dict:
    """One run of a cell; returns the result line's object."""
    st = execute(spec, seed, seconds, trace, allow_cpu=allow_cpu, log=log)
    correct, shown = check.verdict(judge(spec, seed, st),
                                   spec["cell"]["limits"])
    ctx = SimpleNamespace(
        setup_s=st.setup_s, window_s=st.stats["window_s"], stats=st.stats,
        spans=st.spans, trace=st.trace, peaks=st.peaks, counts=counts,
        cfg=spec["cfg"], traffic=spec["traffic"],
        compiles_in_window=st.compiles_in_window,
        unique_per_batch=st.unique_per_batch, train=st.train)
    metrics = {}
    for m in metrics_for(spec, trace):
        v = reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = st.dev
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"], "memory_peak_bytes": st.mem}
    result = {"correct": bool(correct),
              "attempted": int(st.stats["attempted"]),
              "failed": int(st.stats["failed"]),
              "metrics": metrics, "device": device}
    if st.trace is not None:
        device["busy_s"] = st.trace.busy_s
        device["window_s"] = st.trace.window_s
        result["breakdown"] = st.trace.breakdown()
    result["checks"] = shown
    for k, v in shown.items():
        log(f"check {k} = {v['value']!r} limit {v['limit']!r}",
            file=sys.stderr)
    return result


def main(argv) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec = load_spec(args.workload)
        device_info(int(spec["workload"]["chips"]))
        sys.path.insert(0, str(ROOT / "src"))
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        result = run(spec, args.seed, args.seconds, bool(args.trace))
    except (Refused, peaks_mod.UnknownDevice) as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result, allow_nan=False, default=_jsonable))
    return 0


def _jsonable(x):
    if isinstance(x, (np.integer,)):
        return int(x)
    if isinstance(x, (np.floating,)):
        v = float(x)
        return v if math.isfinite(v) else None
    raise TypeError(type(x))
