"""Offered-rate sweep of an open-loop serve cell: one set-up, then a
window at each rate, reporting latency percentiles, whether the backlog
grew (the mean latency of the last fifth of the window's requests
against the first fifth), the serve cache's hit rate, and the programs
compiled in the window. The cell need not be listed in
``BENCHMARK.json``: its configuration, traffic and cell files are named.

    python3 perfbench/tools/sweep.py --cell fm_ftrl.serve_zipf \\
        --config fm_ftrl_criteo --traffic serve_zipf \\
        --seconds 10 --rates 25 25 25 50 100

Needs a TPU, as the benchmark does. The knee is the highest rate whose
backlog does not grow; a cell's file takes about four fifths of it.
"""

import argparse
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 11)
    args = ap.parse_args(argv)
    from harness import bench, check, drive
    bench.device_info(1)
    sys.path.insert(0, str(bench.ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    cfg = bench.load_json(HERE / "configs" / f"{args.config}.json")
    traffic = bench.load_json(HERE / "traffic" / f"{args.traffic}.json")
    cell = bench.load_json(HERE / "cells" / f"{args.cell}.json")
    fam = bench.load_family(cfg["family"])
    t0 = time.perf_counter()
    compiles = drive.CompileCounter()
    cl = fam.build(cfg, args.seed)
    fam.preseed(cl, cfg, args.seed, masters=False, replicas=True)
    drv = drive.ServeDriver(cl, fam.requests(cfg, traffic), traffic,
                            args.seed, drive.Spans(), args.rates[0])
    drv.warm()
    print(json.dumps({"setup_s": time.perf_counter() - t0,
                      "setup_compiles": compiles.count}), flush=True)
    for i, rate in enumerate(args.rates):
        # each window draws its own requests; the tables keep --seed's rows
        drv.seed = args.seed + 1 + i
        drv.rate = rate
        c0 = compiles.count
        st = drv.window(args.seconds)
        lat = st["latency_s"] * 1e3
        k = max(1, len(lat) // 5)
        served = SimpleNamespace(train=False, sample=drv.sample)
        ok, shown = check.verdict(fam.judge({"cfg": cfg}, args.seed, served),
                                  cell["limits"])
        row = {"rate": rate, "requests": len(lat),
               "compiles": compiles.count - c0,
               "p50_ms": float(np.percentile(lat, 50)),
               "p95_ms": float(np.percentile(lat, 95)),
               "first_fifth_ms": float(lat[:k].mean()),
               "last_fifth_ms": float(lat[-k:].mean()),
               "window_s": st["window_s"], "failed": st["failed"],
               "hit_rate": st["cache"]["hit_rate"], "correct": ok,
               "checks": shown}
        print(json.dumps(row), flush=True)
    compiles.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
