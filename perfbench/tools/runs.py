"""Runs of a cell as the check makes them, each a process of its own:
``--sets`` sets of the same seeds at ``run_seconds``, then traced runs;
then, per end-to-end metric, each set's median and spread (the distance
between the quartiles of ``statistics.quantiles(values, n=4)`` over the
median) and the wider spread of the sets.

    python3 perfbench/tools/runs.py --workload fm_ftrl.train_stream \\
        --seeds 1 2 3 4 5 6 --sets 2 --trace-seeds 7 8 9 \\
        --out runs.jsonl

The parent never imports JAX, so each child gets the chip.
"""

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def one(workload, seed, seconds, trace, timeout):
    t0 = time.perf_counter()
    p = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(int(trace))], cwd=ROOT, capture_output=True, text=True,
        timeout=timeout)
    lines = p.stdout.strip().splitlines()
    res = None
    if p.returncode == 0 and lines:
        res = json.loads(lines[-1])
    return {"seed": seed, "trace": int(trace), "rc": p.returncode,
            "wall_s": time.perf_counter() - t0, "result": res,
            "stderr_tail": p.stderr[-1500:]}


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / med if med else float("nan")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--trace-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--timeout", type=float, default=1200)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    seconds = args.seconds or bench["run_seconds"]
    plan = [(s, k, False) for k in range(args.sets) for s in args.seeds]
    plan += [(s, None, True) for s in args.trace_seeds]
    sets: dict = {}
    for seed, k, trace in plan:
        r = one(args.workload, seed, seconds, trace, args.timeout)
        r["set"] = k
        res = r["result"]
        brief = {"seed": seed, "set": k, "trace": int(trace),
                 "rc": r["rc"], "wall_s": round(r["wall_s"], 1)}
        if res:
            brief.update(correct=res["correct"], checks=res["checks"],
                         metrics={m: v["value"]
                                  for m, v in res["metrics"].items()},
                         mem=res["device"].get("memory_peak_bytes"))
            if trace:
                brief.update(busy_s=res["device"].get("busy_s"),
                             window_s=res["device"].get("window_s"),
                             breakdown=res.get("breakdown"))
            elif k is not None:
                sets.setdefault(k, []).append(res["metrics"])
        else:
            brief["stderr_tail"] = r["stderr_tail"]
        print(json.dumps(brief), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps({"workload": args.workload, **r}) + "\n")
    summary = {}
    for k, rows in sets.items():
        if len(rows) < 2:
            continue
        for m in rows[0]:
            vals = [r[m]["value"] for r in rows if m in r]
            # the first run of a set may compile; set-up is judged apart
            med, sp = spread(vals)
            summary.setdefault(m, {})[f"set{k}"] = {
                "median": med, "spread": sp, "values": vals}
    for m, d in summary.items():
        d["widest_spread"] = max(v["spread"] for v in d.values())
    print(json.dumps({"summary": summary}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
