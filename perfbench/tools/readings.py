"""The readings a cell's limits are set from: on each seed, one run of
the cell at its own size (a short window), then its family's readings
(``ctr_ftrl``: the program's numbers against the float32 reference, the
control's, the reference in bfloat16 in the program's place, and, for
train cells, the planted faults': state left unchanged, half of each
batch left out). One process for all seeds, so set-up compiles once.

    python3 perfbench/tools/readings.py --workload <cell> --seconds 5 \\
        --seeds 11 12 13 [--out readings.jsonl]

Needs a TPU, as the benchmark does. The benchmark's own runs never run
the control.
"""

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from harness import bench
    spec = bench.load_spec(args.workload)
    bench.device_info(int(spec["workload"]["chips"]))
    sys.path.insert(0, str(bench.ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    rows = []
    for seed in args.seeds:
        st = bench.execute(spec, seed, args.seconds, False)
        r = {"seed": seed, "setup_s": st.setup_s,
             **spec["family"].readings(spec, seed, st)}
        rows.append(r)
        print(json.dumps(r), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps({"workload": args.workload, **r}) + "\n")
    keys = rows[0]["program"].keys()
    summary = {k: {"program_max": max(r["program"][k] for r in rows),
                   **{f"{kind}_min": min(r[kind][k] for r in rows)
                      for kind in rows[0] if kind not in
                      ("seed", "setup_s", "program")}}
               for k in keys}
    print(json.dumps({"summary": summary}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
