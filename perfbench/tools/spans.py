"""Traced runs of a cell with the program's own spans on: the benchmark's
``--trace 1`` run, with the program's tracer (``repro.obs.trace``,
``annotate=True``) and its ``device_io`` counters read over the window,
so the per-layer metrics staged here can read host work inside the
program by span, on the device trace's clock.

    python3 perfbench/tools/spans.py --workload fm_ftrl.train_stream \\
        --seeds 11 12 [--seconds 10] [--out spans.jsonl]

Prints, per seed, the result line as ``perfbench/run.py --trace 1`` prints
it, with the staged metrics among the others; ``end_to_end``, the traced
window's train rate and staleness p95 (to set beside an untraced run of
the same seed); and ``program`` beside ``breakdown``: self seconds (and
count) of each program span in the window, the device's idle gaps put
down to the innermost program span over them, the share of the idle time
so put, how much of each harness span program spans cover, and the
blocking reads' time by the span they sit in. One process for all seeds.
Needs a TPU, as the benchmark does.

The harness runs as it is: the tool wraps the train driver's window
(tracer on, counters read) and the trace reduction (program spans from
the same file).
"""

import argparse
import json
import sys
from pathlib import Path
from types import SimpleNamespace

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

# per-layer metrics whose readers are under metrics/ and which
# BENCHMARK.json does not list yet (PERF.md, Open questions)
STAGED = [
    {"name": "train_host_ms.train", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "training plane",
     "moves": "train_examples_per_s"},
    {"name": "ps_host_ms.train", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "master shard",
     "moves": "train_examples_per_s"},
    {"name": "device_wait_ms.train", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "host-device boundary",
     "moves": "train_examples_per_s"},
    {"name": "replica_host_ms.train", "unit": "ms", "better": "lower",
     "source": "program_span", "layer": "sync plane, replica side",
     "moves": "staleness_p95_ms"},
    {"name": "h2d_bytes_per_example.train", "unit": "B", "better": "lower",
     "source": "program_counter", "layer": "host-device boundary",
     "moves": "train_examples_per_s"},
    {"name": "d2h_bytes_per_example.train", "unit": "B", "better": "lower",
     "source": "program_counter", "layer": "host-device boundary",
     "moves": "train_examples_per_s"},
]


END_TO_END = ("train_examples_per_s", "staleness_p95_ms")


def staged(spec: dict) -> dict:
    """``spec`` with the staged metrics listed for its cell."""
    cell = spec["workload"]["name"]
    spec["bench"]["per_layer"] = spec["bench"]["per_layer"] + [
        {**m, "workloads": [cell]} for m in STAGED]
    return spec


class Hooks:
    """While entered, wraps the train driver's window and the trace
    reduction; keeps the last window's program spans."""

    def __init__(self):
        self.program = None
        self.end_to_end = None

    def __enter__(self):
        from harness import bench, drive, spans, trace
        self._saved = (drive.TrainDriver.window, trace.reduce_file)
        window = drive.TrainDriver.window
        hooks = self

        def traced_window(drv, seconds):
            io0 = spans.device_io()
            on = spans.tracer_on()
            try:
                stats = window(drv, seconds)
            finally:
                if on:
                    spans.tracer_off()
            stats["device_io"] = spans.io_delta(io0, spans.device_io())
            ctx = SimpleNamespace(train=True, window_s=stats["window_s"],
                                  stats=stats)
            hooks.end_to_end = {m: bench.reader(m)(ctx) for m in END_TO_END}
            return stats

        def reduce_file(path):
            planes = spans.load(path)
            red = trace.reduce_planes(planes)
            red.program_spans = hooks.program = spans.reduce_planes(planes)
            return red

        drive.TrainDriver.window = traced_window
        trace.reduce_file = reduce_file
        return self

    def __exit__(self, *exc):
        from harness import drive, trace
        drive.TrainDriver.window, trace.reduce_file = self._saved
        return False


def run(spec: dict, seed: int, seconds: float, hooks: Hooks, **kw) -> dict:
    """One traced run (``bench.run``'s keywords pass through), with the
    program's spans as ``program``."""
    from harness import bench
    hooks.program = hooks.end_to_end = None
    result = bench.run(spec, seed, seconds, True, **kw)
    result["end_to_end"] = hooks.end_to_end
    if hooks.program is not None:
        result["program"] = hooks.program.breakdown()
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from harness import bench
    spec = staged(bench.load_spec(args.workload))
    bench.device_info(int(spec["workload"]["chips"]))
    sys.path.insert(0, str(bench.ROOT / "src"))
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    seconds = args.seconds or spec["bench"]["run_seconds"]
    with Hooks() as hooks:
        for seed in args.seeds:
            result = run(spec, seed, seconds, hooks)
            line = json.dumps({"seed": seed, **result}, allow_nan=False,
                              default=bench._jsonable)
            print(line, flush=True)
            if args.out:
                with open(args.out, "a") as f:
                    f.write(json.dumps({"workload": args.workload,
                                        **json.loads(line)}) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
