"""Each cell cut to a size the CPU runs in seconds (Pallas in interpret
mode): a few hundred ids a field, a few hundred events a tick, short
requests. Widths, fields' count, layout and codec stay as configured."""

from harness import bench

VOCAB = [64, 3, 500, 2000, 37, 900]

# cells whose files are here but which BENCHMARK.json does not list
# (PERF.md, Open questions): cell -> (its configuration, its traffic)
STAGED = {"fm_ftrl.serve_zipf": ("fm_ftrl_criteo", "serve_zipf"),
          "lr_ftrl.train_stream": ("lr_ftrl_criteo", "train_stream")}


def load(workload: str) -> dict:
    if workload not in STAGED:
        return bench.load_spec(workload)
    config, traffic = STAGED[workload]
    h = bench.HERE
    b = bench.load_json(bench.ROOT / "BENCHMARK.json")
    # a staged cell reports what a listed cell of its traffic reports
    like = next((w["name"] for w in b["workloads"]
                 if w["traffic"] == traffic), None)
    for m in b["end_to_end"] + b["per_layer"]:
        if like in m.get("workloads", ()):
            m["workloads"] = m["workloads"] + [workload]
    return {"bench": b,
            "workload": {"name": workload, "config": config,
                         "traffic": traffic, "chips": 1},
            "cfg": bench.load_json(h / "configs" / f"{config}.json"),
            "traffic": bench.load_json(h / "traffic" / f"{traffic}.json"),
            "cell": bench.load_json(h / "cells" / f"{workload}.json")}


def spec(workload: str) -> dict:
    s = load(workload)
    s["cfg"]["field_vocab"] = list(VOCAB)
    s["cfg"]["sizing"]["ids_per_master"] = sum(VOCAB) // 4
    t = s["traffic"]
    if t["kind"] == "train_stream":
        t.update(events_per_tick=256, warm_ticks=2)
    else:
        t.update(max_examples=64, warm_requests=8, check_requests=8)
        s["cell"]["rate_per_s"] = 20.0
    return s


def run(workload: str, *, seed: int = 2 ** 33 + 17, seconds: float = 1.5,
        trace: bool = False) -> dict:
    return bench.run(spec(workload), seed, seconds, trace, allow_cpu=True,
                     log=lambda *a, **k: None)


def execute(workload: str, *, seed: int = 2 ** 33 + 17,
            seconds: float = 1.5):
    return bench.execute(spec(workload), seed, seconds, False,
                         allow_cpu=True, log=lambda *a, **k: None)
