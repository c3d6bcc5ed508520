"""Each cell cut to a size the CPU runs in seconds (Pallas in interpret
mode), as its configuration's family cuts it (the family's ``tiny``)."""

from harness import bench

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
    cfg = bench.load_json(h / "configs" / f"{config}.json")
    return {"bench": b,
            "workload": {"name": workload, "config": config,
                         "traffic": traffic, "chips": 1},
            "cfg": cfg,
            "traffic": bench.load_json(h / "traffic" / f"{traffic}.json"),
            "cell": bench.load_json(h / "cells" / f"{workload}.json"),
            "family": bench.load_family(cfg["family"])}


def spec(workload: str) -> dict:
    s = load(workload)
    s["family"].tiny(s)
    return s


def run(workload: str, *, seed: int = 2 ** 33 + 17, seconds: float = 1.5,
        trace: bool = False) -> dict:
    return bench.run(spec(workload), seed, seconds, trace, allow_cpu=True,
                     log=lambda *a, **k: None)


def execute(workload: str, *, seed: int = 2 ** 33 + 17,
            seconds: float = 1.5):
    return bench.execute(spec(workload), seed, seconds, False,
                         allow_cpu=True, log=lambda *a, **k: None)
