"""A new model reaches the benchmark as new files alone. In a copy of
``perfbench/`` and ``BENCHMARK.json``, a second family is added as a
module, a configuration, a traffic mix and a cell of their own, with
entries appended to ``BENCHMARK.json``; its cell runs through the
harness as it is (``bench.run`` on the CPU, in a process of its own
that imports the copy) and is judged, its own number among the checks;
and no file of ``perfbench/`` that was there before changed."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from harness import bench

ROOT = bench.ROOT

# ctr_ftrl's model and arithmetic with a stream of its own: each id drawn
# uniformly from the ``hot_ids`` coldest ranks of its field (ctr_ftrl's
# power law is hottest at rank 0), and one more number compared
FAMILY = '''
import numpy as np

from harness import bench
from harness import generate as gen

base = bench.load_family("ctr_ftrl")
build, preseed, requests, collect, readings, tiny = (
    base.build, base.preseed, base.requests, base.collect, base.readings,
    base.tiny)


def hot_set(cfg, traffic):
    v = gen.Vocab(cfg["field_vocab"])
    k = int(traffic["hot_ids"])
    return np.concatenate([v.ids(f, np.arange(max(0, s - k), s))
                           for f, s in enumerate(v.sizes.tolist())])


class Stream(base.Stream):
    def __init__(self, cfg, traffic, seed):
        super().__init__(cfg, traffic, seed)
        self.r = gen.rng(seed, 40)
        self.view = 0

    def tick(self, t):
        v, tr, r = self.vocab, self.traffic, self.r
        n = int(tr["events_per_tick"])
        ranks = v.sizes - 1 - r.integers(
            0, np.minimum(int(tr["hot_ids"]), v.sizes), (n, v.fields))
        ids = np.stack([v.ids(f, ranks[:, f]) for f in range(v.fields)], 1)
        y = (r.random(n) < float(tr["ctr"])).astype(np.float32)
        pos = np.flatnonzero(y)
        vids = np.arange(self.view, self.view + n, dtype=np.int64)
        self.view += n
        ev = {"view_ids": vids, "feature_ids": ids, "labels": y,
              "fb_view_ids": vids[pos],
              "fb_delay": r.exponential(float(tr["feedback_delay_s"]),
                                        len(pos))}
        return ev, self._batch_cls(
            t=t, view_ids=vids, feature_ids=ids, labels=y,
            fb_view_ids=vids[pos], fb_t=t + ev["fb_delay"])


def train_stream(cfg, traffic, seed):
    return Stream(cfg, traffic, seed)


def judge(spec, seed, st):
    out = base.judge(spec, seed, st)
    ids = np.concatenate([np.ravel(a["ids"])
                          for a in st.batches[st.stream_from:]])
    hot = hot_set(spec["cfg"], spec["traffic"])
    out["cold_ids"] = int((~np.isin(ids, hot)).sum())
    return out
'''

RUN = '''
import json
from harness import bench
spec = bench.load_spec("fm_hot.train_hot")
r = bench.run(spec, 2 ** 33 + 29, 1.5, False, allow_cpu=True,
              log=lambda *a, **k: None)
print(json.dumps(r, default=bench._jsonable))
'''


def digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def write_json(path, obj):
    path.write_text(json.dumps(obj, indent=1))


def test_a_family_added_as_new_files_runs_and_is_judged(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    pb = tmp_path / "perfbench"
    shutil.copytree(ROOT / "perfbench", pb,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digests(pb)

    (pb / "families" / "ctr_hot.py").write_text(FAMILY)
    cfg = bench.load_json(pb / "configs" / "fm_ftrl_criteo.json")
    vocab = [64, 3, 500, 2000, 37, 900]
    cfg.update(name="fm_hot", family="ctr_hot", field_vocab=vocab)
    cfg["sizing"]["ids_per_master"] = sum(vocab) // 4
    write_json(pb / "configs" / "fm_hot.json", cfg)
    write_json(pb / "traffic" / "hot_stream.json", {
        "kind": "train_stream", "events_per_tick": 256, "tick_s": 1.0,
        "zipf_a": 1.2, "ctr": 0.25, "feedback_delay_s": 1.0,
        "warm_ticks": 2, "hot_ids": 16})
    limits = bench.load_json(pb / "cells" / "fm_ftrl.train_stream.json")
    limits["limits"]["cold_ids"] = 0
    write_json(pb / "cells" / "fm_hot.train_hot.json", limits)
    new = bench.load_json(tmp_path / "BENCHMARK.json")
    new["configs"].append({
        "name": "fm_hot", "source": "https://arxiv.org/abs/2011.11983",
        "file": "perfbench/configs/fm_hot.json", "reduced": ["field_vocab"],
        "why": "a test family"})
    new["workloads"].append({
        "name": "fm_hot.train_hot", "config": "fm_hot",
        "traffic": "hot_stream", "chips": 1, "why": "a test cell"})
    for m in new["end_to_end"]:
        if "fm_ftrl.train_stream" in m.get("workloads", ()):
            m["workloads"].append("fm_hot.train_hot")
    write_json(tmp_path / "BENCHMARK.json", new)

    p = subprocess.run(
        [sys.executable, "-c", RUN], cwd=pb, capture_output=True, text=True,
        timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "PYTHONPATH": str(ROOT / "src")})
    assert p.returncode == 0, p.stderr[-3000:]
    r = json.loads(p.stdout.strip().splitlines()[-1])
    assert r["correct"], r["checks"]
    assert r["checks"]["cold_ids"] == {"value": 0.0, "limit": 0}
    assert {"rows_err", "join_wrong"} <= set(r["checks"])
    assert set(r["metrics"]) == {"train_examples_per_s", "staleness_p95_ms",
                                 "setup_s"}

    after = digests(pb)
    assert {k: after[k] for k in before} == before
