"""Every cell's drive loop at a tiny size on the CPU (Pallas in interpret
mode): the train stream with its staleness stamps, the open-loop serve
path, the traced run's per-layer metrics, compile counting, and the
command's refusal of any platform but a TPU."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import tiny
from harness import bench, check, drive

ROOT = bench.ROOT


@pytest.fixture(scope="module")
def fm_train():
    return tiny.run("fm_ftrl.train_stream")


def test_train_stream(fm_train):
    r = fm_train
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert set(m) == {"train_examples_per_s", "staleness_p95_ms", "setup_s"}
    assert m["train_examples_per_s"]["value"] > 0
    # staleness is stamped at the train step and read at a later poll
    assert m["staleness_p95_ms"]["value"] > 0
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"rows_err", "replica_miss_pct",
                                "join_wrong", "join_owed"}
    # every row trained is one the stream's join owed; fewer than the
    # smallest train bucket (128) wait in the pipeline's buffer
    assert r["checks"]["join_wrong"]["value"] == 0
    assert r["checks"]["join_owed"]["value"] < 128
    assert r["device"]["platform"] == "cpu"


def test_lr_train_stream():
    r = tiny.run("lr_ftrl.train_stream")
    assert r["correct"], r["checks"]
    assert r["metrics"]["train_examples_per_s"]["value"] > 0


def test_open_loop_serve():
    # the staged serve cell: its drive loop and comparison
    spec = tiny.spec("fm_ftrl.serve_zipf")
    seed = 2 ** 33 + 17
    st = tiny.execute("fm_ftrl.serve_zipf", seed=seed)
    s = st.stats
    # the schedule's request count: rate x seconds
    assert s["attempted"] == 30 and s["failed"] == 0
    assert (s["latency_s"] > 0).all()
    # ids over whole vocabularies: the window's requests miss the cache
    assert 0 < s["cache"]["hit_rate"] < 1
    ok, shown = check.verdict(bench.judge(spec, seed, st),
                              spec["cell"]["limits"])
    assert ok, shown


def test_traced_runs_report_per_layer_metrics():
    r = tiny.run("fm_ftrl.train_stream", trace=True)
    m = r["metrics"]
    for k in ("ingest_ms.train", "train_tick_ms.train", "push_ms.train",
              "apply_ms.train", "compiles_in_window.train"):
        assert k in m, k
    assert m["compiles_in_window.train"]["value"] == 0
    assert "train_examples_per_s" not in m
    # no device plane on the CPU: nothing device-side is reported
    assert "idle_share.train" not in m and "step_mfu.train" not in m


def test_compile_counter():
    import jax
    x5, x6 = np.ones(5, np.float32), np.ones(6, np.float32)
    f = jax.jit(lambda x: x * 3 + 1)
    c = drive.CompileCounter()
    try:
        f(x5)
        assert c.count == 1
        f(x5)
        assert c.count == 1             # same shape: nothing new
        f(x6)
        assert c.count == 2
    finally:
        c.close()


def _cmd(cwd, env):
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload",
         "fm_ftrl.train_stream", "--seed", str(2 ** 33 + 1), "--seconds",
         "1", "--trace", "0"], cwd=cwd, env=env, capture_output=True,
        text=True, timeout=300)


def test_refuses_a_platform_but_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _cmd(ROOT, env)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "needs a TPU" in p.stderr and "cpu" in p.stderr


def test_refuses_in_a_directory_of_the_benchmark_alone(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "perfbench", tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _cmd(tmp_path, env)
    assert p.returncode != 0 and p.stdout == ""


def test_unknown_workload_is_refused():
    with pytest.raises(bench.Refused):
        bench.load_spec("no_such.cell")


def test_every_metric_has_a_reader():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert callable(bench.reader(m["name"])), m["name"]


def test_schedule_is_the_same_work_for_every_seed():
    from harness import generate as gen
    t = tiny.load("fm_ftrl.serve_zipf")["traffic"]
    a = gen.serve_schedule(t, 50.0, 10.0, 1)
    b = gen.serve_schedule(t, 50.0, 10.0, 2 ** 33 + 5)
    assert sorted(a[1]) == sorted(b[1]) and len(a[0]) == len(b[0]) == 500
    assert not np.array_equal(a[1], b[1])
    # log-uniform sizes over the whole range, not a ladder
    assert a[1].min() == 16 and a[1].max() == 1020
    assert len(np.unique(a[1])) > 300
