"""The comparison has to fail what it guards against. At a tiny size on
the CPU: the control (the reference in bfloat16 in the program's place)
reads over a limit, and a whole run with the timed path broken
underneath comes out ``correct: false``, once for each fault a cell can
have on one chip: a step that returns its state unchanged, half of each
batch left out, and an update, a joined label or an answer altered where
it is produced. (No cell spans chips, so none can leave out an exchange
between them.)"""

import numpy as np
import pytest

import tiny
from harness import bench, check


def _limits(workload):
    return tiny.load(workload)["cell"]["limits"]


def _over(numbers, limits):
    return [k for k, v in numbers.items() if v > limits[k]]


@pytest.mark.parametrize("workload", ["fm_ftrl.train_stream",
                                      "lr_ftrl.train_stream"])
def test_train_control_and_faults_read_over_a_limit(workload):
    spec = tiny.spec(workload)
    seed = 2 ** 32 + 3
    st = bench.execute(spec, seed, 1.5, False, allow_cpu=True,
                       log=lambda *a, **k: None)
    r = spec["family"].readings(spec, seed, st)
    lim = _limits(workload)
    assert not _over(r["program"], lim), r
    for kind in ("control", "unchanged", "half_batch"):
        assert _over(r[kind], lim), (kind, r[kind])


def test_serve_control_reads_over_the_limit():
    spec = tiny.spec("fm_ftrl.serve_zipf")
    seed = 2 ** 32 + 5
    st = bench.execute(spec, seed, 1.5, False, allow_cpu=True,
                       log=lambda *a, **k: None)
    r = spec["family"].readings(spec, seed, st)
    lim = _limits("fm_ftrl.serve_zipf")
    assert not _over(r["program"], lim), r
    assert _over(r["control"], lim), r


def test_state_left_unchanged_is_not_correct(monkeypatch):
    from repro.core.ps import MasterShard

    def frozen(self, group, ids, grads, *, step=None):
        uniq = np.unique(np.asarray(ids, np.int64))
        self.step = (self.step if step is None else step) + 1
        if self.collector is not None:
            self.collector.record(group, uniq, "upsert")
        return uniq

    monkeypatch.setattr(MasterShard, "apply_batch", frozen)
    assert not tiny.run("fm_ftrl.train_stream")["correct"]


def test_half_batch_left_out_is_not_correct(monkeypatch):
    from repro.training.plane import TrainingPlane
    orig = TrainingPlane.train_batch

    def half(self, scn, ids, y, *, now=0.0, weights=None, bucket=None):
        k = max(1, len(ids) // 2)
        return orig(self, scn, ids[:k], y[:k], now=now,
                    weights=None if weights is None else weights[:k],
                    bucket=bucket)

    monkeypatch.setattr(TrainingPlane, "train_batch", half)
    assert not tiny.run("fm_ftrl.train_stream")["correct"]


def test_update_altered_where_produced_is_not_correct(monkeypatch):
    from repro.kernels import ops
    orig = ops.fused_ftrl_apply

    def altered(*a, **k):
        out = list(orig(*a, **k))
        out[3] = out[3].copy()
        out[3][0] += 1.0               # z' of one row, as the host gets it
        return tuple(out)

    monkeypatch.setattr(ops, "fused_ftrl_apply", altered)
    assert not tiny.run("fm_ftrl.train_stream")["correct"]


def test_label_altered_where_joined_is_not_correct(monkeypatch):
    from repro.data.joiner import SampleJoiner
    orig = SampleJoiner.drain_batch

    def altered(self, now):
        out = orig(self, now)
        if len(out):
            out.labels[0] = 1.0 - out.labels[0]
        return out

    monkeypatch.setattr(SampleJoiner, "drain_batch", altered)
    r = tiny.run("fm_ftrl.train_stream")
    assert not r["correct"]
    assert r["checks"]["join_wrong"]["value"] > 0


def test_answer_altered_where_produced_is_not_correct(monkeypatch):
    from repro.serving.scheduler import PredictScheduler
    orig = PredictScheduler._run

    def altered(self, ids):
        out = orig(self, ids).copy()
        out[-1] += 0.01
        return out

    monkeypatch.setattr(PredictScheduler, "_run", altered)
    spec = tiny.spec("fm_ftrl.serve_zipf")
    seed = 2 ** 33 + 17
    st = tiny.execute("fm_ftrl.serve_zipf", seed=seed)
    ok, _ = check.verdict(bench.judge(spec, seed, st),
                          spec["cell"]["limits"])
    assert not ok
