"""Family ``dlrm_dcnv2`` at its tiny cut on the CPU: a run is judged
correct; the control and every planted fault read over a limit; the
counts behind ``step_mfu.dlrm``, ``tower_roofline.dlrm`` and
``pool_roofline.dlrm`` against hand counts; the shared FTRL and probe
readers read the configuration's keys; each new trace reader's pattern
matches the name of the program it times, and reads nothing in a trace
without it."""

import json
from types import SimpleNamespace as NS

import jax.numpy as jnp
import pytest

import tiny
from harness import bench, counts, peaks, spans

CELL = "dlrm_dcnv2.train_stream"
PK = peaks.for_kind("TPU v5 lite")


def _counts():
    return bench.reader("step_mfu.dlrm").__globals__["dlrm_counts"]


def _over(numbers, limits):
    return [k for k, v in numbers.items() if v > limits[k]]


def test_tiny_run_is_judged_correct():
    r = tiny.run(CELL, trace=True)
    assert r["correct"], r["checks"]
    assert set(r["checks"]) == set(tiny.load(CELL)["cell"]["limits"])
    m = r["metrics"]
    assert m["compiles_in_window.train"]["value"] == 0
    # no peaks on the CPU: the shares read nothing there
    for name in ("step_mfu.dlrm", "tower_roofline.dlrm",
                 "pool_roofline.dlrm"):
        assert name not in m


# a planted fault that has a number of its own fails that one
OWN = {"control": "first_rows_miss_pct",
       "default_precision": "first_rows_miss_pct",
       "replica_rows_unchanged": "replica_miss_pct",
       "replica_tower_unchanged": "replica_dense_miss_pct"}


# 15 and 17 are seeds on which the control once passed at this cut
@pytest.mark.parametrize("seed", [2 ** 32 + 7, 15, 17, 26])
def test_control_and_faults_read_over_a_limit(seed):
    spec = tiny.spec(CELL)
    st = bench.execute(spec, seed, 1.5, False, allow_cpu=True,
                       log=lambda *a, **k: None)
    r = spec["family"].readings(spec, seed, st)
    lim = spec["cell"]["limits"]
    assert not _over(r["program"], lim), r["program"]
    kinds = set(r) - {"program"}
    assert kinds == {"control", "default_precision", "rows_unchanged",
                     "half_batch", "tower_frozen", "mean_pool",
                     "replica_rows_unchanged", "replica_tower_unchanged"}
    for kind in kinds:
        over = _over(r[kind], lim)
        assert over, (kind, r[kind])
        assert OWN.get(kind, over[0]) in over, (kind, r[kind])


CFG = json.loads((bench.HERE / "configs" /
                  "dlrm_dcnv2_criteo.json").read_text())


def test_counts_match_hand_counts():
    c = _counts()
    small = {"multi_hot": [3, 1], "embed_dim": 2, "dense_features": 3,
             "bottom_mlp": [4, 2], "top_mlp": [5, 1], "dcn_layers": 1,
             "dcn_rank": 2}
    # d = (2 fields + 1) * 2 = 6; matmuls 3x4, 4x2, 6x2, 2x6, 6x5, 5x1
    macs = 12 + 8 + 12 + 12 + 30 + 5
    # bias + ReLU on 4, 2 and 5 outputs, bias on the logit; cross 3 x 6
    elem = 2 * (4 + 2 + 5) + 1 + 18
    assert c.tower_forward(small) == 2 * macs + elem
    assert c.tower_backward(small) == 4 * macs - 2 * 12 + 2 * elem
    assert c.tower_step(small) == 2 * (2 * macs + elem) + \
        4 * macs - 24 + 2 * elem
    assert c.weights(small) == macs + 4 + 2 + 5 + 1 + 6
    # 2 calls, 10 examples: weights read 3 times and written once; the
    # pooled rows (2 fields x 2 x 4 B) read twice and written once
    assert c.tower_bytes(small, 2, 10) == 2 * 4 * c.weights(small) * 4 + \
        10 * 3 * 16
    # 10 examples x 4 slots: rows 8 B and index 4 B read, 2 pooled rows
    # of 8 B out; back: pooled 160 in, index 4 and row read-add-write
    # 16 a slot, 7 unique rows of 8 B out
    assert c.pool(small, 10, 7) == (40 * 12 + 160) + (160 + 40 * 20 + 56)
    # the published model: 16,044,545 parameters, 32 MFLOP forward
    assert c.weights(CFG) == CFG["parameters"]["tower"] == 16_044_545
    assert c.tower_forward(CFG) == \
        CFG["parameters"]["flops_forward_per_example"]
    assert c.tower_step(CFG) == \
        CFG["parameters"]["flops_train_step_per_example"]


def _ctx(trace, **kw):
    base = dict(trace=trace, peaks=PK, counts=counts, cfg=CFG, train=True,
                window_s=10.0, unique_per_batch=[{"emb": 60000}] * 20,
                stats={"examples": 40960, "ticks": 20})
    base.update(kw)
    return NS(**base)


def test_shared_readers_read_the_dlrm_keys():
    """``ftrl_roofline.train`` and ``probe_roofline.train`` count the one
    128-wide group at the configuration's ids per master."""
    load = counts.map_load(CFG["sizing"]["ids_per_master"])
    assert load == pytest.approx(CFG["sizing"]["ids_per_master"] / 2 ** 22)
    tr = NS(module_ns=lambda p: 2e9, op_ns=lambda p: 1e9)
    ctx = _ctx(tr)
    o, b = counts.ftrl(60000, 128, load)
    want = 100 * counts.least_time(20 * o, 20 * b, PK)[0] / 2.0
    assert bench.reader("ftrl_roofline.train")(ctx) == pytest.approx(want)
    pb = counts.probe(60000, load)[1]
    want = 100 * counts.least_time(0.0, 20 * pb, PK)[0] / 1.0
    assert bench.reader("probe_roofline.train")(ctx) == pytest.approx(want)


def test_dlrm_readers_on_a_stand_in():
    c = _counts()
    times = {"jit__dlrm_predict": 0.3e9, "jit__dlrm_loss_grads": 0.9e9,
             "jit__pooled_lookup": 0.1e9, "jit__pooled_grad": 0.2e9,
             "jit__ftrl_program": 5e9}

    def module_ns(pattern):
        import re
        return sum(v for k, v in times.items() if re.search(pattern, k))

    ctx = _ctx(NS(module_ns=module_ns))
    ops = 40960 * c.tower_step(CFG)
    assert bench.reader("step_mfu.dlrm")(ctx) == pytest.approx(
        100 * ops / PK["flops_bf16"] / 10.0)
    t = counts.least_time(ops, c.tower_bytes(CFG, 20, 40960), PK)[0]
    assert bench.reader("tower_roofline.dlrm")(ctx) == pytest.approx(
        100 * t / 1.2)
    t = counts.least_time(0.0, c.pool(CFG, 40960, 20 * 60000), PK)[0]
    assert bench.reader("pool_roofline.dlrm")(ctx) == pytest.approx(
        100 * t / 0.3)
    # a trace without the programs (the parent's), no trace, a
    # configuration without the tower: nothing read
    none = _ctx(NS(module_ns=lambda p: 0.0))
    for name in ("tower_roofline.dlrm", "pool_roofline.dlrm"):
        assert bench.reader(name)(none) is None
        assert bench.reader(name)(_ctx(None)) is None
    fm = json.loads((bench.HERE / "configs" /
                     "fm_ftrl_criteo.json").read_text())
    for name in ("step_mfu.dlrm", "tower_roofline.dlrm",
                 "pool_roofline.dlrm"):
        assert bench.reader(name)(_ctx(NS(module_ns=module_ns),
                                       cfg=fm)) is None


@pytest.mark.parametrize("metric,name", [("pool_ms.dlrm", "train.pool"),
                                         ("dense_update_ms.dlrm",
                                          "train.dense_update")])
def test_staged_span_readers(metric, name):
    p = spans.ProgramSpans(self_ns={name: 8e6, "train.batch": 1e6})
    ctx = NS(trace=NS(program_spans=p), stats={"ticks": 4})
    assert bench.reader(metric)(ctx) == pytest.approx(2.0)
    ctx = NS(trace=NS(program_spans=spans.ProgramSpans(
        self_ns={"train.batch": 1.0})), stats={"ticks": 4})
    assert bench.reader(metric)(ctx) is None


def test_reader_patterns_match_the_program_names():
    """The jitted programs are named as the readers' patterns say."""
    import re

    from repro.kernels import ops
    from repro.models import ctr
    sizes = (2, 1)
    rows = jnp.zeros((16, 8))
    inv = jnp.zeros((4, 3), jnp.int32)
    names = [ops._pooled_lookup.lower(rows, inv, sizes=sizes),
             ops._pooled_grad.lower(jnp.zeros((4, 2, 8)),
                                    jnp.zeros((12,), jnp.int32),
                                    jnp.zeros((12,), jnp.int32), rows=16)]
    dense = {"bottom/w0": jnp.zeros((3, 8)), "bottom/b0": jnp.zeros(8),
             "top/w0": jnp.zeros((24, 1)), "top/b0": jnp.zeros(1)}
    pooled, x = jnp.zeros((4, 2, 8)), jnp.zeros((4, 3))
    names += [ctr._dlrm_predict.lower(pooled, dense, x),
              ctr._dlrm_loss_grads.lower(pooled, dense, x, jnp.zeros(4),
                                         jnp.zeros(4))]
    found = [re.search(r"module @(\S+)", lo.as_text()).group(1)
             for lo in names]
    pool = bench.reader("pool_roofline.dlrm").__globals__["PROGRAM"]
    tower = bench.reader("tower_roofline.dlrm").__globals__["PROGRAM"]
    assert [bool(re.search(pool, n)) for n in found] == \
        [True, True, False, False], found
    assert [bool(re.search(tower, n)) for n in found] == \
        [False, False, True, True], found
