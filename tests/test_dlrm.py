"""DLRM-DCNv2 against its plain reference, at a small size on the CPU.

The reference is the benchmark's (``perfbench/families/dlrm_dcnv2_ref.py``:
``jax.numpy``, float32, no kernels, routing or batching). Sizes: 4 fields
with multi-hot 3, 1, 2, 5 (11 slots), rows 8 wide, 3 dense features, a
16-8 bottom MLP, 2 cross layers of rank 4, a 16-1 top MLP, seeded random
weights. Tolerances are float32 ones: both sides compute in float32, in
different orders (the program fuses the pooling and reduces by sorted
segment sum; the reference sums slices and reduces its own way), so they
agree to a few units in the last place of the largest terms, and the
limits below leave room for that and no more: the control, the reference
with bfloat16 matmuls, reads about a hundred times over them.
"""

import dataclasses
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.weips_ctr import DLRM_DCNV2
from repro.core import ClusterConfig, WeiPSCluster
from repro.data.joiner import SampleJoiner
from repro.kernels import ops
from repro.models import ctr
from repro.serving.router import RowRouter

BENCH = Path(__file__).resolve().parents[1] / "perfbench"
SIZES = (3, 1, 2, 5)
SMALL = dataclasses.replace(
    DLRM_DCNV2, fields=4, embed_dim=8, multi_hot=SIZES, dense_features=3,
    bottom_mlp=(16, 8), top_mlp=(16, 1), dcn_layers=2, dcn_rank=4,
    feature_space=1000)


def _load(name, path):
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


@pytest.fixture(scope="module")
def bench():
    """The benchmark's harness on the path, and the family's modules."""
    for p in (BENCH, BENCH / "tests"):
        if str(p) not in sys.path:
            sys.path.insert(0, str(p))
    fam = _load("perfbench_family_dlrm_dcnv2",
                BENCH / "families" / "dlrm_dcnv2.py")
    return fam, fam.ref


def _cfg_dict(cfg=SMALL) -> dict:
    """The reference's view of a model configuration."""
    return {"multi_hot": list(cfg.multi_hot), "embed_dim": cfg.embed_dim,
            "dense_features": cfg.dense_features,
            "bottom_mlp": list(cfg.bottom_mlp), "top_mlp": list(cfg.top_mlp),
            "dcn_layers": cfg.dcn_layers, "dcn_rank": cfg.dcn_rank}


def _batch(r, b=24, u=40):
    """A batch over ``u`` unique rows: its ids' inverse, labels, weights
    (a few 0, as padding carries) and dense features."""
    inv = r.integers(0, u, (b, sum(SIZES))).astype(np.int32)
    inv[0] = np.arange(sum(SIZES))           # every unique row read
    y = (r.random(b) < 0.3).astype(np.float32)
    w = np.ones(b, np.float32)
    w[-3:] = 0.0
    x = np.log1p(np.floor(r.exponential(5.0, (b, 3)))).astype(np.float32)
    return inv, y, w, x


def test_tower_has_the_published_parameter_count():
    shapes = ctr.dense_shapes(DLRM_DCNV2)
    count = {p: sum(int(np.prod(s)) for n, s in shapes.items()
                    if n.startswith(p)) for p in ("bottom", "cross", "top")}
    assert count == {"bottom": 171_392, "cross": 3 * 3_542_400,
                     "top": 5_245_953}
    assert DLRM_DCNV2.id_slots == 214


def test_cross_layer_is_its_equation():
    r = np.random.default_rng(3)
    x0, xl = r.standard_normal((2, 5, 12))
    v, u = r.standard_normal((12, 4)), r.standard_normal((4, 12))
    b = r.standard_normal(12)
    want = x0 * (xl @ v @ u + b) + xl                     # float64
    got = np.asarray(ctr.cross_layer(*(jnp.asarray(a, jnp.float32)
                                       for a in (x0, xl, v, u, b))))
    # float32 products of 12-term sums of unit normals: 1e-5 relative
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_logits_and_gradients_match_the_reference(bench):
    """Pooled lookup, tower and the transpose to unique rows against the
    reference's forward, loss and gradients by gathered rows (summed into
    unique rows) and by the tower."""
    _, ref = bench
    r = np.random.default_rng(5)
    cfg = _cfg_dict()
    tower = ref.initial_tower(cfg, 11)
    for k in tower:                          # biases off zero too
        if k.split("/")[1].startswith("b"):
            tower[k] = r.standard_normal(tower[k].shape).astype(np.float32)
    u = 40
    urows = r.standard_normal((u, 8)).astype(np.float32)
    inv, y, w, x = _batch(r, u=u)
    pool = ops.PooledLookup(SIZES, 8, 24 * sum(SIZES))
    pooled = pool.lookup(urows, inv)
    dense = {k: jnp.asarray(v) for k, v in tower.items()}
    p = np.asarray(ctr._dlrm_predict(pooled, dense, jnp.asarray(x)))
    loss, g_pooled, g_tower = ctr._dlrm_loss_grads(
        pooled, dense, jnp.asarray(x), jnp.asarray(y), jnp.asarray(w))
    _, inverse, order = RowRouter.unique_order(inv)
    g_rows = pool.grad(g_pooled, order, inverse[order], u)

    with jax.default_matmul_precision("highest"):
        gathered = jnp.asarray(urows)[inv]
        want_p = jax.nn.sigmoid(ref.logits(
            ref.pool(gathered, SIZES, "sum"), dense, jnp.asarray(x),
            "highest"))
        want_loss = ref.loss(gathered, dense, x, y, w, SIZES, "highest",
                             "sum")
        order = np.argsort(inv.reshape(-1), kind="stable").astype(np.int32)
        g_gathered, want_tower = ref.ref_grads(
            ref.ref_gather(urows, inv), dense, x, y, w, sizes=SIZES,
            mode="highest", how="sum")
        want_rows = ref.ref_segment_sum(g_gathered, order,
                                        inv.reshape(-1)[order], rows=u)
    # float32 through ~4 layers of 16-wide sums: 1e-5 relative
    np.testing.assert_allclose(p, np.asarray(want_p), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    np.testing.assert_allclose(g_rows, np.asarray(want_rows), rtol=1e-4,
                               atol=1e-6)
    for k, g in g_tower.items():
        np.testing.assert_allclose(np.asarray(g), np.asarray(want_tower[k]),
                                   rtol=1e-4, atol=1e-6, err_msg=k)
    # the control, bfloat16 matmuls, is far outside these tolerances
    with jax.default_matmul_precision("highest"):
        _, bf = ref.ref_grads(ref.ref_gather(urows, inv), dense, x, y, w,
                              sizes=SIZES, mode="bfloat16", how="sum")
    worst = max(float(np.abs(np.asarray(bf[k]) - np.asarray(g)).max()
                      / np.abs(np.asarray(g)).max())
                for k, g in g_tower.items())
    assert worst > 1e-3


def test_device_segment_sum_is_host_add_at():
    """The transpose on the device against ``np.add.at`` over the expanded
    slots: each slot takes its field's pooled gradient."""
    r = np.random.default_rng(7)
    inv, *_ = _batch(r, b=30, u=50)
    pool = ops.PooledLookup(SIZES, 8, 30 * sum(SIZES))
    pool.lookup(np.zeros((50, 8), np.float32), inv)
    g = r.standard_normal((30, len(SIZES), 8)).astype(np.float32)
    _, inverse, order = RowRouter.unique_order(inv)
    got = pool.grad(jnp.asarray(g), order, inverse[order], 50)
    field = np.repeat(np.arange(len(SIZES)), SIZES)
    want = np.zeros((50, 8), np.float32)
    np.add.at(want, inv.reshape(-1), g[:, field].reshape(-1, 8))
    # sums of at most a few dozen float32 terms, in another order
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_unique_order_is_np_unique():
    ids = np.random.default_rng(1).integers(-5, 60, (17, 11))
    uniq, inverse, order = RowRouter.unique_order(ids)
    u2, i2 = np.unique(ids, return_inverse=True)
    np.testing.assert_array_equal(uniq, u2)
    np.testing.assert_array_equal(inverse, i2.reshape(-1))
    assert (np.diff(inverse[order]) >= 0).all()


@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_train_batch_matches_the_reference(bench, backend):
    """Several ``train_batch`` calls through the cluster (pre-seeded rows
    and tower, int8 sync) against the reference's FTRL rows, Adagrad
    tower and int8 replicas."""
    fam, ref = bench
    import tiny
    spec = tiny.spec("dlrm_dcnv2.train_stream")
    cfg = spec["cfg"]
    cfg["cluster"].update(ps_backend=backend, codec_backend=backend)
    seed = 2 ** 33 + 5
    cl = fam.build(cfg, seed)
    fam.preseed(cl, cfg, seed, masters=True, replicas=True)
    stream = fam.Stream(cfg, spec["traffic"], seed)
    r = np.random.default_rng(9)
    scn = cl.training.scenario()
    calls = []
    for step in range(4):
        b = 40 + 7 * step
        ids = stream.slots.sample(r, b, 1.2)
        y = (r.random(b) < 0.25).astype(np.float32)
        w = np.ones(b, np.float32)
        x = fam.dense_features(r, b, cfg["dense_features"])
        calls.append({"ids": ids, "y": y, "weights": w, "dense_x": x,
                      "bucket": 64})
        cl.training.train_batch(scn, ids, y, weights=w, dense_x=x,
                                bucket=64)
        cl.sync_tick(float(step))
    out = fam.collect(cl, cfg, calls)
    want = ref.Reference(cfg, seed)
    want.replay(ref.calls_of(calls))
    rc = ref.calls_of(calls)
    got = {**fam.state_numbers(out, want,
                               *fam.start_state(cfg, seed, out["ids"]),
                               fam.first_only(rc)),
           **fam.replica_numbers(out, cfg["ftrl"])}
    assert len(fam.first_only(rc)) > 20
    # float32 in another order: every row that took one step within 1 %
    # of the reference's change, the rows' and the tower's changes to
    # 1e-4 of their norms (the bfloat16 control misses every one-step
    # row and reads 5e-3 on the changes: perfbench/tests); every replica
    # element within half an int8 step of its master's
    assert got["first_rows_miss_pct"] == 0.0, got
    assert got["rows_change_err"] < 1e-4, got
    assert got["dense_change_err"] < 1e-4, got
    assert got["replica_miss_pct"] == 0.0, got
    assert got["replica_dense_miss_pct"] == 0.0, got


def test_dense_features_ride_the_join_unchanged():
    j = SampleJoiner(window=2.0, emit_on_feedback=True)
    r = np.random.default_rng(2)
    vids = np.arange(10, dtype=np.int64)
    feats = r.integers(0, 100, (10, 11))
    dense = r.standard_normal((10, 3)).astype(np.float32)
    j.offer_exposures(0.0, vids, feats, dense)
    fast = j.offer_feedbacks(np.full(3, 0.5), vids[[2, 5, 7]])
    rest = j.drain_batch(3.0)
    got = {int(v): (f, d) for b in (fast, rest)
           for v, f, d in zip(b.view_ids, b.feature_ids, b.dense)}
    assert sorted(got) == list(range(10))
    for v, (f, d) in got.items():
        np.testing.assert_array_equal(f, feats[v])
        np.testing.assert_array_equal(d, dense[v])
    with pytest.raises(ValueError):
        j.offer_exposures(4.0, vids + 10, feats)          # dense dropped


def test_pipeline_trains_with_the_events_dense_features():
    """Events with dense features through ``TrainPipeline`` reach
    ``train_batch`` as ``dense_x``, row for row with their ids."""
    cl = WeiPSCluster(SMALL, ClusterConfig(train_buckets=(16,),
                                           join_window=1.0))
    seen = []
    orig = cl.training.train_batch

    def spy(scn, ids, y, **kw):
        seen.append((ids.copy(), kw["dense_x"].copy()))
        return orig(scn, ids, y, **kw)

    cl.training.train_batch = spy
    pipe = cl.make_train_pipeline()
    from repro.data.streams import EventBatch
    r = np.random.default_rng(4)
    ids = r.integers(0, 1000, (32, 11))
    dense = r.standard_normal((32, 3)).astype(np.float32)
    pipe.ingest(EventBatch(t=0.0, view_ids=np.arange(32), feature_ids=ids,
                           labels=np.zeros(32, np.float32),
                           fb_view_ids=np.empty(0, np.int64),
                           fb_t=np.empty(0), dense=dense))
    cl.train_scheduler.tick(2.0)
    assert sum(len(i) for i, _ in seen) == 32
    by_row = {tuple(i): d for i, d in zip(ids, dense)}
    for i, d in seen:
        for row, dd in zip(i, d):
            np.testing.assert_array_equal(dd, by_row[tuple(row)])


def test_serving_refuses_a_model_that_needs_dense_features():
    cl = WeiPSCluster(SMALL, ClusterConfig())
    assert len(cl.serving.registry) == 0          # no default serve scenario
    with pytest.raises(ValueError, match="dense features"):
        cl.add_scenario(SMALL)


def test_train_batch_refuses_missing_dense_features():
    cl = WeiPSCluster(SMALL, ClusterConfig())
    ids = np.zeros((4, 11), np.int64)
    with pytest.raises(ValueError, match="dense_x"):
        cl.training.train_batch(cl.training.scenario(), ids,
                                np.zeros(4, np.float32))
