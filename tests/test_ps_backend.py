"""PS backend equivalence: the ``pallas`` row engine (interpret mode on
CPU, Mosaic on TPU) must match the ``numpy`` reference path through the
real PS layer — SlaveShard serve lookups via the ``hashmap_probe``
kernel and MasterShard FTRL pushes via the fused ``ftrl_row_update``
kernel. This is the acceptance gate that the shipped kernels are actually
exercised by the parameter server, not just by kernel unit tests."""

import numpy as np
import pytest

from repro.core.ps import MasterShard, SlaveShard, SparseTable
from repro.optim import get_optimizer

DIM = 128       # lane-width-aligned rows (TPU idiom; interpret mode on CPU)


def _rand_ids(rng, n, space=10_000):
    return rng.integers(0, space, size=n).astype(np.int64)


def test_sparse_table_gather_backends_match(rng):
    tables = {b: SparseTable(DIM, init_capacity=32, backend=b)
              for b in ("numpy", "pallas")}
    ids = _rand_ids(rng, 12, space=40)
    w = rng.normal(size=(len(ids), DIM)).astype(np.float32)
    for t in tables.values():
        t.scatter(ids, w)
    probe = np.concatenate([ids[:5], _rand_ids(rng, 5, space=40) + 100])
    got_np, _ = tables["numpy"].gather(probe)
    got_pl, _ = tables["pallas"].gather(probe)
    np.testing.assert_array_equal(got_np, got_pl)
    # missing ids (the +100 block) are zeros on both paths
    assert (got_np[5:] == 0).all()


def test_slave_lookup_pallas_matches_numpy(rng):
    groups = {"w": DIM}
    slaves = {b: SlaveShard(0, groups, backend=b)
              for b in ("numpy", "pallas")}
    ids = _rand_ids(rng, 16, space=60)
    vals = rng.normal(size=(len(ids), DIM)).astype(np.float32)
    for s in slaves.values():
        s.tables["w"].scatter(ids, vals)
    probe = np.concatenate([ids, _rand_ids(rng, 4, space=60) + 1000])
    np.testing.assert_array_equal(slaves["numpy"].lookup("w", probe),
                                  slaves["pallas"].lookup("w", probe))


@pytest.mark.parametrize("steps", [1, 4])
def test_master_ftrl_pallas_matches_numpy(rng, steps):
    """apply_batch: hash → gather → fused FTRL kernel → scatter, against
    the vectorized NumPy reference, over several steps (state carries)."""
    opt = get_optimizer("ftrl", alpha=0.1, beta=1.0, l1=0.5, l2=0.2)
    masters = {b: MasterShard(0, {"w": DIM}, opt, backend=b)
               for b in ("numpy", "pallas")}
    for step in range(steps):
        ids = _rand_ids(rng, 8, space=20)
        grads = np.random.default_rng(step).normal(
            size=(len(ids), DIM)).astype(np.float32)
        for m in masters.values():
            m.apply_batch("w", ids, grads, step=step)
    ids_all = masters["numpy"].tables["w"].all_ids()
    w_np, s_np = masters["numpy"].tables["w"].gather(np.sort(ids_all))
    w_pl, s_pl = masters["pallas"].tables["w"].gather(np.sort(ids_all))
    np.testing.assert_allclose(w_np, w_pl, rtol=1e-5, atol=1e-6)
    for k in ("z", "n"):
        np.testing.assert_allclose(s_np[k], s_pl[k], rtol=1e-5, atol=1e-6)


def test_apply_batch_dedups_and_sums_duplicate_ids():
    """Duplicate ids in one minibatch act as summed gradients on one row
    (sparse-grad semantics), and each unique row updates exactly once."""
    opt = get_optimizer("ftrl")
    m_dup = MasterShard(0, {"w": 4}, opt)
    m_sum = MasterShard(0, {"w": 4}, opt)
    ids = np.array([7, 7, 9], np.int64)
    g = np.array([[1.0] * 4, [2.0] * 4, [5.0] * 4], np.float32)
    m_dup.apply_batch("w", ids, g, step=0)
    m_sum.apply_batch("w", np.array([7, 9], np.int64),
                      np.array([[3.0] * 4, [5.0] * 4], np.float32), step=0)
    for m in (m_dup, m_sum):
        assert m.tables["w"].touch_count[
            m.tables["w"].lookup(np.array([7]))[0]] == 1
    w_dup, s_dup = m_dup.tables["w"].gather(np.array([7, 9]))
    w_sum, s_sum = m_sum.tables["w"].gather(np.array([7, 9]))
    np.testing.assert_allclose(w_dup, w_sum, rtol=1e-6)
    np.testing.assert_allclose(s_dup["z"], s_sum["z"], rtol=1e-6)


def test_apply_batch_unsorted_unique_ids():
    """Regression: slots resolve in sorted-unique order, so grad rows must
    be permuted to match even when ids are unique but unsorted."""
    opt = get_optimizer("sgd", lr=1.0)
    m = MasterShard(0, {"w": 2}, opt)
    m.apply_batch("w", np.array([5, 2], np.int64),
                  np.array([[1.0, 1.0], [10.0, 10.0]], np.float32), step=0)
    w, _ = m.tables["w"].gather(np.array([5, 2], np.int64))
    np.testing.assert_allclose(w, [[-1.0, -1.0], [-10.0, -10.0]])


def test_update_rows_matches_update_for_all_optimizers(rng):
    """The batched row path must agree with the elementwise ``update``
    contract every other PS consumer (dense bank, transform) relies on."""
    import jax.numpy as jnp
    for name in ("sgd", "adagrad", "adam", "momentum", "ftrl"):
        opt = get_optimizer(name)
        w = rng.normal(size=(6, 8)).astype(np.float32)
        slots = {k: np.asarray(v) for k, v in
                 opt.init_slots(jnp.asarray(w)).items()}
        g = rng.normal(size=(6, 8)).astype(np.float32)
        new_w, new_s = opt.update_rows(w, slots, g, 3)
        ref_w, ref_s = opt.update(jnp.asarray(w),
                                  {k: jnp.asarray(v)
                                   for k, v in slots.items()},
                                  jnp.asarray(g), 3)
        np.testing.assert_allclose(new_w, np.asarray(ref_w), rtol=1e-5,
                                   atol=1e-6)
        for k in new_s:
            np.testing.assert_allclose(new_s[k], np.asarray(ref_s[k]),
                                       rtol=1e-5, atol=1e-6)


def test_cold_pull_end_to_end_pallas_matches_numpy(rng):
    """Acceptance gate for the fused serve path: a fully cold serve_rows
    through a ``pallas`` cluster (device-mirror probe + fused
    probe→gather lookups) is bit-equal to the ``numpy`` staged path —
    router, replica reads, cache fill and all — and stays bit-equal warm
    (cache hits) and after a second training sync."""
    import dataclasses

    from repro.configs.weips_ctr import FM_FTRL
    from repro.core.cluster import ClusterConfig, WeiPSCluster

    cfg = dataclasses.replace(FM_FTRL, fields=4)
    pool = np.unique(_rand_ids(rng, 96, space=1 << 40))
    req = pool[rng.integers(0, len(pool), size=(6, cfg.fields))]
    served = {}
    for backend in ("numpy", "pallas"):
        cl = WeiPSCluster(cfg, ClusterConfig(
            num_master=1, num_slave=2, num_replicas=1, num_partitions=2,
            ps_backend=backend))
        prng = np.random.default_rng(11)          # same rows per backend
        for mid, mids in cl.plan.split_by_master(pool).items():
            for g, dim in cl.groups.items():
                cl.masters[mid].apply_batch(
                    g, mids,
                    prng.normal(size=(len(mids), dim)).astype(np.float32))
        cl.sync_tick(0.0)
        cold = cl.serve_rows(req)                 # cache starts empty
        warm = cl.serve_rows(req)
        served[backend] = (cold, warm)
    for i in range(2):
        for g in served["numpy"][i]:
            np.testing.assert_array_equal(served["numpy"][i][g],
                                          served["pallas"][i][g])


def test_cluster_forced_hbm_placement_matches_numpy(rng):
    """Every table in a pallas cluster pinned to the HBM windowed-DMA
    probe (`device_placement="hbm"`) — training pushes, replica reads,
    cache fills and warm serves all run through the DMA kernel and stay
    bit-equal to the numpy cluster; the aggregated mirror metrics confirm
    the placement actually took."""
    import dataclasses

    from repro.configs.weips_ctr import FM_FTRL
    from repro.core.cluster import ClusterConfig, WeiPSCluster

    cfg = dataclasses.replace(FM_FTRL, fields=4)
    pool = np.unique(_rand_ids(rng, 96, space=1 << 40))
    req = pool[rng.integers(0, len(pool), size=(6, cfg.fields))]
    served = {}
    for backend in ("numpy", "pallas"):
        cl = WeiPSCluster(cfg, ClusterConfig(
            num_master=1, num_slave=2, num_replicas=1, num_partitions=2,
            ps_backend=backend))
        if backend == "pallas":
            for shard in (list(cl.masters)
                          + [r for rs in cl.replica_sets
                             for r in rs.replicas]):
                for t in shard.tables.values():
                    t.device_placement = "hbm"
            for scn in cl.serving.registry:
                scn.cache.table.device_placement = "hbm"
        prng = np.random.default_rng(23)
        for mid, mids in cl.plan.split_by_master(pool).items():
            for g, dim in cl.groups.items():
                cl.masters[mid].apply_batch(
                    g, mids,
                    prng.normal(size=(len(mids), dim)).astype(np.float32))
        cl.sync_tick(0.0)
        served[backend] = (cl.serve_rows(req), cl.serve_rows(req))
        if backend == "pallas":
            assert cl.serving.device_blocks > 0
            mm = cl.sync_metrics(0.0)["device_mirror"]
            assert mm["tables"] > 0 and mm["key_bytes_uploaded"] > 0
            scn = cl.serving.scenario()
            assert scn.cache.table._dev.placement == "hbm"
    for i in range(2):
        for g in served["numpy"][i]:
            np.testing.assert_array_equal(served["numpy"][i][g],
                                          served["pallas"][i][g])


def test_cold_pull_large_map_pallas_matches_numpy(rng):
    """End-to-end cold→warm serve through a >2M-slot serving map: the
    scenario cache arena is rebuilt at 2^22 slots, so auto placement
    flips to the HBM windowed-DMA probe for every warm cache hit — and
    the served rows stay bit-equal to the numpy backend throughout."""
    import dataclasses

    from repro.configs.weips_ctr import FM_FTRL
    from repro.core.cluster import ClusterConfig, WeiPSCluster
    from repro.core.ps import SparseTable
    from repro.kernels.hashmap_probe import VMEM_SLOT_BOUND

    cfg = dataclasses.replace(FM_FTRL, fields=4)
    pool = np.unique(_rand_ids(rng, 80, space=1 << 40))
    req = pool[rng.integers(0, len(pool), size=(5, cfg.fields))]
    served = {}
    for backend in ("numpy", "pallas"):
        cl = WeiPSCluster(cfg, ClusterConfig(
            num_master=1, num_slave=2, num_replicas=1, num_partitions=2,
            ps_backend=backend))
        scn = cl.serving.scenario()
        scn.cache.table = SparseTable(scn.cache.width, backend=backend,
                                      init_capacity=1 << 22)
        assert scn.cache.table._map.capacity > VMEM_SLOT_BOUND
        prng = np.random.default_rng(31)
        for mid, mids in cl.plan.split_by_master(pool).items():
            for g, dim in cl.groups.items():
                cl.masters[mid].apply_batch(
                    g, mids,
                    prng.normal(size=(len(mids), dim)).astype(np.float32))
        cl.sync_tick(0.0)
        served[backend] = (cl.serve_rows(req), cl.serve_rows(req))
        if backend == "pallas":
            assert scn.cache.table._dev.placement == "hbm"
            assert cl.serving.device_blocks > 0
    for i in range(2):
        for g in served["numpy"][i]:
            np.testing.assert_array_equal(served["numpy"][i][g],
                                          served["pallas"][i][g])


def test_mirror_incremental_key_sync_counters(rng):
    """The dirty-slot journal keeps mirror key syncs incremental: after
    the first full upload, inserting a few ids re-uploads only their
    slots (bytes counted per slot, not per table), visible per-table and
    aggregated through ``cluster.sync_metrics``."""
    from repro.core.ps import SparseTable

    st = SparseTable(4, ("n", "z"), backend="pallas",
                     init_capacity=1 << 12)
    ids = np.unique(_rand_ids(rng, 256, space=1 << 40))
    st.ensure(ids)
    st._gather_device(ids[:32])                  # first sync: full upload
    m0 = st.mirror_metrics()
    assert m0["key_full_uploads"] == 1
    assert m0["key_incremental_uploads"] == 0
    full_bytes = m0["key_bytes_uploaded"]
    assert full_bytes > 0
    fresh = np.unique(_rand_ids(rng, 8, space=1 << 40) + (1 << 41))
    st.ensure(fresh)
    st._gather_device(fresh)                     # second sync: journal path
    m1 = st.mirror_metrics()
    assert m1["key_full_uploads"] == 1           # no re-upload of the table
    assert m1["key_incremental_uploads"] == 1
    delta = m1["key_bytes_uploaded"] - full_bytes
    assert 0 < delta <= len(fresh) * 2 * 20      # per-slot, not per-table
    # evict → tombstones flow through the same journal
    st.evict(ids[:4])
    rows, found, _ = st.lookup_device(ids[:8])
    assert not found[:4].any() and found[4:].all()
    m2 = st.mirror_metrics()
    assert m2["key_full_uploads"] == 1
    assert m2["key_incremental_uploads"] == 2


def test_fused_ftrl_apply_counts_device_io(rng):
    """A tiny fused FTRL call adds its padded inputs to ``h2d_bytes``, its
    four row and mask outputs to ``d2h_bytes``, and one blocking read."""
    from repro.kernels import hashmap_probe as hm
    from repro.kernels import ops
    from repro.kernels.device_io import DEVICE_IO
    t = SparseTable(DIM, ("n", "z"), backend="pallas")
    ids = np.sort(_rand_ids(rng, 5, space=100))
    sl = t.ensure(ids)
    mir = t._mirror()
    mir.sync()
    grads = np.ones((5, DIM), np.float32)
    before = DEVICE_IO.metrics()
    out = ops.fused_ftrl_apply(
        mir.keys_lo, mir.keys_hi, mir.slot_of, mir.arenas["z"],
        mir.arenas["n"], mir.arenas["w"], ids, grads, shift=mir.shift,
        alpha=0.1, beta=1.0, l1=0.5, l2=0.2, placement=mir.placement)
    after = DEVICE_IO.metrics()
    nb = hm.OFFSET_BLOCK                # 5 ids pad to one probe granule
    assert after["h2d_bytes"] - before["h2d_bytes"] == \
        2 * nb * 4 + nb * DIM * 4       # id limbs + grads
    assert after["d2h_bytes"] - before["d2h_bytes"] == \
        3 * nb * DIM * 4 + nb           # z', n', w' + found mask
    assert after["waits"] - before["waits"] == 1
    assert out[-1].all() and len(sl) == 5
