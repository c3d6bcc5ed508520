"""Smoke run of the device-backed parameter server on one TPU chip.

Drives the main path once, in this one process, through the entry points
a user calls: a ``WeiPSCluster`` of the FM-FTRL model at its published
widths (32 fields, embed dim 8, 2^22-id feature space) with the ``pallas``
PS and codec backends, quickstart's layout (4 masters, 2 slave shards x 2
replicas, 8 partitions, int8 sync codec). It trains 30 ticks of 4096
click events (join -> pipeline -> fused FTRL on the device -> streaming
sync) and answers 8 predict requests of 2048 examples. The same seeded
run on the ``numpy`` backends is the reference: predictions and every
master's touched z, n, w rows must agree.

Master 0 is pre-seeded with ids the stream never draws, enough that its
hash maps outgrow the VMEM probe bound: its tables are probed with the
key table in HBM, the others with it in VMEM.

Before the cluster runs, the compiled probe alone is held bit-equal to
the host hash map on present, deleted and absent ids, at capacities from
below one probe window to past the VMEM bound, in every placement the
table fits.

Run: ``python chip_smoke.py`` on a machine with a TPU. It refuses any
other platform. The last line of its output is a JSON object naming the
device; the lines before it are the evidence.
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import numpy as np

PRED_ATOL = 1e-4            # predictions, pallas vs numpy
ROW_RTOL, ROW_ATOL = 1e-5, 1e-6   # master z, n, w rows


def probe_check(caps, *, seed: int = 1, log=print) -> None:
    """``ops.hashmap_probe`` against ``IdHashMap._probe`` on maps of each
    capacity in ``caps``, with the key table laid out as the device
    mirror uploads it: ``vmem`` placement where the table fits under
    ``VMEM_SLOT_BOUND``, ``hbm`` always. Raises AssertionError on any
    difference."""
    from repro.core.hashmap import IdHashMap
    from repro.kernels import hashmap_probe as hm
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    for cap in caps:
        m = IdHashMap(cap)
        ids = rng.choice(1 << 40, size=max(2, min(4096, cap // 8)),
                         replace=False).astype(np.int64)
        m.put(ids, np.arange(len(ids)))
        m.delete(ids[::5])
        assert m.capacity == cap, (m.capacity, cap)
        qs = np.concatenate([ids, ids + (1 << 40)])     # + absent ids
        host_pos, host_found = m._probe(qs)
        klo, khi = hm.wrap_pad_limbs(*ops.int64_limbs(m.key_table), cap=cap)
        qlo, qhi = ops.int64_limbs(qs)
        for placement in ("vmem", "hbm"):
            if placement == "vmem" and cap > hm.VMEM_SLOT_BOUND:
                continue
            pos, found = ops.hashmap_probe(klo, khi, qlo, qhi,
                                           shift=int(m.shift),
                                           placement=placement)
            pos, found = np.asarray(pos), np.asarray(found)
            np.testing.assert_array_equal(found, host_found)
            np.testing.assert_array_equal(pos[found], host_pos[host_found])
            log(f"probe cap={cap} placement={placement}: "
                f"{int(found.sum())} found of {len(qs)}, equal to host map")


def run(backend: str, *, ticks: int, events: int, requests: int,
        request_examples: int, seed: int = 0, log=print) -> dict:
    """One seeded run of the cluster on one PS/codec backend. Returns the
    predictions, every master's touched rows and the device counters."""
    import jax

    from repro.configs.weips_ctr import FM_FTRL
    from repro.core import ClusterConfig, WeiPSCluster
    from repro.data import ClickStream
    from repro.kernels.hashmap_probe import VMEM_SLOT_BOUND

    t0 = time.perf_counter()
    cl = WeiPSCluster(FM_FTRL, ClusterConfig(
        num_master=4, num_slave=2, num_replicas=2, num_partitions=8,
        codec="int8", codec_backend=backend, ps_backend=backend,
        join_window=3.0, seed=seed))
    # ids past the feature space owned by master 0: its maps grow past
    # the VMEM probe bound (an IdHashMap doubles at 25 % load)
    cand = FM_FTRL.feature_space + np.arange(8 * (VMEM_SLOT_BOUND // 4 + 1),
                                             dtype=np.int64)
    seed_ids = cand[cl.plan.master_shard(cand) == 0][:VMEM_SLOT_BOUND // 4
                                                      + 1]
    for g in cl.groups:
        cl.masters[0].pull(g, seed_ids, create=True)
    stream = ClickStream(feature_space=FM_FTRL.feature_space,
                         fields=FM_FTRL.fields, zipf_a=1.2,
                         signal_scale=0.8, feedback_delay=1.0, seed=seed)
    pipeline = cl.make_train_pipeline(emit_on_feedback=True)
    t1 = time.perf_counter()

    now = 0.0
    for _ in range(ticks):
        pipeline.ingest(stream.events_batch(events, now))
        cl.train_scheduler.tick(now)
        cl.sync_tick(now)
        now += 0.2
    cl.train_scheduler.flush(now + 4.0)
    cl.sync_tick(now + 4.0)
    t2 = time.perf_counter()

    preds = [cl.predict(stream.batch(request_examples)[0])
             for _ in range(requests)]
    t3 = time.perf_counter()

    masters = []
    for m in cl.masters:
        tables = {}
        for g, t in m.tables.items():
            ids = t.all_ids()
            sl = t.lookup(ids)
            touched = t.touch_count[sl] > 0
            w, slots = t.read_rows(sl[touched])
            tables[g] = {"ids": ids[touched], "w": w, **slots,
                         "mirror": t.mirror_metrics()}
        masters.append({"fused_batches": m.fused_batches, "tables": tables})
    sm = cl.sync_metrics(now)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    log(f"[{backend}] phases s: setup={t1 - t0:.3f} train={t2 - t1:.3f} "
        f"serve={t3 - t2:.3f}; peak_bytes_in_use={peak}; "
        f"trained={sm['training']['scenarios'][FM_FTRL.name]['examples']}")
    return {"preds": np.concatenate(preds), "masters": masters,
            "device_blocks": sm["serving"]["device_blocks"],
            "mirror_syncs": sm["device_mirror"]["syncs"]}


def smoke(*, ticks: int = 30, events: int = 4096, requests: int = 8,
          request_examples: int = 2048,
          probe_caps=(1 << 4, 1 << 12, 1 << 20, 1 << 22), seed: int = 0,
          log=print) -> dict:
    """Check the probe alone (``probe_check``; the default capacities
    bracket the v5e VMEM bound of 2^20 slots), then run the pallas
    backend and the numpy reference, check that the device path ran and
    that both agree; raise AssertionError where not. Returns the largest
    deviations."""
    t0 = time.perf_counter()
    probe_check(probe_caps, log=log)
    log(f"probe check s: {time.perf_counter() - t0:.3f}")
    kw = dict(ticks=ticks, events=events, requests=requests,
              request_examples=request_examples, seed=seed, log=log)
    dev = run("pallas", **kw)
    ref = run("numpy", **kw)

    placements = set()
    for i, m in enumerate(dev["masters"]):
        for g, t in m["tables"].items():
            mm = t["mirror"]
            log(f"master {i} table {g}: placement={mm['placement']} "
                f"syncs={mm['syncs']} rows={len(t['ids'])}")
            placements.add(mm["placement"])
            assert mm["syncs"] > 0, (i, g, mm)
        log(f"master {i}: fused_batches={m['fused_batches']}")
        assert m["fused_batches"] > 0, (i, m["fused_batches"])
    log(f"serving.device_blocks={dev['device_blocks']} "
        f"device_mirror.syncs={dev['mirror_syncs']}")
    assert placements == {"vmem", "hbm"}, placements
    assert dev["device_blocks"] > 0 and dev["mirror_syncs"] > 0

    dev_p = dev["preds"]
    pred_dev = float(np.abs(dev_p - ref["preds"]).max())
    worst = {"pred": pred_dev}
    for i, (dm, rm) in enumerate(zip(dev["masters"], ref["masters"])):
        for g, dt in dm["tables"].items():
            rt = rm["tables"][g]
            np.testing.assert_array_equal(dt["ids"], rt["ids"])
            for col in ("z", "n", "w"):
                a, b = dt[col], rt[col]
                worst[col] = max(worst.get(col, 0.0),
                                 float(np.abs(a - b).max(initial=0.0)))
                np.testing.assert_allclose(a, b, rtol=ROW_RTOL,
                                           atol=ROW_ATOL,
                                           err_msg=f"master {i} {g}.{col}")
    log("pallas vs numpy max abs deviation: "
        + " ".join(f"{k}={v:.3e}" for k, v in worst.items()))
    assert np.isfinite(dev_p).all() and pred_dev <= PRED_ATOL, pred_dev
    return worst


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found platform {dev.platform!r}",
              file=sys.stderr)
        return 2
    count = len(jax.devices())
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={count}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    t0 = time.perf_counter()
    smoke()
    print(f"total wall s: {time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
