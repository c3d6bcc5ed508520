"""Quickstart — the paper's workload end-to-end: large-scale sparse CTR
online learning on WeiPS, driven through the online training plane.

One process simulates the whole symmetric fusion cluster: a click
stream emits exposure/feedback events; the vectorized SampleJoiner
window-joins them into labeled samples; the TrainPipeline admits,
dedups, and trains them in pow2 buckets against 4 master PS shards
(FM-FTRL); the streaming sync pipeline (collect -> gather -> push ->
scatter) deploys every update to 2 slave shards x 2 hot replicas within
one tick; predictors serve from the slaves; windowed progressive
validation monitors quality; checkpoints + domino downgrade guard
stability; backpressure keeps training from outrunning deployment.

Run: PYTHONPATH=src python examples/quickstart.py [--steps 300]
"""

import argparse
import sys
import time

import numpy as np

sys.path.insert(0, "src")

from repro.compile_cache import enable_compile_cache
from repro.configs.weips_ctr import FM_FTRL
from repro.core import ClusterConfig, WeiPSCluster
from repro.core.monitor import auc
from repro.data import ClickStream


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--gather-mode", default="realtime",
                    choices=("realtime", "threshold", "period"))
    ap.add_argument("--codec", default="int8",
                    choices=("identity", "cast16", "int8"))
    ap.add_argument("--join-window", type=float, default=3.0)
    ap.add_argument("--emit-on-feedback", action="store_true",
                    help="positives train the moment feedback arrives")
    args = ap.parse_args()
    enable_compile_cache()

    cluster = WeiPSCluster(FM_FTRL, ClusterConfig(
        num_master=4, num_slave=2, num_replicas=2, num_partitions=8,
        gather_mode=args.gather_mode, codec=args.codec,
        local_ckpt_interval=5.0, remote_ckpt_interval=60.0,
        join_window=args.join_window))
    pipeline = cluster.make_train_pipeline(
        emit_on_feedback=args.emit_on_feedback)
    stream = ClickStream(feature_space=1 << 18, fields=FM_FTRL.fields,
                         zipf_a=1.2, signal_scale=0.8, feedback_delay=1.0,
                         seed=0)
    scn = cluster.training.scenario()

    print(f"model={FM_FTRL.name} optimizer={FM_FTRL.optimizer} "
          f"codec={args.codec} gather={args.gather_mode} "
          f"join_window={args.join_window}s")
    t_start = time.time()
    now = 0.0
    for step in range(args.steps):
        # stream -> join -> admit -> dedup -> bucketed train ...
        pipeline.ingest(stream.events_batch(args.batch, now))
        cluster.train_scheduler.tick(now)
        cluster.sync_tick(now)                 # ... -> second-level deploy
        cluster.maybe_checkpoint(now)
        cluster.downgrade_check(now)
        now += 0.2
        if step % 50 == 0 or step == args.steps - 1:
            sm = cluster.sync_metrics(now)
            tm = sm["training"]["scenarios"][scn.name]
            jm = tm["pipeline"]["joiner"]
            print(f"step {step:4d} trained={tm['examples']:6d} "
                  f"logloss={tm['logloss']:.4f} auc={tm['auc']:.3f} "
                  f"calib={tm['calibration']:.2f} "
                  f"dedup={tm['dedup_ratio']:.2f} "
                  f"join_p50={jm['join_delay']['p50']:.1f}s "
                  f"in_flight={jm['in_flight']} "
                  f"sync_lag={sm['sync_lag_seconds']:.2f}s")
    cluster.train_scheduler.flush(now + args.join_window + 1)
    cluster.sync_tick(now + args.join_window + 1)

    # --- serve from the slave plane and compare with ground truth -------
    ids, y = stream.batch(2048)
    p = cluster.predict(ids)
    rows_total = sum(len(m.tables[g]) for m in cluster.masters
                     for g in cluster.groups)
    print(f"\nserving-plane AUC on fresh traffic: {auc(y, p):.3f}")
    print(f"PS rows: {rows_total}  "
          f"checkpoints: {cluster.store.versions()}")
    print(f"windowed progressive validation: "
          f"logloss={scn.evaluator.smoothed('logloss'):.4f} "
          f"auc={scn.evaluator.smoothed('auc'):.3f} "
          f"calibration={scn.evaluator.smoothed('calibration'):.3f}")
    jm = pipeline.metrics()["joiner"]
    print(f"joiner: emitted={jm['emitted']} late={jm['late_feedback']} "
          f"fast={jm['fast_emits']} "
          f"delay p50/p99={jm['join_delay']['p50']:.1f}/"
          f"{jm['join_delay']['p99']:.1f}s")
    print(f"wall: {time.time()-t_start:.1f}s for {args.steps} online steps")


if __name__ == "__main__":
    main()
