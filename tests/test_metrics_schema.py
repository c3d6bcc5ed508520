"""Schema snapshot for the metrics surface: ``sync_metrics()`` must
remain a thin view over the cluster ``MetricsRegistry`` with the
pre-registry key layout, and the registry's canonical dotted names are
frozen here — adding a metric means updating SNAPSHOT *and* its row in
docs/OBSERVABILITY.md (`scripts/check_metrics_docs.py` enforces the
doc half)."""

import numpy as np
import pytest

from repro.configs.weips_ctr import FM_FTRL
from repro.core import ClusterConfig, WeiPSCluster

# the frozen canonical name set (scenario/group segments canonicalized)
SNAPSHOT = """
dedup_ratio
device_mirror.arena_bytes_uploaded
device_mirror.key_bytes_uploaded
device_mirror.key_full_uploads
device_mirror.key_incremental_uploads
device_mirror.syncs
device_mirror.tables
device_io.d2h_bytes
device_io.h2d_bytes
device_io.waits
pushed_bytes
queue_bytes
replica_failovers
replica_lag_skips
serving.admission.executed_examples
serving.admission.executed_requests
serving.admission.offered_examples
serving.admission.offered_requests
serving.admission.shed_deadline_requests
serving.admission.shed_depth_requests
serving.admission.shed_examples
serving.admission.shed_requests
serving.device_blocks
serving.latency.p50
serving.latency.p99
serving.predict_seconds
serving.replica_lag_skips
serving.scenarios.<scenario>.admission.executed_examples
serving.scenarios.<scenario>.admission.executed_requests
serving.scenarios.<scenario>.admission.offered_examples
serving.scenarios.<scenario>.admission.offered_requests
serving.scenarios.<scenario>.admission.shed_deadline_requests
serving.scenarios.<scenario>.admission.shed_depth_requests
serving.scenarios.<scenario>.admission.shed_examples
serving.scenarios.<scenario>.admission.shed_requests
serving.scenarios.<scenario>.batches
serving.scenarios.<scenario>.cache.hit_rate
serving.scenarios.<scenario>.cache.hits
serving.scenarios.<scenario>.cache.invalidated
serving.scenarios.<scenario>.cache.misses
serving.scenarios.<scenario>.cache.rows
serving.scenarios.<scenario>.cache.trims
serving.scenarios.<scenario>.dense_cache.hit_rate
serving.scenarios.<scenario>.dense_cache.hits
serving.scenarios.<scenario>.dense_cache.invalidated
serving.scenarios.<scenario>.dense_cache.misses
serving.scenarios.<scenario>.dense_cache.rows
serving.scenarios.<scenario>.dense_refreshes
serving.scenarios.<scenario>.examples
serving.scenarios.<scenario>.latency.p50
serving.scenarios.<scenario>.latency.p99
serving.scenarios.<scenario>.padding_fraction
serving.scenarios.<scenario>.requests
serving.shard_pulled_rows
staleness.p50
staleness.p99
sync_lag_records
sync_lag_seconds
training.scenarios.<scenario>.auc
training.scenarios.<scenario>.batches
training.scenarios.<scenario>.calibration
training.scenarios.<scenario>.dedup_ratio
training.scenarios.<scenario>.examples
training.scenarios.<scenario>.logloss
training.scenarios.<scenario>.padding_fraction
training.scenarios.<scenario>.step
""".split()


@pytest.fixture(scope="module")
def driven_cluster():
    cl = WeiPSCluster(FM_FTRL, ClusterConfig(
        num_master=1, num_slave=2, num_replicas=1, num_partitions=2))
    ids = np.arange(64, dtype=np.int64).reshape(8, 8)
    cl.train_on_batch(ids, np.zeros(8, np.float32), now=0.0)
    cl.sync_tick(0.0)
    cl.predict(ids)
    return cl


def _canonical(cl):
    scenarios = {s.name for s in cl.serving.registry} | \
        {s.name for s in cl.training.registry}
    groups = set(cl.groups)
    out = set()
    for name in cl.metrics_registry.names(1.0):
        segs = ["<scenario>" if s in scenarios else
                "<group>" if s in groups else s
                for s in name.split(".")]
        out.add(".".join(segs))
    return sorted(out)


def test_registry_names_match_snapshot(driven_cluster):
    got = _canonical(driven_cluster)
    assert got == sorted(SNAPSHOT), (
        "registry schema drifted: "
        f"added={sorted(set(got) - set(SNAPSHOT))} "
        f"removed={sorted(set(SNAPSHOT) - set(got))} — update SNAPSHOT "
        "and docs/OBSERVABILITY.md")


def test_sync_metrics_is_registry_view(driven_cluster):
    cl = driven_cluster
    now = 2.0
    tree = cl.metrics_registry.tree(now)
    m = cl.sync_metrics(now)
    assert m == tree


def test_sync_metrics_top_level_schema(driven_cluster):
    m = driven_cluster.sync_metrics(1.0)
    assert set(m) == {
        "sync_lag_seconds", "staleness", "sync_lag_records",
        "pushed_bytes", "queue_bytes", "dedup_ratio",
        "replica_failovers", "replica_lag_skips", "device_mirror",
        "device_io", "serving", "training"}
    assert set(m["staleness"]) == {"p50", "p99"}
    assert isinstance(m["serving"]["scenarios"], dict)
    assert isinstance(m["training"]["scenarios"], dict)


def test_values_are_live_not_frozen(driven_cluster):
    cl = driven_cluster
    before = cl.sync_metrics(1.0)["pushed_bytes"]
    ids = np.arange(64, 128, dtype=np.int64).reshape(8, 8)
    cl.train_on_batch(ids, np.ones(8, np.float32), now=2.0)
    cl.sync_tick(2.0)
    assert cl.sync_metrics(2.0)["pushed_bytes"] > before
