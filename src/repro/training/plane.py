"""The training plane as a subsystem — the symmetric twin of
``serving/plane.py``, closing the paper's fusion claim from the other
side: after PR 4 gave serving its own subsystem (router, cache,
micro-batch scheduler, scenario registry), training was still one
``WeiPSCluster.train_on_batch`` method. This plane promotes it:

    ingest (TrainPipeline) ── join → admit → dedup → bucket
      └ train_batch(scenario, ids, y, w):
          ONE np.unique over the batch's ids (the ≥90 % update-repetition
          dedup, shared by admission, pull, and push)
            ├ FeatureFilter admission — gates row *creation*: the pull
            │   reads with create=False (absent rows are zeros, exactly
            │   what a fresh row would hold) and non-admitted ids are
            │   dropped from the gradient push, so junk features never
            │   allocate PS rows
            ├ pull: argsort owner segments (RowRouter — the SAME routing
            │   code the serving plane runs) → bulk master gathers
            ├ pad rows/labels/weights to the pow2 bucket → the jitted
            │   weighted loss compiles once per bucket shape (the exact
            │   mirror of serving's PredictScheduler)
            ├ progressive validation BEFORE the update (paper §4.3.1):
            │   per-scenario ProgressiveValidator (checkpoint metrics) +
            │   StreamingEvaluator (the downgrade trigger signal)
            └ push: per-row grads segment-summed over the batch inverse,
                routed to owner masters; per-scenario dense head updated
                through its optimizer (the store's, or the scenario's
                ``dense_optimizer``) and re-broadcast

A multi-hot model (DLRM-DCNv2) keeps the one dedup and the one pull of
unique rows, then pools on the device (``kernels.ops.PooledLookup``):
the unique rows and the inverse go up once, each field's slots are
gathered and summed there, the tower runs on the pooled rows and the
batch's dense features, and the gradient comes back already reduced
to the unique rows — the ``(B, slots, dim)`` rows never cross the
host-device boundary. The tower, too, stays on the device from step to
step: master 0's bank holds the updated device arrays, and the pusher
codes them there (``Int8Transform.encode_tensors``), so only their int8
records cross.

Scenarios (``registry.py``) either share store groups or own namespaced
ones created online on every shard — N models training concurrently off
one shared PS, each with its own metrics, step clock, and pipeline.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.weips_ctr import CTRConfig
from repro.core.feature_filter import FeatureFilter
from repro.core.routing import RoutingPlan
from repro.kernels.device_io import count_h2d, to_host
from repro.models import ctr as ctr_model
from repro.obs import trace as obs_trace
from repro.optim import Optimizer, get_optimizer
from repro.kernels import ops
from repro.serving.router import RowRouter
from repro.training.registry import TrainRegistry, TrainScenario

# Adagrad's starting sum for a tower that trains apart from the store
# (TensorFlow's ``initial_accumulator_value``, as DLRM-DCNv2 trains)
TOWER_ADAGRAD_INITIAL_SUM = 0.1

def _padded(a, n: int) -> np.ndarray:
    """Float32 ``a`` extended with zero rows to ``n`` rows."""
    out = np.zeros((n,) + np.shape(a)[1:], np.float32)
    out[:len(a)] = a
    return out


class TrainingPlane:
    """Training-side subsystem over a cluster's master shards."""

    def __init__(self, plan: RoutingPlan, masters: list,
                 store_groups: dict[str, int], optimizer: Optimizer, *,
                 feature_filter: Optional[FeatureFilter] = None,
                 on_new_groups: Optional[Callable] = None,
                 seed: int = 0, max_batch: int = 4096):
        self.plan = plan
        self.masters = masters
        self.store_groups = store_groups      # live view of the PS groups
        self.optimizer = optimizer
        self.filter = feature_filter
        # cluster hook: create slave tables / widen serving store_groups
        # when an isolated scenario adds namespaced groups
        self.on_new_groups = on_new_groups
        self.seed = seed
        self.max_batch = max_batch            # sizes a multi-hot pool
        self.router = RowRouter(plan)
        self.registry = TrainRegistry()

    # ------------------------------------------------------------------
    # scenarios
    # ------------------------------------------------------------------
    def add_scenario(self, cfg: CTRConfig, *, name: Optional[str] = None,
                     share_groups: bool = True) -> TrainScenario:
        """Register a training scenario. ``share_groups=True`` trains the
        store's own groups (validated subset — optimizer slots must line
        up, so the scenario's optimizer family must match the store's).
        ``share_groups=False`` namespaces every group (and dense tensor)
        under ``<name>/`` and creates the tables online on every master
        (and, via ``on_new_groups``, every slave): isolated parameters on
        shared infrastructure."""
        name = name or cfg.name
        if cfg.optimizer != getattr(self.optimizer, "name", cfg.optimizer):
            raise ValueError(
                f"scenario optimizer {cfg.optimizer!r} must match the "
                f"store optimizer {self.optimizer.name!r} (one Pusher "
                f"transform per cluster)")
        groups = ctr_model.groups_for(cfg)
        if share_groups:
            ctr_model.check_scenario_groups(groups, self.store_groups)
            group_map = {g: g for g in groups}
            dense_prefix = ""
        else:
            group_map = {g: f"{name}/{g}" for g in groups}
            dense_prefix = f"{name}/"
            created = {}
            for g, dim in groups.items():
                store_g = group_map[g]
                for m in self.masters:
                    m.add_group(store_g, dim)
                self.store_groups[store_g] = dim
                created[store_g] = dim
            if self.on_new_groups is not None:
                self.on_new_groups(created)

        dense = ctr_model.init_dense(
            cfg, jax.random.PRNGKey(self.seed + len(self.registry)))
        opt = self.dense_optimizer(cfg)
        dense_slots = {k: opt.init_slots(jnp.asarray(v))
                       for k, v in dense.items()}
        pool = None
        if cfg.multi_hot:
            (g, dim), = groups.items()
            pool = ops.PooledLookup(cfg.multi_hot, dim,
                                    self.max_batch * cfg.id_slots)
        scn = TrainScenario(
            name=name, cfg=cfg, group_map=group_map, groups=groups,
            predict=ctr_model.predict_fn(cfg),
            loss_grads=ctr_model.weighted_loss_and_grads_fn(cfg),
            dense=dense, dense_slots=dense_slots, dense_prefix=dense_prefix,
            dense_step=jax.jit(opt.update_tree) if dense else None,
            pool=pool)
        for dn, v in dense.items():
            self.masters[0].push_dense(scn.dense_store_name(dn), v)
        return self.registry.add(scn)

    def dense_optimizer(self, cfg: CTRConfig) -> Optimizer:
        """The optimizer of a scenario's dense tensors: the store's, unless
        the model names its own (``dense_optimizer``, at ``lr``; Adagrad
        starts its sums at ``TOWER_ADAGRAD_INITIAL_SUM``)."""
        name = cfg.dense_optimizer
        if not name or name == self.optimizer.name:
            return self.optimizer
        kw = {"lr": cfg.lr}
        if name == "adagrad":
            kw["initial_accumulator"] = TOWER_ADAGRAD_INITIAL_SUM
        return get_optimizer(name, **kw)

    def scenario(self, name: Optional[str] = None) -> TrainScenario:
        return self.registry.get(name)

    # ------------------------------------------------------------------
    # pull path (the training twin of ServingPlane.pull_request)
    # ------------------------------------------------------------------
    def pull_unique(self, scn: TrainScenario,
                    uniq: np.ndarray) -> dict[str, np.ndarray]:
        """Unique-space ``{model group: (U, dim)}`` training rows through
        the shared argsort ownership router. ``create=False``: a row that
        does not exist yet reads as zeros — bit-identical to what a
        freshly created row would hold — so row *creation* stays with the
        gradient push, where admission gates it."""
        return self.router.pull(
            uniq, scn.groups, self.plan.master_shard(uniq),
            lambda mid, mids: {
                g: self.masters[mid].pull(scn.group_map[g], mids,
                                          create=False)
                for g in scn.groups})

    # ------------------------------------------------------------------
    # train step
    # ------------------------------------------------------------------
    def train_batch(self, scn: TrainScenario, ids: np.ndarray,
                    y: np.ndarray, *, now: float = 0.0,
                    weights: Optional[np.ndarray] = None,
                    bucket: Optional[int] = None,
                    dense_x: Optional[np.ndarray] = None) -> dict:
        """One online-learning step for one scenario: predict-before-train
        validation, weighted loss, gradient push through the PS
        optimizer. ``bucket`` pads rows/labels/weights up to that example
        count (padding weight 0) so the jitted fns compile once per
        bucket shape. ``dense_x`` (B, dense_features) are the examples'
        dense features, for a model that reads them."""
        want = scn.cfg.dense_features
        if want and (dense_x is None or np.shape(dense_x) !=
                     (np.shape(ids)[0], want)):
            raise ValueError(
                f"model {scn.cfg.name!r} needs dense_x of shape "
                f"({np.shape(ids)[0]}, {want}), got "
                f"{None if dense_x is None else np.shape(dense_x)}")
        with obs_trace.get_tracer().span("train.batch"):
            if scn.pool is not None:
                return self._train_pooled(scn, ids, y, dense_x, now=now,
                                          weights=weights, bucket=bucket)
            return self._train_batch(scn, ids, y, now=now, weights=weights,
                                     bucket=bucket)

    def _admit(self, scn: TrainScenario, ids: np.ndarray,
               uniq: np.ndarray) -> np.ndarray:
        """The admitted ids of a batch's ``uniq``: ONE dedup serves
        admission, pull, and push."""
        scn.stats.raw_ids += ids.size
        scn.stats.unique_ids += len(uniq)
        return self.filter.admit(uniq) if self.filter is not None else uniq

    def _pad_to(self, scn: TrainScenario, b: int,
                bucket: Optional[int]) -> int:
        """The padded example count of a ``b``-example batch."""
        nb = b if bucket is None or bucket < b else bucket
        if nb > b:
            scn.stats.padded_examples += nb - b
            scn.stats.bucket_counts[nb] = \
                scn.stats.bucket_counts.get(nb, 0) + 1
        return nb

    def _split(self, uniq: np.ndarray, admitted: np.ndarray) -> dict:
        """{master: its admitted ids of ``uniq``}: non-admitted ids are
        dropped BEFORE the push, so they never create rows."""
        keep = np.isin(uniq, admitted, assume_unique=True) \
            if len(admitted) != len(uniq) else None
        return self.plan.split_by_master(uniq if keep is None
                                         else uniq[keep])

    def _push_rows(self, scn: TrainScenario, group: str, uniq: np.ndarray,
                   by_master: dict, agg: np.ndarray) -> None:
        """Push the ``(U, dim)`` unique-row gradients of one group to their
        owner masters (``by_master`` from ``_split``)."""
        with obs_trace.get_tracer().span("train.grad_agg"):
            parts = [(mid, mids, agg[np.searchsorted(uniq, mids)])
                     for mid, mids in by_master.items()]
        for mid, mids, gm in parts:
            self.masters[mid].push_grad(scn.group_map[group], mids, gm,
                                        step=scn.step)

    def _update_dense(self, scn: TrainScenario, dense_j: dict,
                      dense_grads: dict) -> None:
        """The dense tensors' optimizer step (one jitted program over all
        of them) and their push to master shard 0: a ``train.dense_update``
        span. The new tensors stay on the device: the next forward reads
        them there, and master 0's bank holds the same arrays."""
        if not dense_grads:
            return
        with obs_trace.get_tracer().span("train.dense_update"):
            new_w, scn.dense_slots = scn.dense_step(
                dense_j, scn.dense_slots, dense_grads, scn.step)
            for dn, v in new_w.items():
                scn.dense[dn] = v
                self.masters[0].push_dense(scn.dense_store_name(dn), v)

    def _train_pooled(self, scn: TrainScenario, ids: np.ndarray,
                      y: np.ndarray, dense_x: np.ndarray, *, now: float,
                      weights: Optional[np.ndarray],
                      bucket: Optional[int]) -> dict:
        """``train_batch`` of a multi-hot model: one dedup and one pull of
        unique rows on the host, pooling and its transpose on the device
        (``train.pool``), the tower on the pooled rows and ``dense_x``,
        the unique-row gradients pushed, the tower updated
        (``train.dense_update``)."""
        tr = obs_trace.get_tracer()
        ids = np.asarray(ids, dtype=np.int64)
        b, s = ids.shape
        y = np.asarray(y, np.float32)
        w = np.ones(b, np.float32) if weights is None else \
            np.asarray(weights, np.float32)
        (group, _), = scn.groups.items()
        with tr.span("train.dedup"):
            uniq, inverse, order = RowRouter.unique_order(ids)
            admitted = self._admit(scn, ids, uniq)
        with tr.span("train.pull"):
            rows = self.pull_unique(scn, uniq)[group]
            nb = self._pad_to(scn, b, bucket)
        with tr.span("train.pool"):
            inv = np.zeros((nb, s), np.int32)
            inv[:b] = inverse.reshape(b, s)
            pooled = scn.pool.lookup(rows, inv)
            tr.count("train.pooled_ids", b * s)
        with tr.span("train.forward"):
            x_in, y_in, w_in = (_padded(a, nb) for a in (dense_x, y, w))
            # the tower goes up only while it is host arrays (seeded or
            # restored); after its first update it lives on the device
            count_h2d(x_in, y_in, w_in, *scn.dense.values())
            x_j = jnp.asarray(x_in)
            dense_j = {k: jnp.asarray(v) for k, v in scn.dense.items()}
            # progressive validation (predict BEFORE applying the update)
            p = to_host(scn.predict(pooled, dense_j, x_j))[0][:b]
            point = scn.validator.observe(now, scn.step, y, p)
            scn.evaluator.observe(now, scn.step, y, p, weights=w)
            loss, g_pooled, dense_grads = scn.loss_grads(
                pooled, dense_j, x_j, jnp.asarray(y_in), jnp.asarray(w_in))
        with tr.span("train.grad_agg"):
            # padding slots (zero gradient) go last, onto the last row
            pad = np.arange(b * s, nb * s)
            agg = scn.pool.grad(
                g_pooled, np.concatenate([order, pad]),
                np.concatenate([inverse[order],
                                np.full(len(pad), len(uniq) - 1)]),
                len(uniq))
            by_master = self._split(uniq, admitted)
        self._push_rows(scn, group, uniq, by_master, agg)
        self._update_dense(scn, dense_j, dense_grads)
        scn.step += 1
        scn.stats.batches += 1
        scn.stats.examples += b
        return {"loss": float(to_host(loss)[0]), **point.values}

    def _train_batch(self, scn: TrainScenario, ids: np.ndarray,
                     y: np.ndarray, *, now: float,
                     weights: Optional[np.ndarray],
                     bucket: Optional[int]) -> dict:
        """``train_batch``'s body; each stage is a span of
        ``repro.obs.trace`` (``train.dedup`` … ``train.grad_agg``), the
        masters' updates nest their own ``ps.*`` spans."""
        tr = obs_trace.get_tracer()
        ids = np.asarray(ids, dtype=np.int64)
        b, f = ids.shape
        y = np.asarray(y, np.float32)
        w = np.ones(b, np.float32) if weights is None else \
            np.asarray(weights, np.float32)

        with tr.span("train.dedup"):
            uniq, inverse = RowRouter.unique(ids)
            admitted = self._admit(scn, ids, uniq)

        with tr.span("train.pull"):
            vals = self.pull_unique(scn, uniq)
            rows = RowRouter.expand(vals, inverse, (b, f))
            nb = self._pad_to(scn, b, bucket)
            if nb > b:
                pad = nb - b
                rows = {g: np.concatenate(
                    [v, np.zeros((pad,) + v.shape[1:], v.dtype)]) for g, v
                    in rows.items()}
                y_in = np.concatenate([y, np.zeros(pad, np.float32)])
                w_in = np.concatenate([w, np.zeros(pad, np.float32)])
            else:
                y_in, w_in = y, w

        with tr.span("train.forward"):
            count_h2d(*rows.values(), *scn.dense.values(), y_in, w_in)
            rows_j = {k: jnp.asarray(v) for k, v in rows.items()}
            dense_j = {k: jnp.asarray(v) for k, v in scn.dense.items()}
            # progressive validation (predict BEFORE applying the update);
            # padded rows are sliced off — the metrics never see them
            p = to_host(scn.predict(rows_j, dense_j))[0][:b]
            point = scn.validator.observe(now, scn.step, y, p)
            scn.evaluator.observe(now, scn.step, y, p, weights=w)
            loss, row_grads, dense_grads = scn.loss_grads(
                rows_j, dense_j, jnp.asarray(y_in), jnp.asarray(w_in))

        # aggregate per-row grads over duplicate ids, push to owner
        # masters (padding rows carry weight 0 → zero grads, and the [:b]
        # slice drops them from the aggregation entirely)
        with tr.span("train.grad_agg"):
            by_master = self._split(uniq, admitted)
        for group, g in row_grads.items():
            with tr.span("train.grad_agg"):
                g = to_host(g)[0][:b].reshape(-1, g.shape[-1])  # (B*F, dim)
                agg = np.zeros((len(uniq), g.shape[-1]), np.float32)
                np.add.at(agg, inverse, g)
            self._push_rows(scn, group, uniq, by_master, agg)
        self._update_dense(scn, dense_j, dense_grads)

        scn.step += 1
        scn.stats.batches += 1
        scn.stats.examples += b
        return {"loss": float(to_host(loss)[0]), **point.values}

    # ------------------------------------------------------------------
    # metrics
    # ------------------------------------------------------------------
    def metrics(self) -> dict:
        return {"scenarios": {s.name: s.metrics() for s in self.registry}}

    def register_metrics(self, reg, prefix: str = "training") -> None:
        """Publish per-scenario training counters into a
        ``repro.obs.metrics.MetricsRegistry`` — same shape as
        ``metrics()``."""
        from repro.obs.metrics import join
        reg.register(join(prefix, "scenarios"),
                     lambda: {s.name: s.metrics() for s in self.registry})
