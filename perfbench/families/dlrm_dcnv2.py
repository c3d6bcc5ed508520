"""Family ``dlrm_dcnv2``: DLRM-DCNv2 (MLPerf Training's recommendation
model) trained online through the parameter server: 128-wide multi-hot
embeddings on the masters under FTRL-proximal, pooled on the device, and
a ~16M-parameter tower (bottom MLP, low-rank DCN-V2 cross layers, top
MLP) trained by Adagrad on master 0, both streamed int8 to the serving
replicas. A configuration names it with ``"family": "dlrm_dcnv2"``.

The entries are ``ctr_ftrl``'s (see its docstring). The arithmetic is
``dlrm_dcnv2_ref.py`` beside this module (the plain reference, and the
pre-seeded rows and tower it regenerates) and ``harness/generate.py``'s
ids and click stream: each of the 26 fields draws its multi-hot size of
ids (Zipf within the field, so ids repeat within a bag as they may in
the reference's synthetic data), and 13 dense features, seeded counts
fed as log(1 + x). The numbers compared (``judge``), over the ids the
recorded calls touched:

- ``first_rows_miss_pct``: of the rows that took exactly one training
  step (ids of the first call with a nonzero weight, and of no later
  one), the share (%) whose z change is off the reference's by more than
  ``ONE_STEP_TOL`` of its norm. One step from the pre-seeded state is
  where the program and the reference compute the same thing from the
  same inputs: after a few steps the two float32 computations part
  through the tower's ReLUs (a perturbation of 1e-6 moves under 1 % of
  examples' gradients by more than 1e-5, and a few by 10 %), so later
  rows are held only to ``rows_change_err``. z's change is the row's
  gradient; n's is its square, under an ulp of the pre-seeded n, so
  float32 rounding and not the arithmetic decides it, and n is held by
  ``rows_change_err`` alone;
- ``rows_change_err``, ``dense_change_err``: the norm of the gap of the
  master rows (z, n, w, by column) and of master 0's tower (by tensor) to
  the reference's, over the norm of the reference's change from the
  pre-seeded state (a state left unchanged reads 1);
- ``replica_miss_pct``, ``replica_dense_miss_pct``: the replicas against
  what the master holds, int8-coded as the sync codes it, the share (%)
  of elements more than half an int8 step (of the master row's scale)
  off;
- ``join_wrong``, ``join_owed``: ``check.join_numbers`` with each row's
  dense features keyed beside its ids.
"""

from __future__ import annotations

import importlib.util
import sys
from pathlib import Path

import numpy as np

from harness import check
from harness import generate as gen

HERE = Path(__file__).resolve().parent
# a row that took one training step misses when its z change is off the
# reference's by more than this share (norm over the row): a bfloat16
# tower puts each row's gradient 0.17 % or more off (CPU, tiny cut), a
# float32 one under 1e-7
ONE_STEP_TOL = 1e-3


def _beside(name: str):
    key = "perfbench_family_" + name
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, HERE / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
    return sys.modules[key]


ref = _beside("dlrm_dcnv2_ref")

# the CPU cut of ``perfbench/tests/tiny.py``: 4 fields, small widths
TINY = {"field_vocab": [300, 7, 1200, 2500], "multi_hot": [3, 1, 2, 5],
        "embed_dim": 8, "bottom_mlp": [16, 8], "top_mlp": [16, 1],
        "dcn_layers": 2, "dcn_rank": 4}


def model_config(cfg: dict):
    """The program's ``CTRConfig`` of a configuration."""
    from repro.configs.weips_ctr import CTRConfig
    o, a = cfg["ftrl"], cfg["adagrad"]
    return CTRConfig(
        name=cfg["name"], model_type="dlrm_dcnv2",
        feature_space=int(sum(cfg["field_vocab"])),
        fields=len(cfg["multi_hot"]), embed_dim=int(cfg["embed_dim"]),
        dense_features=int(cfg["dense_features"]),
        multi_hot=tuple(int(n) for n in cfg["multi_hot"]),
        bottom_mlp=tuple(cfg["bottom_mlp"]), top_mlp=tuple(cfg["top_mlp"]),
        dcn_layers=int(cfg["dcn_layers"]), dcn_rank=int(cfg["dcn_rank"]),
        optimizer="ftrl", ftrl_alpha=o["alpha"], ftrl_beta=o["beta"],
        ftrl_l1=o["l1"], ftrl_l2=o["l2"], dense_optimizer="adagrad",
        lr=a["lr"])


def build(cfg: dict, seed: int):
    """The cluster of the configuration's model and layout."""
    from repro.core import ClusterConfig, WeiPSCluster
    c = cfg["cluster"]
    return WeiPSCluster(model_config(cfg), ClusterConfig(
        num_master=c["num_master"], num_slave=c["num_slave"],
        num_replicas=c["num_replicas"],
        num_partitions=c["num_partitions"], codec=c["codec"],
        codec_backend=c["codec_backend"], ps_backend=c["ps_backend"],
        join_window=c["join_window_s"],
        serve_cache_rows=c["serve_cache_rows"],
        train_buckets=tuple(c["train_buckets"]),
        seed=seed % (2 ** 31 - 1)))


def preseed(cl, cfg: dict, seed: int, *, masters: bool,
            replicas: bool) -> int:
    """Load every id of the vocabulary into the master tables (FTRL z, n,
    w) and/or the serving replicas (int8-coded w), and the seeded tower
    into master 0 (and the scenario that trains it). Returns the rows
    loaded."""
    ids = gen.Vocab(cfg["field_vocab"]).all_ids()
    (g, dim), = cfg["groups"].items()
    zeros = np.zeros(len(ids), np.int64)
    loaded = 0
    if masters:
        owner = cl.plan.master_shard(ids)
        for m in cl.masters:
            sel = np.flatnonzero(owner == m.shard_id)
            z, n, w = ref.initial_rows(ids[sel], dim, seed, 0, cfg["ftrl"])
            m.load_table_rows(g, {
                "ids": ids[sel], "w": w, "slots": {"z": z, "n": n},
                "last_touch": zeros[sel], "touch_count": zeros[sel]})
            loaded += len(sel)
            del z, n, w
        scn = cl.training.scenario()
        tower = ref.initial_tower(cfg, seed)
        if set(tower) != set(scn.dense):
            raise ValueError(f"tower tensors {sorted(scn.dense)} are not "
                             f"the reference's {sorted(tower)}")
        for name, v in tower.items():
            scn.dense[name] = v
            cl.masters[0].push_dense(scn.dense_store_name(name), v.copy())
    if replicas:
        owner = cl.plan.slave_shard(ids)
        for rs in cl.replica_sets:
            sel = np.flatnonzero(owner == rs.replicas[0].shard_id)
            ws, = ref.initial_rows(ids[sel], dim, seed, 0, cfg["ftrl"],
                                   serve=True)
            for shard in rs.replicas:
                shard.tables[g].load_rows({
                    "ids": ids[sel], "w": ws, "slots": {},
                    "last_touch": zeros[sel], "touch_count": zeros[sel]})
                loaded += len(sel)
            del ws
    return loaded


class MultiHot:
    """The fields' slots as ``generate.TrainStream`` samples them:
    ``sample`` gives ``(n, slots)`` ids, field ``f`` filling its
    ``multi_hot[f]`` slots, each a power-law rank of the field's own
    vocabulary."""

    def __init__(self, sizes, multi_hot):
        self.vocab = gen.Vocab(sizes)
        self.multi_hot = [int(k) for k in multi_hot]
        self.total = self.vocab.total
        self.fields = sum(self.multi_hot)

    def sample(self, r: np.random.Generator, n: int, a: float) -> np.ndarray:
        u = r.random((n, self.fields))
        out = np.empty((n, self.fields), np.int64)
        lo = 0
        for f, k in enumerate(self.multi_hot):
            size = int(self.vocab.sizes[f])
            out[:, lo:lo + k] = self.vocab.ids(
                f, gen.zipf_ranks(u[:, lo:lo + k], size, a))
            lo += k
        return out


def dense_features(r: np.random.Generator, n: int, k: int) -> np.ndarray:
    """``(n, k)`` float32 log(1 + x) of counts x: feature ``f``'s counts
    are exponential of mean 10^(f / 3), floored (1 to about 2e4)."""
    mean = 10.0 ** (np.arange(k) / 3.0)
    counts = np.floor(r.exponential(1.0, (n, k)) * mean)
    return np.log1p(counts).astype(np.float32)


class Stream:
    """The click stream of ``generate.TrainStream`` over the multi-hot
    slots, each event with its dense features, as the harness's train
    loop takes it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.data.streams import EventBatch
        self._batch_cls = EventBatch
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.slots = MultiHot(cfg["field_vocab"], cfg["multi_hot"])
        self.stream = gen.TrainStream(self.slots, traffic, seed)
        self.r_dense = gen.rng(seed, 41)

    def tick(self, t: float) -> tuple:
        """(the tick's events, dense features among them, as the
        reference's join reads them; the batch ``TrainPipeline.ingest``
        takes)."""
        ev = self.stream.tick()
        ev["dense"] = dense_features(self.r_dense, len(ev["view_ids"]),
                                     int(self.cfg["dense_features"]))
        return ev, self._batch_cls(
            t=t, view_ids=ev["view_ids"], feature_ids=ev["feature_ids"],
            labels=ev["labels"], fb_view_ids=ev["fb_view_ids"],
            fb_t=t + ev["fb_delay"], dense=ev["dense"])

    def warm_batches(self, buckets):
        """Every train bucket with zero-weight batches of the stream's own
        ids, filled to the bucket and to just over half of it (so the
        unique-id counts a window's calls can take are reached), as
        ``(args, kwargs)`` of ``train_batch`` after the scenario."""
        r = gen.rng(self.seed, 7)
        a = float(self.traffic["zipf_a"])
        k = int(self.cfg["dense_features"])
        for b in buckets:
            for m in (b // 2 + 1, b):
                ids = self.slots.sample(r, m, a)
                yield (ids, np.zeros(m, np.float32)), {
                    "weights": np.zeros(m, np.float32), "bucket": b,
                    "dense_x": dense_features(r, m, k)}

    def unique_per_batch(self, args: dict) -> dict:
        """{group: unique ids} of one recorded ``train_batch`` call."""
        n = len(np.unique(args["ids"]))
        return {g: n for g in self.cfg["groups"]}


def train_stream(cfg: dict, traffic: dict, seed: int) -> Stream:
    return Stream(cfg, traffic, seed)


def requests(cfg: dict, traffic: dict):
    raise ValueError("family dlrm_dcnv2 has no serve cells: the serve path "
                     "carries no dense features")


def collect(cl, cfg: dict, batches: list) -> dict:
    """Master and replica rows of every id the recorded calls touched,
    master 0's tower and each replica's, as the program holds them after
    the window."""
    ids = np.unique(np.concatenate([np.asarray(b["ids"]).reshape(-1)
                                    for b in batches]))
    owner = cl.plan.master_shard(ids)
    sowner = cl.plan.slave_shard(ids)
    (g, dim), = cfg["groups"].items()
    out = {k: np.empty((len(ids), dim), np.float32) for k in ("z", "n", "w")}
    for m in cl.masters:
        sel = np.flatnonzero(owner == m.shard_id)
        t = m.tables[g]
        w, slots = t.read_rows(t.lookup(ids[sel]))
        out["w"][sel], out["z"][sel], out["n"][sel] = \
            w, slots["z"], slots["n"]
    reps, towers = [], []
    for rs in cl.replica_sets:
        for shard in rs.replicas:
            sel = np.flatnonzero(sowner == shard.shard_id)
            t = shard.tables[g]
            reps.append((sel, t.read_rows(t.lookup(ids[sel]))[0]))
            towers.append({k: np.asarray(v) for k, v in shard.dense.items()})
    scn = cl.training.scenario()
    tower = {k: np.asarray(cl.masters[0].dense.tensors[
        scn.dense_store_name(k)]) for k in scn.dense}
    return {"ids": ids, "masters": {g: out}, "replicas": {g: reps},
            "tower": tower, "replica_towers": towers}


def _change_rel(got, want, start) -> float:
    """The norm of the gap over the norm of ``want``'s change from
    ``start``: 1 for a state left at ``start``."""
    moved = float(np.linalg.norm(np.asarray(want, np.float64) - start))
    if moved == 0.0:
        return 0.0
    return float(np.linalg.norm(np.asarray(got, np.float64) - want)) / moved


def first_only(calls: list) -> np.ndarray:
    """Sorted ids that the first call with a nonzero weight touched and
    no later call did: their rows took exactly one training step, from
    the pre-seeded state (every call before it weighs 0 and changes
    nothing)."""
    real = [c for c in calls if c[2].any()]
    if not real:
        return np.empty(0, np.int64)
    first = np.unique(real[0][0])
    later = np.unique(np.concatenate([c[0].reshape(-1) for c in real[1:]])) \
        if len(real) > 1 else np.empty(0, np.int64)
    return np.setdiff1d(first, later, assume_unique=True)


def state_numbers(out: dict, r, start_rows: dict, start_tower: dict,
                  first: np.ndarray) -> dict:
    """The master rows and master 0's tower against the reference ``r``
    (``start_*``: the pre-seeded state, ``first``: ``first_only``)."""
    ids = out["ids"]
    want = r.rows(ids)["emb"]
    got = out["masters"]["emb"]
    sel = np.searchsorted(ids, first)
    w = np.asarray(want["z"][sel], np.float64)
    moved = np.linalg.norm(w - start_rows["z"][sel], axis=1)
    gap = np.linalg.norm(np.asarray(got["z"][sel], np.float64) - w, axis=1)
    miss = gap > ONE_STEP_TOL * moved
    return {
        "first_rows_miss_pct": 100.0 * miss.mean() if len(sel) else 0.0,
        "rows_change_err": max(_change_rel(got[c], want[c], start_rows[c])
                               for c in want),
        "dense_change_err": max(_change_rel(out["tower"][k], w,
                                            start_tower[k])
                                for k, w in r.tower.items())}


def replica_numbers(out: dict, opt: dict) -> dict:
    """The replicas against what the master holds, int8-coded as the
    sync codes it: ``replica_miss_pct`` over the rows (the serve weight
    of the master's z, n), ``replica_dense_miss_pct`` over the tower (by
    its rows); the share (%) of elements more than half an int8 step (of
    the master row's scale) off."""
    m = out["masters"]["emb"]
    want = gen.int8_roundtrip(gen.ftrl_w(m["z"], m["n"], opt))
    bad = tot = 0
    for sel, rows in out["replicas"]["emb"]:
        step = np.abs(want[sel]).max(axis=1, keepdims=True) / 127.0
        gap = np.abs(np.asarray(rows, np.float64) - want[sel])
        bad += int((gap > 0.5 * step).sum())
        tot += gap.size
    rbad = rtot = 0
    for rep in out["replica_towers"]:
        for k, t in out["tower"].items():
            rows = t.reshape(-1, t.shape[-1]) if t.ndim > 1 else \
                t.reshape(1, -1)
            step = np.abs(rows).max(axis=1, keepdims=True) / 127.0
            gap = np.abs(np.asarray(rep[k], np.float64).reshape(rows.shape)
                         - ref.int8_rows(t).reshape(rows.shape))
            rbad += int((gap > 0.5 * step).sum())
            rtot += gap.size
    return {"replica_miss_pct": 100.0 * bad / max(tot, 1),
            "replica_dense_miss_pct": 100.0 * rbad / max(rtot, 1)}


def _keyed(ids: np.ndarray, dense: np.ndarray) -> np.ndarray:
    """A row's ids with its dense features' bits beside them, so the
    join's row keys hold both."""
    bits = np.ascontiguousarray(dense, np.float32).view(np.int32)
    return np.concatenate([ids, bits.astype(np.int64)], axis=1)


def join_numbers(spec: dict, st, calls: list) -> dict:
    """``check.join_numbers`` of the calls the join made (after set-up's
    warm batches), dense features keyed with the ids."""
    events = [(t, {**ev, "feature_ids": _keyed(ev["feature_ids"],
                                               ev["dense"])})
              for t, ev in st.events]
    rows = [(_keyed(ids, x), y, w) for ids, y, w, x, _ in
            calls[st.stream_from:]]
    return check.join_numbers(events, rows,
                              spec["cfg"]["cluster"]["join_window_s"],
                              spec["traffic"]["tick_s"])


def start_state(cfg: dict, seed: int, ids: np.ndarray) -> tuple:
    """The pre-seeded rows of ``ids`` ({"z", "n", "w"}) and tower."""
    z, n, w = ref.initial_rows(ids, cfg["embed_dim"], seed, 0, cfg["ftrl"])
    return {"z": z, "n": n, "w": w}, ref.initial_tower(cfg, seed)


def numbers(spec: dict, st, r, calls: list, start: tuple,
            out: dict = None) -> dict:
    """A run's numbers: its state (or ``out``, a stand-in's, in
    ``collect``'s form) against the replayed reference ``r`` (``start``:
    ``start_state``), its replicas against its masters, and the join's
    calls against the generated stream."""
    out = st.out if out is None else out
    return {**state_numbers(out, r, *start, first_only(calls)),
            **replica_numbers(out, spec["cfg"]["ftrl"]),
            **join_numbers(spec, st, calls)}


def judge(spec: dict, seed: int, st) -> dict:
    """The numbers compared with the float32 reference."""
    calls = ref.calls_of(st.batches)
    r = ref.Reference(spec["cfg"], seed)
    r.replay(calls)
    return numbers(spec, st, r, calls,
                   start_state(spec["cfg"], seed, st.out["ids"]))


def _as_output(r, ids: np.ndarray, tower: dict = None) -> dict:
    """A reference's state in ``collect``'s form (control and faults)."""
    idx = np.arange(len(ids))
    tower = r.tower if tower is None else tower
    return {"ids": ids, "masters": r.rows(ids),
            "replicas": {g: [(idx, v)] for g, v in
                         r.replica_rows(ids).items()},
            "tower": tower,
            "replica_towers": [{k: ref.int8_rows(v)
                                for k, v in tower.items()}]}


def readings(spec: dict, seed: int, st) -> dict:
    """The program's numbers, the control's (the reference with bfloat16
    matmuls, in the program's place) and the planted faults': the tower's
    matmuls at a TPU's default precision (one bfloat16 pass), the rows as
    pre-seeded, half of every batch left out, the tower frozen as
    pre-seeded, mean in place of sum pooling, and the program's own state
    with its replicas' rows, or their towers, left as pre-seeded (the
    sync's records dropped, the masters as trained)."""
    cfg = spec["cfg"]
    calls = ref.calls_of(st.batches)
    ids = st.out["ids"]
    r = ref.Reference(cfg, seed)
    r.replay(calls)

    def replayed(the_calls, **kw):
        o = ref.Reference(cfg, seed, **kw)
        o.replay(the_calls)
        return _as_output(o, ids)

    half = [tuple(a[:max(1, len(a) // 2)] for a in c[:4]) + (c[4],)
            for c in calls]
    start = start_state(cfg, seed, ids)
    idx = np.arange(len(ids))
    unchanged = {**_as_output(r, ids), "masters": {"emb": start[0]},
                 "replicas": {"emb": [(idx, gen.int8_roundtrip(
                     start[0]["w"]))]}}

    def judged(out=None):
        return numbers(spec, st, r, calls, start, out)

    stale_rows = [(sel, gen.int8_roundtrip(start[0]["w"][sel]))
                  for sel, _ in st.out["replicas"]["emb"]]
    stale_tower = {k: ref.int8_rows(v) for k, v in start[1].items()}

    return {"program": judged(),
            "control": judged(replayed(calls, mode="bfloat16")),
            "default_precision": judged(replayed(calls, mode="one_pass")),
            "rows_unchanged": judged(unchanged),
            "half_batch": judged(replayed(half)),
            "tower_frozen": judged(_as_output(r, ids, start[1])),
            "mean_pool": judged(replayed(calls, how="mean")),
            "replica_rows_unchanged": judged(
                {**st.out, "replicas": {"emb": stale_rows}}),
            "replica_tower_unchanged": judged(
                {**st.out, "replica_towers": [stale_tower] *
                 len(st.out["replica_towers"])})}


def tiny(spec: dict) -> None:
    """Cut ``spec`` in place to a size the CPU runs in seconds (Pallas in
    interpret mode): 4 fields of a few thousand ids, narrow tower, a few
    hundred events a tick. Layout and codec stay as configured."""
    cfg = spec["cfg"]
    cfg.update({k: list(v) if isinstance(v, list) else v
                for k, v in TINY.items()})
    cfg["groups"] = {"emb": TINY["embed_dim"]}
    cfg["sizing"]["ids_per_master"] = sum(TINY["field_vocab"]) // 4
    cfg["cluster"]["train_buckets"] = [256]
    spec["traffic"].update(events_per_tick=256, warm_ticks=2)
