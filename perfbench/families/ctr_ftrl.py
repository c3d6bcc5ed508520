"""Family ``ctr_ftrl``: LR and FM models over one sparse id per field,
trained online with FTRL-proximal on the masters and served from int8
replicas. A configuration names it with ``"family": "ctr_ftrl"``.

The family's arithmetic is ``harness/generate.py`` (ids, events, rows),
``harness/reference.py`` (the plain reference) and the FTRL parts of
``harness/check.py`` (the numbers compared); this module hands them to
the harness's generic drive loops (``harness/drive.py``) and run
(``harness/bench.py``), which call a family's entries so:

- ``build(cfg, seed)``: the cluster;
- ``preseed(cl, cfg, seed, masters=, replicas=)``: fill its tables in
  set-up; returns the rows loaded;
- ``train_stream(cfg, traffic, seed)``: an object with ``tick(t)`` ->
  (the tick's events for the reference, the batch
  ``TrainPipeline.ingest`` takes), ``warm_batches(buckets)`` ->
  ``(args, kwargs)`` of ``train_batch`` after the scenario, and
  ``unique_per_batch(call)`` -> {group: unique ids} of one recorded
  call (the roofline readers' counts);
- ``requests(cfg, traffic)``: ``draw(rng, size)`` -> one predict request;
- ``collect(cl, cfg, batches)``: what the program holds after the
  window, ``batches`` being the recorded ``train_batch`` calls, each a
  dict of its arguments;
- ``judge(spec, seed, st)``: {number: value} held to the cell's
  ``limits``; ``readings(spec, seed, st)``: {"program", "control", ...:
  {number: value}} that ``tools/readings.py`` prints;
- ``tiny(spec)``: the cut, in place, that ``tests/tiny.py`` runs on the
  CPU.
"""

from __future__ import annotations

import numpy as np

from harness import check
from harness import generate as gen
from harness import reference

# the CPU cut of ``perfbench/tests/tiny.py``: ids a field
TINY_VOCAB = [64, 3, 500, 2000, 37, 900]


def build(cfg: dict, seed: int):
    """The cluster of the configuration's model and layout."""
    from repro.configs.weips_ctr import CTRConfig
    from repro.core import ClusterConfig, WeiPSCluster
    o = cfg["ftrl"]
    model = CTRConfig(name=cfg["name"], model_type=cfg["model_type"],
                      feature_space=int(sum(cfg["field_vocab"])),
                      fields=len(cfg["field_vocab"]),
                      embed_dim=int(cfg["groups"].get("v", 1)),
                      optimizer="ftrl", ftrl_alpha=o["alpha"],
                      ftrl_beta=o["beta"], ftrl_l1=o["l1"], ftrl_l2=o["l2"])
    c = cfg["cluster"]
    return WeiPSCluster(model, ClusterConfig(
        num_master=c["num_master"], num_slave=c["num_slave"],
        num_replicas=c["num_replicas"],
        num_partitions=c["num_partitions"], codec=c["codec"],
        codec_backend=c["codec_backend"], ps_backend=c["ps_backend"],
        join_window=c["join_window_s"],
        serve_cache_rows=c["serve_cache_rows"],
        seed=seed % (2 ** 31 - 1)))


def preseed(cl, cfg: dict, seed: int, *, masters: bool,
            replicas: bool) -> int:
    """Load every id of the vocabulary into the master tables (FTRL z, n,
    w) and/or the serving replicas (int8-coded w) with the probe-free
    bulk insert. Returns the rows loaded."""
    ids = gen.Vocab(cfg["field_vocab"]).all_ids()
    zeros = np.zeros(len(ids), np.int64)
    loaded = 0
    m_owner = cl.plan.master_shard(ids) if masters else None
    s_owner = cl.plan.slave_shard(ids) if replicas else None
    for gi, (g, dim) in enumerate(cfg["groups"].items()):
        z, n = gen.ftrl_state(ids, dim, seed, gi)
        w = gen.ftrl_w(z, n, cfg["ftrl"])
        if masters:
            for m in cl.masters:
                sel = np.flatnonzero(m_owner == m.shard_id)
                m.load_table_rows(g, {
                    "ids": ids[sel], "w": w[sel],
                    "slots": {"z": z[sel], "n": n[sel]},
                    "last_touch": zeros[sel], "touch_count": zeros[sel]})
                loaded += len(sel)
        del z, n
        if replicas:
            ws = gen.int8_roundtrip(w)
            for rs in cl.replica_sets:
                for shard in rs.replicas:
                    sel = np.flatnonzero(s_owner == shard.shard_id)
                    shard.tables[g].load_rows({
                        "ids": ids[sel], "w": ws[sel], "slots": {},
                        "last_touch": zeros[sel],
                        "touch_count": zeros[sel]})
                    loaded += len(sel)
            del ws
        del w
    return loaded


class Stream:
    """The click stream of ``generate.TrainStream``, as the train driver
    takes it."""

    def __init__(self, cfg: dict, traffic: dict, seed: int):
        from repro.data.streams import EventBatch
        self._batch_cls = EventBatch
        self.cfg = cfg
        self.traffic = traffic
        self.seed = seed
        self.vocab = gen.Vocab(cfg["field_vocab"])
        self.stream = gen.TrainStream(self.vocab, traffic, seed)

    def tick(self, t: float) -> tuple:
        """(the tick's events as the reference's join reads them, the
        batch ``TrainPipeline.ingest`` takes)."""
        ev = self.stream.tick()
        return ev, self._batch_cls(
            t=t, view_ids=ev["view_ids"], feature_ids=ev["feature_ids"],
            labels=ev["labels"], fb_view_ids=ev["fb_view_ids"],
            fb_t=t + ev["fb_delay"])

    def warm_batches(self, buckets):
        """Every train bucket once with zero-weight batches, of hot ids and
        of ids spread over the vocabulary (so the per-call id counts reach
        every power-of-two the window's calls can take), as
        ``(args, kwargs)`` of ``train_batch`` after the scenario."""
        r = gen.rng(self.seed, 7)
        v = self.vocab
        for b in buckets:
            for spread in (False, True):
                if spread:
                    ids = (r.integers(0, v.total, (b, v.fields))
                           .astype(np.int64))
                else:
                    ids = v.sample(r, b, float(self.traffic["zipf_a"]))
                yield (ids, np.zeros(b, np.float32)), {
                    "weights": np.zeros(b, np.float32), "bucket": b}

    def unique_per_batch(self, args: dict) -> dict:
        """{group: unique ids} of one recorded ``train_batch`` call."""
        n = len(np.unique(args["ids"]))
        return {g: n for g in self.cfg["groups"]}


def train_stream(cfg: dict, traffic: dict, seed: int) -> Stream:
    return Stream(cfg, traffic, seed)


def requests(cfg: dict, traffic: dict):
    """``draw(rng, size)``: one predict request's (size, fields) ids."""
    vocab = gen.Vocab(cfg["field_vocab"])
    a = float(traffic["zipf_a"])
    return lambda r, size: vocab.sample(r, int(size), a)


def collect(cl, cfg: dict, batches: list) -> dict:
    """Master rows and replica rows of every id the recorded batches
    touched, as the program holds them after the window."""
    ids = np.unique(np.concatenate([b[0].reshape(-1)
                                    for b in check.train_rows(batches)]))
    owner = cl.plan.master_shard(ids)
    sowner = cl.plan.slave_shard(ids)
    masters, reps = {}, {}
    for g, dim in cfg["groups"].items():
        out = {k: np.empty((len(ids), dim), np.float32)
               for k in ("z", "n", "w")}
        for m in cl.masters:
            sel = np.flatnonzero(owner == m.shard_id)
            t = m.tables[g]
            w, slots = t.read_rows(t.lookup(ids[sel]))
            out["w"][sel], out["z"][sel], out["n"][sel] = \
                w, slots["z"], slots["n"]
        masters[g] = out
        rep = []
        for rs in cl.replica_sets:
            for shard in rs.replicas:
                sel = np.flatnonzero(sowner == shard.shard_id)
                t = shard.tables[g]
                w, _ = t.read_rows(t.lookup(ids[sel]))
                rep.append((sel, w))
        reps[g] = rep
    return {"ids": ids, "masters": masters, "replicas": reps}


def judge(spec: dict, seed: int, st) -> dict:
    """The numbers compared with the float32 reference."""
    if not st.train:
        return check.serve_numbers(spec["cfg"], seed, st.sample)
    rows = check.train_rows(st.batches)
    ref = reference.TrainReference(spec["cfg"], seed)
    ref.replay(rows)
    return check.train_judged(spec, st, ref, rows)


def readings(spec: dict, seed: int, st) -> dict:
    """The program's, the control's and (train) the planted faults'
    numbers of one run."""
    if not st.train:
        return check.serve_readings(spec["cfg"], seed, st.sample)
    return check.train_readings(spec, seed, st)


def tiny(spec: dict) -> None:
    """Cut ``spec`` in place to a size the CPU runs in seconds (Pallas in
    interpret mode): a few hundred ids a field, a few hundred events a
    tick, short requests. Widths, layout and codec stay as
    configured."""
    cfg = spec["cfg"]
    cfg["field_vocab"] = list(TINY_VOCAB)
    cfg["sizing"]["ids_per_master"] = sum(TINY_VOCAB) // 4
    t = spec["traffic"]
    if t["kind"] == "train_stream":
        t.update(events_per_tick=256, warm_ticks=2)
    else:
        t.update(max_examples=64, warm_requests=8, check_requests=8)
        spec["cell"]["rate_per_s"] = 20.0
