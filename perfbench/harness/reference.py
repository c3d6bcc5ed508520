"""The plain reference of the cells: the window join of the click stream,
FM / LR logits in numpy, the weighted logistic loss's gradients,
FTRL-proximal row updates, and the int8 row codec of the sync stream. It
imports nothing of the program and starts from rows and events it
regenerates itself (``generate``).

``Arith`` sets the precision: ``float32`` is the reference the program
is held to; ``bfloat16`` rounds every intermediate result to bfloat16 and
is the control that has to come out as not correct.
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

from harness import generate as gen


class Arith:
    """Rounding applied after each operation."""

    def __init__(self, name: str = "float32"):
        assert name in ("float32", "bfloat16"), name
        self.name = name

    def __call__(self, a):
        a = np.asarray(a, np.float32)
        if self.name == "float32":
            return a
        return a.astype(ml_dtypes.bfloat16).astype(np.float32)


def logits(rows: dict, model: str, q: Arith) -> np.ndarray:
    """rows: {group: (B, F, D)} -> (B,) logits of an LR or FM model."""
    lin = q(q(rows["w"][..., 0]).sum(axis=1))
    if model == "lr":
        return lin
    v = q(rows["v"])
    s = q(v.sum(axis=1))                                   # (B, k)
    sq = q(q(np.square(v)).sum(axis=1))                    # (B, k)
    inter = q(q(np.float32(0.5) * q(q(np.square(s)) - sq)).sum(axis=-1))
    return q(lin + inter)


def sigmoid(x: np.ndarray, q: Arith) -> np.ndarray:
    return q(np.float32(1.0) / q(np.float32(1.0) + q(np.exp(-x))))


def row_grads(rows: dict, model: str, y: np.ndarray, wts: np.ndarray,
              q: Arith) -> dict:
    """Gradients of sum(w * logloss) / max(sum(w), 1e-9) by every row."""
    p = sigmoid(logits(rows, model, q), q)
    dlogit = q(q(wts * q(p - y)) / np.float32(max(float(wts.sum()), 1e-9)))
    out = {"w": np.broadcast_to(dlogit[:, None, None],
                                rows["w"].shape).astype(np.float32)}
    if model == "fm":
        v = q(rows["v"])
        s = q(v.sum(axis=1, keepdims=True))
        out["v"] = q(dlogit[:, None, None] * q(s - v))
    return out


def ftrl_update(z, n, g, opt: dict, q: Arith):
    """One FTRL-proximal step of rows (z, n) by gradient rows g."""
    w = q(gen.ftrl_w(z, n, opt))
    n2 = q(n + q(g * g))
    sigma = q(q(np.sqrt(n2) - np.sqrt(n)) / np.float32(opt["alpha"]))
    z2 = q(q(z + g) - q(sigma * w))
    return z2, n2, q(gen.ftrl_w(z2, n2, opt))


def join(events: list, window_s: float, tick_s: float) -> list:
    """The examples the stream's join owes, per tick of events offered:
    [(feature ids (n, F), labels (n,), emit times (n,))]. Exposures are
    offered at their tick's time ``t``; a click's feedback reaches the
    join at the first tick at or after ``t + delay``, and a window closes
    at the first tick at or after ``t + window_s``. A click whose feedback
    comes no later than its window's close is a positive, emitted at the
    feedback's tick; every other exposure is a negative, emitted at the
    close."""
    def tick_of(x):
        return np.ceil(np.asarray(x, np.float64) / tick_s - 1e-9) * tick_s

    out = []
    for t, ev in events:
        n = len(ev["feature_ids"])
        close = float(tick_of(t + window_s))
        labels = np.zeros(n, np.float32)
        emit = np.full(n, close)
        pos = np.flatnonzero(ev["labels"] > 0)
        fb = tick_of(t + ev["fb_delay"])
        hit = fb <= close
        labels[pos[hit]] = 1.0
        emit[pos[hit]] = fb[hit]
        out.append((ev["feature_ids"], labels, emit))
    return out


class TrainReference:
    """Replays the recorded train batches, in order, over regenerated
    master rows of every id they touch."""

    def __init__(self, cfg: dict, seed: int, arith: str = "float32"):
        self.cfg = cfg
        self.seed = seed
        self.q = Arith(arith)
        self.groups = cfg["groups"]
        self.opt = cfg["ftrl"]
        self.ids = np.empty(0, np.int64)
        self.state: dict = {}

    def initial(self, ids: np.ndarray) -> dict:
        """{group: (z, n, w)} as pre-seeded, for sorted unique ids."""
        out = {}
        for gi, (g, dim) in enumerate(self.groups.items()):
            z, n = gen.ftrl_state(ids, dim, self.seed, gi)
            out[g] = (self.q(z), self.q(n), self.q(gen.ftrl_w(z, n,
                                                             self.opt)))
        return out

    def replay(self, batches: list) -> None:
        """``batches``: [(ids (B, F), labels (B,), weights (B,))]."""
        self.ids = np.unique(np.concatenate(
            [b[0].reshape(-1) for b in batches])) if batches else \
            np.empty(0, np.int64)
        init = self.initial(self.ids)
        self.state = {g: [a.copy() for a in init[g]] for g in self.groups}
        model = self.cfg["model_type"]
        q = self.q
        for ids, y, wts in batches:
            b, f = ids.shape
            uniq, inv = np.unique(ids, return_inverse=True)
            pos = np.searchsorted(self.ids, uniq)
            rows = {g: self.state[g][2][pos][inv.reshape(-1)].reshape(
                b, f, dim) for g, dim in self.groups.items()}
            grads = row_grads(rows, model, np.asarray(y, np.float32),
                              np.asarray(wts, np.float32), q)
            for g, dim in self.groups.items():
                agg = np.zeros((len(uniq), dim), np.float32)
                np.add.at(agg, inv.reshape(-1), grads[g].reshape(-1, dim))
                z, n, w = self.state[g]
                z2, n2, w2 = ftrl_update(z[pos], n[pos], q(agg), self.opt, q)
                z[pos], n[pos], w[pos] = z2, n2, w2

    def rows(self, ids: np.ndarray) -> dict:
        """{group: {"z", "n", "w"}} of ``ids`` (all in the replayed set)."""
        pos = np.searchsorted(self.ids, ids)
        return {g: dict(zip(("z", "n", "w"), (a[pos] for a in st)))
                for g, st in self.state.items()}

    def replica_rows(self, ids: np.ndarray) -> dict:
        """{group: rows} a serving replica should hold after the sync."""
        pos = np.searchsorted(self.ids, ids)
        return {g: gen.int8_roundtrip(
            self.q(gen.ftrl_w(st[0][pos], st[1][pos], self.opt)))
            for g, st in self.state.items()}


def predict(cfg: dict, seed: int, ids: np.ndarray,
            arith: str = "float32") -> np.ndarray:
    """Predictions of a serving replica's rows (as pre-seeded) for a
    request's (B, F) ids."""
    q = Arith(arith)
    b, f = ids.shape
    flat = ids.reshape(-1)
    rows = {}
    for gi, (g, dim) in enumerate(cfg["groups"].items()):
        rows[g] = q(gen.serve_rows(flat, dim, seed, gi,
                                   cfg["ftrl"])).reshape(b, f, dim)
    return sigmoid(logits(rows, cfg["model_type"], q), q)
