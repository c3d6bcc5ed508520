"""The comparison that decides ``correct``. ``verdict`` holds a run's
numbers to the limits its cell's file sets, for every family; the rest
is family ``ctr_ftrl``'s numbers, compared with its plain reference.

Its train cells compare, for every id the recorded batches touched:

- ``rows_err``: the master rows (FTRL z, n, w) after the window, the
  largest gap to the reference in any group and column, over the largest
  reference magnitude of that group and column;
- ``replica_miss_pct``: the serving replicas' rows after the int8 sync,
  the share (%) of elements more than half an int8 step (of the
  reference row's scale) from the reference's decode;
- ``join_wrong``: rows the program trained (ids, label, weight) that the
  reference's join of the generated stream does not owe, or trained more
  often than owed;
- ``join_owed``: rows the reference's join owed by the last tick that
  the program has not trained.

The reference replays the program's train batches in the program's
order; the join numbers hold their rows to the generated stream.

Its serve cells compare ``pred_err``: the largest gap between a returned
prediction and the reference's, over a seeded sample of the window's
requests that holds the longest one.
"""

from __future__ import annotations

import numpy as np

from harness import generate as gen
from harness import reference as ref_mod


def _rows_err(got: dict, want: dict) -> float:
    worst = 0.0
    for g, cols in want.items():
        for c, w in cols.items():
            scale = float(np.abs(w).max(initial=0.0))
            if scale == 0.0:
                continue
            gap = float(np.abs(np.asarray(got[g][c], np.float64)
                               - w).max(initial=0.0))
            worst = max(worst, gap / scale)
    return worst


def _replica_miss_pct(got: dict, want: dict) -> float:
    bad = tot = 0
    for g, w in want.items():
        step = np.abs(w).max(axis=-1, keepdims=True) / 127.0
        for sel, rows in got[g]:
            gap = np.abs(np.asarray(rows, np.float64) - w[sel])
            bad += int((gap > 0.5 * step[sel]).sum())
            tot += gap.size
    return 100.0 * bad / max(tot, 1)


def row_keys(ids: np.ndarray, labels: np.ndarray,
             weights: np.ndarray) -> np.ndarray:
    """A 64-bit key of each example row: its ids, label and weight."""
    h = np.full(len(ids), gen.key(0, 6), np.uint64)
    for col in np.asarray(ids, np.int64).T:
        h = gen.mix64(h ^ col.astype(np.uint64))
    for v in (labels, weights):
        h = gen.mix64(h ^ np.asarray(v, np.float32).view(np.uint32)
                      .astype(np.uint64))
    return h


def _excess(a: np.ndarray, b: np.ndarray) -> int:
    """How many of the keys ``a`` are not matched by one of ``b``
    (multisets)."""
    ua, ca = np.unique(a, return_counts=True)
    ub, cb = np.unique(b, return_counts=True)
    have = np.zeros_like(ca)
    if len(ub):
        at = np.minimum(np.searchsorted(ub, ua), len(ub) - 1)
        hit = ub[at] == ua
        have[hit] = cb[at][hit]
    return int(np.maximum(ca - have, 0).sum())


def join_numbers(events: list, batches: list, window_s: float,
                 tick_s: float) -> dict:
    """``events``: [(t, the tick's generated events)] as offered;
    ``batches``: the join's train batches, [(ids, labels, weights)]."""
    none = np.empty(0, np.uint64)
    keys, emit = [none], [np.empty(0)]
    for f, y, e in ref_mod.join(events, window_s, tick_s):
        keys.append(row_keys(f, y, np.ones(len(y), np.float32)))
        emit.append(e)
    keys, emit = np.concatenate(keys), np.concatenate(emit)
    end = events[-1][0] if events else 0.0
    got = np.concatenate([none] + [row_keys(*b) for b in batches])
    return {"join_wrong": _excess(got, keys),
            "join_owed": _excess(keys[emit <= end + 1e-9], got)}


def train_numbers(out: dict, ref: "ref_mod.TrainReference") -> dict:
    """``out``: {"ids", "masters": {g: {z, n, w}}, "replicas": {g:
    [(index into ids, rows), ...] per replica}} — the program's rows, or
    a stand-in's in the same form."""
    ids = out["ids"]
    return {"rows_err": _rows_err(out["masters"], ref.rows(ids)),
            "replica_miss_pct": _replica_miss_pct(out["replicas"],
                                                  ref.replica_rows(ids))}


def reference_as_output(ref: "ref_mod.TrainReference", ids) -> dict:
    """A reference's rows in the program's output form (for the control
    and the planted faults)."""
    idx = np.arange(len(ids))
    return {"ids": ids, "masters": ref.rows(ids),
            "replicas": {g: [(idx, r)] for g, r in
                         ref.replica_rows(ids).items()}}


def unchanged_output(ref: "ref_mod.TrainReference", ids) -> dict:
    """The fault of a step that returns its state unchanged: every row
    as pre-seeded."""
    init = ref.initial(ids)
    idx = np.arange(len(ids))
    return {"ids": ids,
            "masters": {g: dict(zip(("z", "n", "w"), st))
                        for g, st in init.items()},
            "replicas": {g: [(idx, gen.int8_roundtrip(st[2]))]
                         for g, st in init.items()}}


def serve_numbers(cfg: dict, seed: int, sample: list,
                  arith: str = "float32", against: str = "float32") -> dict:
    """``sample``: [(ids (B, F), predictions (B,))]. With ``arith`` set
    below ``against``, the predictions are replaced by the reference's in
    that precision (the control)."""
    worst = 0.0
    for ids, p in sample:
        want = ref_mod.predict(cfg, seed, ids, against)
        got = p if arith == against else ref_mod.predict(cfg, seed, ids,
                                                         arith)
        worst = max(worst, float(np.abs(np.asarray(got, np.float64)
                                        - want).max(initial=0.0)))
    return {"pred_err": worst}


def verdict(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}). A number that is not finite,
    or has no limit, fails."""
    shown = {}
    ok = True
    for k, v in numbers.items():
        lim = limits.get(k)
        finite = bool(np.isfinite(v))
        shown[k] = {"value": float(v) if finite else None, "limit": lim}
        if lim is None or not finite or v > lim:
            ok = False
    return ok, shown


def train_rows(records: list) -> list:
    """The ``train_batch`` calls recorded at the training plane's entry
    (each a dict of the call's arguments) as (ids, labels, weights)."""
    rows = []
    for a in records:
        ids = np.asarray(a["ids"], np.int64)
        w = a["weights"]
        rows.append((ids, np.asarray(a["y"], np.float32),
                     np.ones(len(ids), np.float32) if w is None else
                     np.asarray(w, np.float32)))
    return rows


def train_judged(spec: dict, st, ref: "ref_mod.TrainReference",
                 batches: list, out: dict = None) -> dict:
    """A train run's numbers: its rows (or ``out``, a stand-in's) against
    ``ref``, and the join's part of ``batches`` (``train_rows``) against
    the generated stream."""
    return {**train_numbers(st.out if out is None else out, ref),
            **join_numbers(st.events, batches[st.stream_from:],
                           spec["cfg"]["cluster"]["join_window_s"],
                           spec["traffic"]["tick_s"])}


def half_batches(batches: list) -> list:
    """The fault of half of every batch left out, the mean taken over the
    rest."""
    return [tuple(a[:max(1, len(a) // 2)] for a in b) for b in batches]


def train_readings(spec: dict, seed: int, st) -> dict:
    """Every reading a train cell's limits are set from, on one run: the
    program against the float32 reference, the control (the reference in
    bfloat16, in the program's place), and two planted faults in the
    reference's place: a step that leaves the state unchanged, and half
    of every batch left out."""
    cfg = spec["cfg"]
    rows = train_rows(st.batches)
    ref = ref_mod.TrainReference(cfg, seed)
    ref.replay(rows)
    ids = st.out["ids"]
    ctrl = ref_mod.TrainReference(cfg, seed, "bfloat16")
    ctrl.replay(rows)
    halved = half_batches(rows)
    half = ref_mod.TrainReference(cfg, seed)
    half.replay(halved)
    return {"program": train_judged(spec, st, ref, rows),
            "control": train_judged(spec, st, ref, rows,
                                    reference_as_output(ctrl, ids)),
            "unchanged": train_judged(spec, st, ref, rows,
                                      unchanged_output(ref, ids)),
            "half_batch": train_judged(spec, st, ref, halved,
                                       reference_as_output(half, ids))}


def serve_readings(cfg: dict, seed: int, sample: list) -> dict:
    """The program's and the control's readings of a serve cell."""
    return {"program": serve_numbers(cfg, seed, sample),
            "control": serve_numbers(cfg, seed, sample, arith="bfloat16")}
