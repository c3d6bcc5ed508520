"""PS hot-path benchmark: dict-loop baseline vs vectorized open-addressing
vs Pallas-interpret gather, measured as rows/sec through batched
``_ensure`` + gather (the per-minibatch PS resolution path) and through
the full FTRL push (gather → update → scatter).

The dict-loop baseline is the seed implementation this PR replaced
(per-row ``dict.get`` in Python, fancy-indexed row copies); it is kept
here verbatim as the reference point for the recorded speedup. The seed's
full push path additionally ran the FTRL update through per-call eager
JAX dispatch — ``seed_push`` reproduces that too.

Timing uses best-of-``--reps`` over a fixed batch set (the ``timeit``
convention: the minimum measures the code, not scheduler/VM noise).

Run:  PYTHONPATH=src python benchmarks/ps_hot_path.py
      [--rows 1000000 --batch 4096 --dim 16 --reps 9 --quick]
Emits BENCH_ps_hot_path.json (or --out PATH).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np


# ---------------------------------------------------------------------------
# Baseline: the seed's dict-based SparseTable row resolution (verbatim
# semantics: per-id Python loop over a dict + free list, fancy+copy gather).
# ---------------------------------------------------------------------------
class DictLoopTable:
    def __init__(self, dim: int, slot_names: tuple = (),
                 init_capacity: int = 1024):
        self.dim = dim
        self._slot_of: dict[int, int] = {}
        self._id_of: list[int] = []
        self._free: list[int] = []
        self._w = np.zeros((init_capacity, dim), dtype=np.float32)
        self._slots = {n: np.zeros((init_capacity, dim), np.float32)
                       for n in slot_names}

    def _grow(self, need: int) -> None:
        cap = self._w.shape[0]
        new_cap = max(need, cap * 2)
        def grow(a):
            out = np.zeros((new_cap,) + a.shape[1:], dtype=a.dtype)
            out[:cap] = a
            return out
        self._w = grow(self._w)
        self._slots = {n: grow(a) for n, a in self._slots.items()}

    def _ensure(self, ids: np.ndarray) -> np.ndarray:
        slots = np.empty(len(ids), dtype=np.int64)
        for i, rid in enumerate(ids.tolist()):
            s = self._slot_of.get(rid)
            if s is None:
                if self._free:
                    s = self._free.pop()
                else:
                    s = len(self._id_of)
                    self._id_of.append(-1)
                    if s >= self._w.shape[0]:
                        self._grow(s + 1)
                self._slot_of[rid] = s
                self._id_of[s] = rid
                self._w[s] = 0.0
                for a in self._slots.values():
                    a[s] = 0.0
            slots[i] = s
        return slots

    def gather(self, ids: np.ndarray):
        sl = self._ensure(ids)
        return self._w[sl].copy(), {n: a[sl].copy()
                                    for n, a in self._slots.items()}

    def scatter(self, ids: np.ndarray, w: np.ndarray, slots: dict) -> None:
        sl = self._ensure(ids)
        self._w[sl] = w
        for n, v in slots.items():
            self._slots[n][sl] = v


def best_of(fn, batches, reps: int) -> float:
    """Minimum per-batch seconds over ``reps`` sweeps (timeit convention)."""
    fn(batches[0])                                    # warm-up
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for b in batches:
            fn(b)
        best = min(best, (time.perf_counter() - t0) / len(batches))
    return best


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=1_000_000)
    ap.add_argument("--batch", type=int, default=4096)
    ap.add_argument("--dim", type=int, default=1,
                    help="row dim; default 1 = the paper's flagship "
                         "LR-on-FTRL CTR config (weips_ctr.LR_FTRL, "
                         "groups {'w': 1}); use 8/16 for FM/DNN embeddings")
    ap.add_argument("--reps", type=int, default=11)
    ap.add_argument("--hot-batches", type=int, default=10)
    ap.add_argument("--sweep-slots", type=int, nargs="*", default=None,
                    help="map capacities for the HBM/windowed-DMA sweep "
                         "(default 1M..16M; --quick defaults 1M,4M)")
    ap.add_argument("--sweep-batch", type=int, default=512)
    ap.add_argument("--sweep-live", type=int, default=65536,
                    help="live rows per sweep table (map capacity is the "
                         "swept quantity; load stays far below growth)")
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--out", default="BENCH_ps_hot_path.json")
    args = ap.parse_args()
    if args.quick:
        args.rows, args.reps = min(args.rows, 100_000), 3
    if args.sweep_slots is None:
        args.sweep_slots = [1 << 20, 1 << 22] if args.quick else \
            [1 << 20, 1 << 21, 1 << 22, 1 << 23, 1 << 24]

    from repro.core.ps import MasterShard, SparseTable
    from repro.optim import get_optimizer

    rng = np.random.default_rng(0)
    # unique random int64 ids over a huge space (realistic hashed features)
    ids = rng.choice(1 << 40, size=args.rows, replace=False).astype(np.int64)
    hot = [rng.choice(ids, size=args.batch).astype(np.int64)
           for _ in range(args.hot_batches)]

    results: dict[str, dict] = {}

    # -- populate (cold insert) --------------------------------------------
    dt = DictLoopTable(args.dim, init_capacity=args.rows)
    t0 = time.perf_counter()
    for i in range(0, args.rows, args.batch):
        dt._ensure(ids[i:i + args.batch])
    dict_pop = time.perf_counter() - t0
    vt = SparseTable(args.dim, init_capacity=args.rows)
    t0 = time.perf_counter()
    for i in range(0, args.rows, args.batch):
        vt.ensure(ids[i:i + args.batch])
    vec_pop = time.perf_counter() - t0

    # -- hot ensure + gather (the acceptance leg) --------------------------
    d_s = best_of(dt.gather, hot, args.reps)
    v_s = best_of(lambda b: vt.gather(b, create=True), hot, args.reps)
    results["dict_loop"] = {
        "populate_rows_per_sec": args.rows / dict_pop,
        "ensure_gather_rows_per_sec": args.batch / d_s,
        "us_per_batch": d_s * 1e6}
    results["vectorized"] = {
        "populate_rows_per_sec": args.rows / vec_pop,
        "ensure_gather_rows_per_sec": args.batch / v_s,
        "us_per_batch": v_s * 1e6}

    # -- full FTRL push: seed path (dict + eager-JAX) vs apply_batch -------
    opt = get_optimizer("ftrl")
    sdt = DictLoopTable(args.dim, ("n", "z"), init_capacity=args.rows)
    for i in range(0, args.rows, args.batch):
        sdt._ensure(ids[i:i + args.batch])
    grads = np.ones((args.batch, args.dim), np.float32)

    import jax.numpy as jnp

    def seed_push(b):                 # the seed MasterShard.push_grad body
        w, slots = sdt.gather(b)
        new_w, new_slots = opt.update(
            jnp.asarray(w), {k: jnp.asarray(v) for k, v in slots.items()},
            jnp.asarray(grads[:len(b)]), 0)
        sdt.scatter(b, np.asarray(new_w),
                    {k: np.asarray(v) for k, v in new_slots.items()})

    m = MasterShard(0, {"w": args.dim}, opt)
    for i in range(0, args.rows, args.batch):
        m.tables["w"].ensure(ids[i:i + args.batch])
    s_push = best_of(seed_push, hot, max(1, args.reps // 3))
    v_push = best_of(lambda b: m.apply_batch("w", b, grads[:len(b)]),
                     hot, args.reps)
    results["ftrl_push"] = {
        "seed_rows_per_sec": args.batch / s_push,
        "apply_batch_rows_per_sec": args.batch / v_push,
        "speedup": s_push / v_push}


    # -- map-size sweep: fused lookup + FTRL apply vs map capacity ---------
    # The point: past VMEM_SLOT_BOUND (1M slots) the probe's key table
    # cannot stream into VMEM — the windowed-DMA HBM kernel takes over
    # (placement flips to "hbm") and the fused paths keep running, with
    # bit-equality gates against the host-authoritative arrays at every
    # size. Interpret mode on CPU; the Mosaic path is compiled by
    # tests/test_tpu_compile.py and run on a chip by chip_smoke.py.
    from repro.kernels.hashmap_probe import VMEM_SLOT_BOUND
    from repro.optim.optimizers import FTRL

    sweep: dict[str, dict] = {}
    sw_reps = max(2, args.reps // 3)
    for slots in args.sweep_slots:
        st = SparseTable(args.dim, ("n", "z"), init_capacity=slots,
                         backend="pallas")
        n_live = min(args.sweep_live, slots // 8)   # stay below 25% growth
        live = np.unique(rng.integers(
            1, 1 << 62, size=n_live + 1024).astype(np.int64))[:n_live]
        st.ensure(live)
        assert st._map.capacity == slots, (st._map.capacity, slots)
        q_live = rng.choice(live, size=args.sweep_batch, replace=False)
        q_mixed = np.concatenate([
            q_live[:args.sweep_batch // 2],
            rng.integers(1 << 62, (1 << 62) + (1 << 40),
                         args.sweep_batch // 2).astype(np.int64)])
        grads = rng.normal(size=(args.sweep_batch, args.dim)) \
            .astype(np.float32)

        # bit-equality gates BEFORE timing (timing mutates rows)
        dev = np.asarray(st._gather_device(q_mixed))
        sl_h = st.lookup(q_mixed)
        ok = sl_h >= 0
        host = np.where(ok[:, None],
                        st._w[np.where(ok, sl_h, 0)].astype(np.float32),
                        np.float32(0.0))
        lookup_equal = bool((dev == host).all())

        # FTRL gate: the fused chain (probe→gather→FTRL→scatter over the
        # HBM/VMEM mirror) must be BIT-EQUAL to the same FTRL kernel run
        # standalone on host-gathered rows — anything the probe placement
        # or scatter got wrong shows up here. The numpy oracle differs in
        # float op order (~1 ulp on w), so it gates at allclose with the
        # max deviation recorded.
        opt = FTRL()
        sl = st.lookup(q_live)
        w0, slots0 = st.read_rows(sl)
        ref_w, ref_slots = opt.update_rows(w0, slots0, grads, 0,
                                           backend="pallas")
        np_w, np_slots = opt.update_rows(w0, slots0, grads, 0,
                                         backend="numpy")
        st.fused_ftrl_update(q_live, sl, grads, alpha=opt.alpha,
                             beta=opt.beta, l1=opt.l1, l2=opt.l2)
        w1, slots1 = st.read_rows(sl)
        ftrl_equal = bool(
            (w1.astype(np.float32) == ref_w.astype(np.float32)).all()
            and all((slots1[k] == ref_slots[k]).all() for k in slots1))
        ftrl_np_dev = float(max(
            np.abs(w1.astype(np.float32) - np_w.astype(np.float32)).max(),
            max(np.abs(slots1[k] - np_slots[k]).max() for k in slots1)))
        ftrl_np_close = bool(np.allclose(w1, np_w, rtol=1e-5, atol=1e-6))

        lk_batches = [q_mixed, np.roll(q_mixed, 7)]
        lk_s = best_of(st._gather_device, lk_batches, sw_reps)
        up_s = best_of(
            lambda b: st.fused_ftrl_update(
                q_live, sl, grads, alpha=opt.alpha, beta=opt.beta,
                l1=opt.l1, l2=opt.l2),
            [q_live], sw_reps)
        sweep[str(slots)] = {
            "slots": slots,
            "live_rows": n_live,
            "placement": st._dev.placement,
            "past_vmem_bound": slots > VMEM_SLOT_BOUND,
            "lookup_us_per_batch": lk_s * 1e6,
            "lookup_rows_per_sec": args.sweep_batch / lk_s,
            "ftrl_us_per_batch": up_s * 1e6,
            "ftrl_rows_per_sec": args.sweep_batch / up_s,
            "lookup_bit_equal_host": lookup_equal,
            "ftrl_bit_equal_kernel": ftrl_equal,
            "ftrl_allclose_numpy": ftrl_np_close,
            "ftrl_numpy_max_abs_dev": ftrl_np_dev,
        }
        del st
    results["map_size_sweep"] = {
        "batch": args.sweep_batch, "live_rows": args.sweep_live,
        "vmem_slot_bound": VMEM_SLOT_BOUND,
        "sizes": sweep,
        "note": "interpret mode on CPU; placement flips vmem->hbm past "
                "the bound — the windowed-DMA kernel is what keeps "
                ">2M-slot maps device-resident at all"}

    speedup = d_s / v_s
    out = {
        "config": {"rows": args.rows, "batch": args.batch, "dim": args.dim,
                   "reps": args.reps},
        "results": results,
        "speedup_vectorized_over_dict": speedup,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(json.dumps(out, indent=2))
    print(f"\nvectorized ensure+gather speedup over dict loop: "
          f"{speedup:.1f}x; full FTRL push speedup: "
          f"{results['ftrl_push']['speedup']:.1f}x")


if __name__ == "__main__":
    main()
