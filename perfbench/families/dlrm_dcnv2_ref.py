"""The plain reference of family ``dlrm_dcnv2``: DLRM-DCNv2's forward
pass, weighted logistic loss and gradients in ``jax.numpy``, float32,
under ``jax.default_matmul_precision("highest")``, with no kernels,
routing or batching; the sparse rows' FTRL-proximal step and the int8 row
codec are ``harness/reference.py``'s and ``harness/generate.py``'s. It
imports nothing of the program and starts from rows, a tower and events
it regenerates itself.

The model (arXiv 1906.00091; the cross network of arXiv 2008.13535, as
MLPerf Training's ``recommendation_v2/torchrec_dlrm`` builds it): the
dense features through a bottom MLP (ReLU after every layer) to the
embedding width; each field's multi-hot ids' rows summed; x0 = [bottom
output, pooled rows] flattened; ``x_{l+1} = x0 * (U_l (V_l x_l) + b_l) +
x_l``; a top MLP (ReLU after every layer but the last) to one logit.
Departures from the published model, each in the program too:

- the sparse rows train by FTRL-proximal (WeiPS's online rule), not the
  reference's row-wise Adagrad; the tower by plain Adagrad whose sums
  start at ``initial_accumulator``;
- the loss is weighted (the join's weights; padding weighs 0) and the
  data is the benchmark's generated stream, not Criteo 1TB;
- the weights start from a seeded normal law of variance 1 / fan-in,
  biases at 0 (the reference initializes by layer kind);
- the vocabulary is cut (the configuration's ``reduced``).

``Reference.replay`` takes the recorded ``train_batch`` calls in order; a
call whose weights are all 0 (set-up's warm batches) has a zero gradient
everywhere, so FTRL's and Adagrad's steps leave every row and the tower
exactly as they were, and it is passed over. A call's unique rows are
gathered by its inverse, pooled, and the gradients by the gathered rows
are summed into the unique rows on the device; its examples are padded
with weight 0 to the call's bucket and its unique rows with zeros to a
multiple of ``UNIQUE_STEP``, so a few programs serve every call.
"""

from __future__ import annotations

import functools
import os
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

from harness import generate as gen
from harness import reference as base

BLOCK = 1 << 15             # ids a thread generates or updates at once
UNIQUE_STEP = 1 << 13       # a call's unique rows are padded to a multiple
MASK64 = (1 << 64) - 1


def _threads():
    return ThreadPoolExecutor(max_workers=min(16, os.cpu_count() or 1))


def _blocks(n: int):
    return [(lo, min(n, lo + BLOCK)) for lo in range(0, n, BLOCK)]


# --------------------------------------------------------------------------
# pre-seeded state
# --------------------------------------------------------------------------

def _state_block(ids: np.ndarray, dim: int, seed: int, group: int):
    """``generate.ftrl_state`` of ``ids``, every element pair of a row from
    one hash at once: the same numbers."""
    h0 = gen.mix64(np.ascontiguousarray(ids, np.int64).view(np.uint64)
                   ^ gen.key(seed, 2, group))[:, None]
    pairs = (dim + 1) // 2
    salt = np.array([((j + 1) * int(gen._GOLD)) & MASK64
                     for j in range(pairs)], np.uint64)
    h = gen.mix64(h0 + salt[None, :])
    m16 = np.uint64(0xFFFF)
    z = np.empty((len(ids), 2 * pairs), np.float32)
    n = np.empty((len(ids), 2 * pairs), np.float32)
    for col, shift in ((0, 48), (1, 16)):
        z[:, col::2] = ((h >> np.uint64(shift)) & m16).astype(np.int32) \
            - (1 << 15)
        n[:, col::2] = ((h >> np.uint64(shift - 16)) & m16) >> np.uint64(1)
    z, n = z[:, :dim], n[:, :dim]
    z *= np.float32(2.0 ** -12)
    n *= np.float32(2.0 ** -12)
    n += np.float32(0.5)
    return z, n


def initial_rows(ids: np.ndarray, dim: int, seed: int, group: int,
                 opt: dict, *, serve: bool = False):
    """Pre-seeded (z, n, w) of ``ids`` (FTRL's state and weight of
    ``generate.ftrl_state``), or with ``serve`` the int8-coded w a serving
    replica holds; made in blocks, on threads."""
    n_ids = len(ids)
    if serve:
        out = (np.empty((n_ids, dim), np.float32),)
    else:
        out = tuple(np.empty((n_ids, dim), np.float32) for _ in range(3))

    def one(lo_hi):
        lo, hi = lo_hi
        z, n = _state_block(ids[lo:hi], dim, seed, group)
        w = gen.ftrl_w(z, n, opt)
        if serve:
            out[0][lo:hi] = gen.int8_roundtrip(w)
        else:
            out[0][lo:hi], out[1][lo:hi], out[2][lo:hi] = z, n, w

    with _threads() as ex:
        list(ex.map(one, _blocks(n_ids)))
    return out


def tower_shapes(cfg: dict) -> dict:
    """The tower's tensors, ``(in, out)`` weights: ``bottom/w{i}``,
    ``bottom/b{i}``; ``cross/v{l}`` (d, rank), ``cross/u{l}`` (rank, d),
    ``cross/b{l}`` (d,), d = (fields + 1) * embed_dim; ``top/w{i}``,
    ``top/b{i}``."""
    d = (len(cfg["multi_hot"]) + 1) * cfg["embed_dim"]
    out = {}
    sizes = [cfg["dense_features"]] + list(cfg["bottom_mlp"])
    for i in range(len(sizes) - 1):
        out[f"bottom/w{i}"] = (sizes[i], sizes[i + 1])
        out[f"bottom/b{i}"] = (sizes[i + 1],)
    for i in range(cfg["dcn_layers"]):
        out[f"cross/v{i}"] = (d, cfg["dcn_rank"])
        out[f"cross/u{i}"] = (cfg["dcn_rank"], d)
        out[f"cross/b{i}"] = (d,)
    sizes = [d] + list(cfg["top_mlp"])
    for i in range(len(sizes) - 1):
        out[f"top/w{i}"] = (sizes[i], sizes[i + 1])
        out[f"top/b{i}"] = (sizes[i + 1],)
    return out


def initial_tower(cfg: dict, seed: int) -> dict:
    """The seeded starting tower: weights normal of variance 1 / fan-in,
    biases 0, float32."""
    out = {}
    for i, (name, shape) in enumerate(tower_shapes(cfg).items()):
        if len(shape) == 1:
            out[name] = np.zeros(shape, np.float32)
        else:
            r = gen.rng(seed, 100 + i)
            out[name] = r.standard_normal(shape, np.float32) \
                * np.float32(shape[0] ** -0.5)
    return out


def int8_rows(t: np.ndarray) -> np.ndarray:
    """A dense tensor as the sync delivers it: int8 by its rows (a
    vector is one row)."""
    rows = t.reshape(-1, t.shape[-1]) if t.ndim > 1 else t.reshape(1, -1)
    return gen.int8_roundtrip(rows).reshape(t.shape)


# --------------------------------------------------------------------------
# the model
# --------------------------------------------------------------------------

def matmul(a, b, mode: str):
    """``highest``: float32 (the reference); ``bfloat16``: inputs and
    result in bfloat16 (the control); ``one_pass``: inputs rounded to
    bfloat16, result float32 — what a TPU's default precision does to a
    float32 matmul (a planted fault)."""
    if mode == "highest":
        return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)
    lo = (a.astype(jnp.bfloat16), b.astype(jnp.bfloat16))
    if mode == "bfloat16":
        return jnp.matmul(*lo).astype(jnp.float32)
    assert mode == "one_pass", mode
    return jnp.matmul(*lo, preferred_element_type=jnp.float32)


def pool(gathered, sizes, how: str):
    """(B, F, D): each field's slots of ``gathered`` (B, S, D) summed
    (``sum``, the model) or averaged (``mean``, a planted fault)."""
    out, lo = [], 0
    for n in sizes:
        s = gathered[:, lo:lo + n].sum(axis=1)
        out.append(s / n if how == "mean" else s)
        lo += n
    return jnp.stack(out, axis=1)


def logits(pooled, tower, x, mode: str):
    n_bottom = sum(1 for k in tower if k.startswith("bottom/w"))
    n_cross = sum(1 for k in tower if k.startswith("cross/v"))
    n_top = sum(1 for k in tower if k.startswith("top/w"))
    h = x
    for i in range(n_bottom):
        h = jnp.maximum(matmul(h, tower[f"bottom/w{i}"], mode)
                        + tower[f"bottom/b{i}"], 0.0)
    x0 = jnp.concatenate([h[:, None, :], pooled], axis=1)
    x0 = x0.reshape(x0.shape[0], -1)
    xl = x0
    for i in range(n_cross):
        low = matmul(xl, tower[f"cross/v{i}"], mode)
        xl = x0 * (matmul(low, tower[f"cross/u{i}"], mode)
                   + tower[f"cross/b{i}"]) + xl
    h = xl
    for i in range(n_top):
        h = matmul(h, tower[f"top/w{i}"], mode) + tower[f"top/b{i}"]
        if i < n_top - 1:
            h = jnp.maximum(h, 0.0)
    return h[:, 0]


def loss(gathered, tower, x, y, w, sizes, mode, how):
    """sum(w * logloss) / max(sum(w), 1e-9)."""
    z = logits(pool(gathered, sizes, how), tower, x, mode)
    per = jnp.maximum(z, 0.0) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z)))
    return jnp.sum(w * per) / jnp.maximum(jnp.sum(w), 1e-9)


@jax.jit
def ref_gather(urows, inv):
    """Each slot's row: ``urows[inv]``, ``(B, S, D)``."""
    return urows[inv]


@functools.partial(jax.jit, static_argnames=("sizes", "mode", "how"))
def ref_grads(gathered, tower, x, y, w, *, sizes, mode, how):
    """Gradients of ``loss`` by the gathered rows and by the tower."""
    return jax.grad(loss, argnums=(0, 1))(gathered, tower, x, y, w, sizes,
                                          mode, how)


@functools.partial(jax.jit, static_argnames=("rows",))
def ref_segment_sum(g_rows, order, flat, *, rows):
    """Each slot's row gradient summed into the unique row it read:
    ``order`` sorts the slots by that row, ``flat`` the rows so sorted."""
    d = g_rows.shape[-1]
    return jnp.zeros((rows, d), g_rows.dtype).at[flat].add(
        g_rows.reshape(-1, d)[order], indices_are_sorted=True)


@jax.jit
def ref_adagrad(tower, acc, grads, lr, eps):
    """Adagrad: acc += g^2; p -= lr * g / (sqrt(acc) + eps)."""
    acc = jax.tree.map(lambda a, g: a + g * g, acc, grads)
    tower = jax.tree.map(lambda p, g, a: p - lr * g / (jnp.sqrt(a) + eps),
                         tower, grads, acc)
    return tower, acc


# --------------------------------------------------------------------------
# replay
# --------------------------------------------------------------------------

def calls_of(records: list) -> list:
    """The recorded ``train_batch`` calls as (ids (B, S), labels, weights,
    dense features, bucket)."""
    out = []
    for a in records:
        ids = np.asarray(a["ids"], np.int64)
        w = a["weights"]
        out.append((ids, np.asarray(a["y"], np.float32),
                    np.ones(len(ids), np.float32) if w is None else
                    np.asarray(w, np.float32),
                    np.asarray(a["dense_x"], np.float32), a["bucket"]))
    return out


class Reference:
    """Replays recorded train calls over regenerated state. ``mode`` is
    the matmul precision (``matmul``), ``how`` the pooling (``pool``)."""

    def __init__(self, cfg: dict, seed: int, *, mode: str = "highest",
                 how: str = "sum"):
        self.cfg = cfg
        self.seed = seed
        self.mode = mode
        self.how = how
        self.opt = cfg["ftrl"]
        self.dim = cfg["embed_dim"]
        self.sizes = tuple(int(n) for n in cfg["multi_hot"])
        self.ids = np.empty(0, np.int64)
        self.state = None
        self.tower = None

    def replay(self, calls: list) -> None:
        """``calls``: ``calls_of`` the recorded calls, in order."""
        self.ids = np.unique(np.concatenate([c[0].reshape(-1)
                                             for c in calls])) \
            if calls else np.empty(0, np.int64)
        self.state = list(initial_rows(self.ids, self.dim, self.seed, 0,
                                       self.opt))
        ad = self.cfg["adagrad"]
        with jax.default_matmul_precision("highest"):
            tower = {k: jnp.asarray(v) for k, v in
                     initial_tower(self.cfg, self.seed).items()}
            acc = {k: jnp.full(v.shape, ad["initial_accumulator"],
                               jnp.float32) for k, v in tower.items()}
            for ids, y, w, x, bucket in calls:
                if not w.any():
                    continue
                tower, acc = self._step(ids, y, w, x, bucket, tower, acc,
                                        ad)
        self.tower = {k: np.asarray(v) for k, v in tower.items()}

    def _step(self, ids, y, w, x, bucket, tower, acc, ad):
        b, s = ids.shape
        nb = max(b, bucket or b)
        uniq, inv = np.unique(ids, return_inverse=True)
        pos = np.searchsorted(self.ids, uniq)
        z, n, wt = self.state
        # unique rows padded to a multiple of UNIQUE_STEP, so a few
        # programs serve every batch
        rows = -(-len(uniq) // UNIQUE_STEP) * UNIQUE_STEP
        urows = np.zeros((rows, self.dim), np.float32)
        urows[:len(uniq)] = wt[pos]
        inv_p = np.zeros((nb, s), np.int32)
        inv_p[:b] = inv.reshape(b, s)

        def pad(a):
            out = np.zeros((nb,) + a.shape[1:], np.float32)
            out[:b] = a
            return out

        order = np.argsort(inv_p.reshape(-1), kind="stable").astype(
            np.int32)
        g_rows, g_t = ref_grads(ref_gather(urows, inv_p), tower, pad(x),
                                pad(y), pad(w), sizes=self.sizes,
                                mode=self.mode, how=self.how)
        g = np.asarray(ref_segment_sum(g_rows, order, inv_p.reshape(-1)[order],
                                       rows=rows))[:len(uniq)]
        q = base.Arith("float32")

        def one(lo_hi):
            lo, hi = lo_hi
            p = pos[lo:hi]
            z2, n2, w2 = base.ftrl_update(z[p], n[p], g[lo:hi], self.opt, q)
            z[p], n[p], wt[p] = z2, n2, w2

        with _threads() as ex:
            list(ex.map(one, _blocks(len(uniq))))
        return ref_adagrad(tower, acc, g_t, np.float32(ad["lr"]),
                           np.float32(ad["eps"]))

    def rows(self, ids: np.ndarray) -> dict:
        """{"emb": {"z", "n", "w"}} of ``ids`` (all in the replayed set)."""
        pos = np.searchsorted(self.ids, ids)
        return {"emb": dict(zip(("z", "n", "w"),
                                (a[pos] for a in self.state)))}

    def replica_rows(self, ids: np.ndarray) -> dict:
        """{"emb": rows} a serving replica should hold after the sync."""
        pos = np.searchsorted(self.ids, ids)
        z, n = self.state[0][pos], self.state[1][pos]
        return {"emb": gen.int8_roundtrip(gen.ftrl_w(z, n, self.opt))}
