"""Everything a cell feeds the program, made from ``--seed`` by the
benchmark's own code: feature ids, click labels and feedback times,
predict requests and their arrival schedule, and the pre-seeded table
rows. The reference regenerates the rows it needs from the same
functions, so nothing it compares with comes from the program.

Ids. Each field has its own slice of the id space (``Vocab``); within a
field, rank ``r`` (0 = hottest) maps to an id through a fixed affine
permutation of the field's slice, and ranks follow a bounded power law
(a Zipf law of exponent ``a``, continuous approximation, cut at the
field's vocabulary). The permutation is the same for
every seed, so the hot ids, and the shards they land on, are too: a seed
draws its own events from the same work, it does not change the work.

Rows. A row's values are a hash of (seed, group, id), so any subset of
rows can be regenerated without the others. FTRL ``z`` lies in [-8, 8)
and ``n`` in [0.5, 8.5), both multiples of 2^-12 (exact in float32);
``w`` is FTRL's weight of them; a serving replica holds ``w`` after the
int8 row codec, as the sync stream would deliver it.
"""

from __future__ import annotations

import math

import numpy as np

_M1 = np.uint64(0xBF58476D1CE4E5B9)
_M2 = np.uint64(0x94D049BB133111EB)
_GOLD = np.uint64(0x9E3779B97F4A7C15)
_MASK64 = (1 << 64) - 1


def mix64(x) -> np.ndarray:
    """SplitMix64 finalizer over a uint64 array (wrapping arithmetic)."""
    x = np.array(x, dtype=np.uint64, ndmin=1)
    x ^= x >> np.uint64(30)
    x *= _M1
    x ^= x >> np.uint64(27)
    x *= _M2
    x ^= x >> np.uint64(31)
    return x


def key(seed: int, *salt: int) -> np.uint64:
    """A 64-bit key from the run seed and salts."""
    h = mix64([seed & _MASK64])
    for s in salt:
        h = mix64(h ^ np.uint64(s & _MASK64))
    return h[0]


def rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([seed & _MASK64, salt])


# --------------------------------------------------------------------------
# ids
# --------------------------------------------------------------------------

def zipf_ranks(u: np.ndarray, size: int, a: float) -> np.ndarray:
    """Ranks in [0, size) from uniforms ``u``: the inverse CDF of a power
    law of exponent ``a`` on [1, size + 1), floored."""
    u = np.asarray(u, np.float64)
    if abs(a - 1.0) < 1e-9:
        x = np.exp(u * math.log(size + 1.0))
    else:
        e = 1.0 - a
        x = (((size + 1.0) ** e - 1.0) * u + 1.0) ** (1.0 / e)
    return np.clip(np.floor(x).astype(np.int64) - 1, 0, size - 1)


class Vocab:
    """Per-field id slices and their rank permutations."""

    PERMUTATION_SEED = 0x5EED

    def __init__(self, sizes):
        self.sizes = np.asarray(sizes, np.int64)
        self.offsets = np.concatenate(([0], np.cumsum(self.sizes)[:-1]))
        self.total = int(self.sizes.sum())
        r = rng(self.PERMUTATION_SEED, 1)
        self.mul = np.empty(len(self.sizes), np.int64)
        self.add = np.empty(len(self.sizes), np.int64)
        for f, v in enumerate(self.sizes.tolist()):
            m = int(r.integers(1, max(2, v)))
            while math.gcd(m, v) != 1:
                m += 1
            self.mul[f] = m % v if v > 1 else 0
            self.add[f] = int(r.integers(0, v))

    @property
    def fields(self) -> int:
        return len(self.sizes)

    def ids(self, f: int, ranks: np.ndarray) -> np.ndarray:
        v = int(self.sizes[f])
        return self.offsets[f] + (self.mul[f] * ranks + self.add[f]) % v

    def sample(self, r: np.random.Generator, n: int, a: float) -> np.ndarray:
        """(n, fields) int64 ids, ranks power-law within each field's whole
        vocabulary."""
        out = np.empty((n, self.fields), np.int64)
        u = r.random((n, self.fields))
        for f in range(self.fields):
            out[:, f] = self.ids(f, zipf_ranks(u[:, f], int(self.sizes[f]), a))
        return out

    def all_ids(self) -> np.ndarray:
        """Every id of the vocabulary."""
        return np.concatenate([self.ids(f, np.arange(int(v), dtype=np.int64))
                               for f, v in enumerate(self.sizes.tolist())])


# --------------------------------------------------------------------------
# rows
# --------------------------------------------------------------------------

def ftrl_state(ids: np.ndarray, dim: int, seed: int,
               group: int) -> tuple[np.ndarray, np.ndarray]:
    """Pre-seeded FTRL (z, n) rows of ``ids`` for one table group: each
    64-bit hash of (seed, group, id, pair) gives two elements' z and n
    from its four 16-bit quarters."""
    ids = np.ascontiguousarray(ids, np.int64)
    h0 = mix64(ids.view(np.uint64) ^ key(seed, 2, group))
    z = np.empty((len(ids), dim), np.float32)
    n = np.empty((len(ids), dim), np.float32)
    m16 = np.uint64(0xFFFF)
    for j in range(0, dim, 2):
        h = mix64(h0 + np.uint64(((j // 2 + 1) * int(_GOLD)) & _MASK64))
        for jj, shift in ((j, 48), (j + 1, 16)):
            if jj >= dim:
                break
            z[:, jj] = ((h >> np.uint64(shift)) & m16).astype(np.int32) \
                - (1 << 15)
            n[:, jj] = ((h >> np.uint64(shift - 16)) & m16) >> np.uint64(1)
    z *= np.float32(2.0 ** -12)
    n *= np.float32(2.0 ** -12)
    n += np.float32(0.5)
    return z, n


def ftrl_w(z: np.ndarray, n: np.ndarray, opt: dict) -> np.ndarray:
    """FTRL-proximal weights of (z, n), float32."""
    z = np.asarray(z, np.float32)
    denom = np.sqrt(np.asarray(n, np.float32))
    denom += np.float32(opt["beta"])
    denom /= np.float32(opt["alpha"])
    denom += np.float32(opt["l2"])
    w = np.sign(z)
    w *= np.float32(opt["l1"])
    w -= z
    w /= denom
    return np.where(np.abs(z) > np.float32(opt["l1"]), w,
                    np.float32(0.0)).astype(np.float32)


def int8_roundtrip(v: np.ndarray) -> np.ndarray:
    """Row-wise absmax int8 encode then decode (the sync codec)."""
    v = np.asarray(v, np.float32)
    s = np.maximum(np.abs(v).max(axis=-1, keepdims=True)
                   * np.float32(1.0 / 127.0), np.float32(1e-12))
    q = np.clip(np.rint(v / s), -127, 127).astype(np.int8)
    return q.astype(np.float32) * s


def serve_rows(ids: np.ndarray, dim: int, seed: int, group: int,
               opt: dict) -> np.ndarray:
    """What a serving replica holds for ``ids`` of one group."""
    z, n = ftrl_state(ids, dim, seed, group)
    return int8_roundtrip(ftrl_w(z, n, opt))


# --------------------------------------------------------------------------
# traffic
# --------------------------------------------------------------------------

def truth(ids: np.ndarray, seed: int, scale: float) -> np.ndarray:
    """Ground-truth logit share of each id, uniform in [-scale, scale)."""
    ids = np.ascontiguousarray(ids, np.int64)
    h = mix64(ids.reshape(-1).view(np.uint64) ^ key(seed, 3))
    u = (h >> np.uint64(40)).astype(np.float64) * 2.0 ** -24
    return ((2.0 * u - 1.0) * scale).reshape(ids.shape)


class TrainStream:
    """Closed-loop click stream: each tick offers ``events_per_tick``
    exposures at once. Exactly ``ctr`` of them are clicks, drawn without
    replacement by their ground-truth logits (Gumbel top-k); each click
    sends feedback after a delay taken from one fixed multiset of
    exponential quantiles (mean ``feedback_delay_s``, simulated seconds)
    in the seed's order. So every tick of every seed joins, and trains,
    the same number of examples."""

    def __init__(self, vocab: Vocab, traffic: dict, seed: int):
        self.vocab = vocab
        self.t = traffic
        self.seed = seed
        self.r = rng(seed, 4)
        self.view = 0
        n = int(traffic["events_per_tick"])
        self.clicks = int(round(float(traffic["ctr"]) * n))
        q = (np.arange(self.clicks) + 0.5) / max(self.clicks, 1)
        self.delays = -np.log1p(-q) * float(traffic["feedback_delay_s"])

    def tick(self) -> dict:
        n = int(self.t["events_per_tick"])
        ids = self.vocab.sample(self.r, n, float(self.t["zipf_a"]))
        logit = truth(ids, self.seed, float(self.t["truth_scale"])).sum(1)
        gumbel = -np.log(-np.log(self.r.random(n) * (1 - 1e-12) + 1e-12))
        pos = np.sort(np.argpartition(-(logit + gumbel), self.clicks)
                      [:self.clicks])
        y = np.zeros(n, np.float32)
        y[pos] = 1.0
        vids = np.arange(self.view, self.view + n, dtype=np.int64)
        self.view += n
        delay = self.delays[self.r.permutation(self.clicks)]
        return {"view_ids": vids, "feature_ids": ids, "labels": y,
                "fb_view_ids": vids[pos], "fb_delay": delay}


def request_sizes(traffic: dict, n: int) -> np.ndarray:
    """``n`` request sizes, log-uniform between ``min_examples`` and
    ``max_examples``: the distribution's quantiles at (i + 1/2) / n."""
    lo = math.log(float(traffic["min_examples"]))
    hi = math.log(float(traffic["max_examples"]))
    q = (np.arange(n) + 0.5) / n
    return np.rint(np.exp(lo + (hi - lo) * q)).astype(np.int64)


def serve_schedule(traffic: dict, rate: float, seconds: float,
                   seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Due times (s from the window's start) and sizes of the window's
    predict requests. Every seed gets the same multiset of inter-arrival
    gaps (exponential quantiles at ``rate``) and of sizes
    (``request_sizes``), in its own order: the work is the seed's
    arrangement, not its amount."""
    n = max(1, int(math.ceil(rate * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q) / rate
    sizes = request_sizes(traffic, n)
    r = rng(seed, 5)
    gaps = gaps[r.permutation(n)]
    sizes = sizes[r.permutation(n)]
    due = np.concatenate(([0.0], np.cumsum(gaps)[:-1]))
    return due, sizes
