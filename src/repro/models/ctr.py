"""The paper's own model family: sparse CTR models (LR / FM / DNN) whose
parameters live on the WeiPS parameter server.

Per-example inputs are ``fields`` hashed feature IDs. The PS supplies
gathered rows; these functions are pure JAX on the gathered values, so
gradients w.r.t. rows flow back to the PS push path.

Paper §4.1.2: "LR-FTRL has 3 sparse matrices" (w + z + n), "FM-FTRL has 6"
(w,z,n for linear + latent), "FM-SGD has two", "DNN is multiple sparse plus
multiple dense" — here groups are {"w": 1} for LR, {"w": 1, "v": k} for FM,
{"emb": k} + dense MLP for DNN; optimizer slots multiply the stored
matrices exactly as the paper counts them.

DLRM-DCNv2 (``dlrm_dcnv2``; arXiv 1906.00091 with 2008.13535's cross
network, as MLPerf Training's ``recommendation_v2/torchrec_dlrm`` runs
it) reads ``{"emb": k}`` too, but a field pools several ids: its
functions take the ``(B, fields, k)`` sum-pooled rows (``kernels/ops.py``
``PooledLookup`` pools them on the device) and the example's dense
features, and train a ~16M-parameter tower (bottom MLP, low-rank cross
layers, top MLP) whose matmuls run at ``precision=HIGHEST``: float32, as
the reference trains.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.weips_ctr import CTRConfig


def groups_for(cfg: CTRConfig) -> dict[str, int]:
    if cfg.model_type == "lr":
        return {"w": 1}
    if cfg.model_type == "fm":
        return {"w": 1, "v": cfg.embed_dim}
    if cfg.model_type in ("dnn", "dlrm_dcnv2"):
        return {"emb": cfg.embed_dim}
    raise ValueError(cfg.model_type)


def check_scenario_groups(scenario_groups: dict[str, int],
                          store_groups: dict[str, int]) -> None:
    """A scenario can serve off the shared parameter store only when every
    sparse group it reads exists there with the same row dim (scenarios
    select *subsets* of the store — an LR scenario reads ``w`` off an FM
    store — they never widen it)."""
    for g, dim in scenario_groups.items():
        have = store_groups.get(g)
        if have is None:
            raise ValueError(
                f"scenario group {g!r} is not in the parameter store "
                f"(store groups: {sorted(store_groups)})")
        if have != dim:
            raise ValueError(
                f"scenario group {g!r} wants dim {dim} but the store "
                f"holds dim {have}")


def dense_shapes(cfg: CTRConfig) -> dict[str, tuple[int, ...]]:
    if cfg.model_type == "dlrm_dcnv2":
        return dlrm_shapes(cfg)
    if cfg.model_type != "dnn":
        return {}
    sizes = (cfg.fields * cfg.embed_dim,) + cfg.dnn_hidden + (1,)
    out = {}
    for i in range(len(sizes) - 1):
        out[f"mlp/w{i}"] = (sizes[i], sizes[i + 1])
        out[f"mlp/b{i}"] = (sizes[i + 1],)
    return out


def dlrm_shapes(cfg: CTRConfig) -> dict[str, tuple[int, ...]]:
    """The DLRM-DCNv2 tower's tensors, ``(in, out)`` weights: bottom MLP
    ``bottom/w{i}, b{i}``; cross layer ``l``'s ``cross/v{l}`` (d, rank),
    ``cross/u{l}`` (rank, d) and ``cross/b{l}`` (d,), d = (fields + 1) *
    embed_dim; top MLP ``top/w{i}, b{i}``."""
    if cfg.bottom_mlp[-1] != cfg.embed_dim or cfg.top_mlp[-1] != 1:
        raise ValueError("the bottom MLP must end at embed_dim and the top "
                         "MLP at one logit")
    d = (cfg.fields + 1) * cfg.embed_dim
    out = {}

    def mlp(part, sizes):
        for i in range(len(sizes) - 1):
            out[f"{part}/w{i}"] = (sizes[i], sizes[i + 1])
            out[f"{part}/b{i}"] = (sizes[i + 1],)

    mlp("bottom", (cfg.dense_features,) + cfg.bottom_mlp)
    for i in range(cfg.dcn_layers):
        out[f"cross/v{i}"] = (d, cfg.dcn_rank)
        out[f"cross/u{i}"] = (cfg.dcn_rank, d)
        out[f"cross/b{i}"] = (d,)
    mlp("top", (d,) + cfg.top_mlp)
    return out


def init_dense(cfg: CTRConfig, key: jax.Array) -> dict[str, np.ndarray]:
    if cfg.model_type == "dlrm_dcnv2":
        # weights normal with variance 1 / fan_in, biases zero: the
        # embeddings are not zero at the start, so no ReLU sits at 0
        out = {}
        for name, shape in dlrm_shapes(cfg).items():
            key, sub = jax.random.split(key)
            out[name] = np.zeros(shape, np.float32) if len(shape) == 1 \
                else np.asarray(jax.random.normal(sub, shape)
                                * (shape[0] ** -0.5), dtype=np.float32)
        return out
    shapes = dense_shapes(cfg)
    n_layers = sum(1 for n in shapes if n.startswith("mlp/w"))
    out = {}
    for name, shape in shapes.items():
        key, sub = jax.random.split(key)
        if name.endswith(tuple("b%d" % i for i in range(9))):
            # hidden biases start small-POSITIVE: embedding rows are
            # created as zeros on the PS, so with zero biases every ReLU
            # sits exactly at 0 and its gradient is 0 — no signal ever
            # reaches the embeddings and the DNN never learns (it was the
            # weips-dnn-adam seed failure). The output bias stays 0 so the
            # first prediction is the uninformed prior.
            i = int(name[len("mlp/b"):])
            fill = 0.1 if i < n_layers - 1 else 0.0
            out[name] = np.full(shape, fill, np.float32)
        else:
            out[name] = np.asarray(
                jax.random.normal(sub, shape) * (shape[0] ** -0.5),
                dtype=np.float32)
    return out


# ---------------------------------------------------------------------------
# Forward / loss — pure functions of the gathered rows
# ---------------------------------------------------------------------------


def lr_logits(rows: dict, dense: dict) -> jax.Array:
    # rows["w"]: (B, F, 1)
    return rows["w"][..., 0].sum(axis=1)


def fm_logits(rows: dict, dense: dict) -> jax.Array:
    linear = rows["w"][..., 0].sum(axis=1)                    # (B,)
    v = rows["v"]                                             # (B, F, k)
    s = v.sum(axis=1)                                         # (B, k)
    inter = 0.5 * (jnp.square(s) - jnp.square(v).sum(axis=1)).sum(axis=-1)
    return linear + inter


def dnn_logits(rows: dict, dense: dict) -> jax.Array:
    emb = rows["emb"]                                         # (B, F, k)
    h = emb.reshape(emb.shape[0], -1)
    i = 0
    while f"mlp/w{i}" in dense:
        h = h @ dense[f"mlp/w{i}"] + dense[f"mlp/b{i}"]
        if f"mlp/w{i+1}" in dense:
            h = jax.nn.relu(h)
        i += 1
    return h[:, 0]


_LOGITS: dict[str, Callable] = {"lr": lr_logits, "fm": fm_logits,
                                "dnn": dnn_logits}

HIGHEST = jax.lax.Precision.HIGHEST


def _layers(dense: dict, part: str) -> int:
    n = 0
    while f"{part}/w{n}" in dense:
        n += 1
    return n


def cross_layer(x0, xl, v, u, b):
    """One low-rank DCN-V2 cross layer: x0 * ((xl V) U + b) + xl."""
    low = jnp.dot(xl, v, precision=HIGHEST)
    return x0 * (jnp.dot(low, u, precision=HIGHEST) + b) + xl


def dlrm_logits(pooled: jax.Array, dense: dict, x: jax.Array) -> jax.Array:
    """DLRM-DCNv2's logit of ``pooled`` (B, F, k) sum-pooled embeddings and
    ``x`` (B, dense_features): bottom MLP (ReLU after every layer) to k
    wide; x0 = [bottom output, F pooled rows] flattened ((F + 1) * k); each
    cross layer x_{l+1} = x0 * (x_l V_l U_l + b_l) + x_l; top MLP (ReLU
    after every layer but the last)."""
    def mm(a, w):
        return jnp.dot(a, w, precision=HIGHEST)

    h = x
    for i in range(_layers(dense, "bottom")):
        h = jax.nn.relu(mm(h, dense[f"bottom/w{i}"]) + dense[f"bottom/b{i}"])
    x0 = jnp.concatenate([h[:, None, :], pooled], axis=1).reshape(
        pooled.shape[0], -1)
    xl = x0
    i = 0
    while f"cross/v{i}" in dense:
        xl = cross_layer(x0, xl, dense[f"cross/v{i}"], dense[f"cross/u{i}"],
                         dense[f"cross/b{i}"])
        i += 1
    h = xl
    n = _layers(dense, "top")
    for i in range(n):
        h = mm(h, dense[f"top/w{i}"]) + dense[f"top/b{i}"]
        if i < n - 1:
            h = jax.nn.relu(h)
    return h[:, 0]


def _weighted_logloss(logits, y, w):
    per = (jnp.maximum(logits, 0) - logits * y
           + jnp.log1p(jnp.exp(-jnp.abs(logits))))
    return jnp.sum(w * per) / jnp.maximum(jnp.sum(w), 1e-9)


@jax.jit
def _dlrm_predict(pooled, dense, x):
    return jax.nn.sigmoid(dlrm_logits(pooled, dense, x))


@jax.jit
def _dlrm_loss_grads(pooled, dense, x, y, w):
    """(weighted logloss, its gradient by the pooled rows, by the tower)."""
    val, grads = jax.value_and_grad(
        lambda p, d: _weighted_logloss(dlrm_logits(p, d, x), y, w),
        argnums=(0, 1))(pooled, dense)
    return val, grads[0], grads[1]


def predict_fn(cfg: CTRConfig) -> Callable:
    if cfg.model_type == "dlrm_dcnv2":
        return _dlrm_predict               # (pooled, dense, x) -> (B,)
    f = _LOGITS[cfg.model_type]

    @jax.jit
    def predict(rows, dense):
        return jax.nn.sigmoid(f(rows, dense))

    return predict


def predict_block_fn(cfg: CTRConfig,
                     offsets: dict[str, tuple[int, int]]) -> Callable:
    """Predict from a combined-group row block ``(B*F, sum of dims)`` —
    the serve cache's native layout (``ServeCache.offsets``): the
    per-group split happens *inside* the jitted function as device
    slices fused into the predict graph, so the serving hot path pays
    ONE host→device transfer and zero per-group host row copies."""
    f = _LOGITS[cfg.model_type]
    fields = cfg.fields
    offs = tuple((g, lo, hi) for g, (lo, hi) in offsets.items())

    @jax.jit
    def predict(block, dense):
        r3 = block.reshape(-1, fields, block.shape[1])
        rows = {g: r3[:, :, lo:hi] for g, lo, hi in offs}
        return jax.nn.sigmoid(f(rows, dense))

    return predict


def loss_and_grads_fn(cfg: CTRConfig) -> Callable:
    f = _LOGITS[cfg.model_type]

    def loss(rows, dense, y):
        logits = f(rows, dense)
        return jnp.mean(
            jnp.maximum(logits, 0) - logits * y
            + jnp.log1p(jnp.exp(-jnp.abs(logits))))

    @jax.jit
    def loss_and_grads(rows, dense, y):
        val, grads = jax.value_and_grad(loss, argnums=(0, 1))(rows, dense, y)
        return val, grads[0], grads[1]

    return loss_and_grads


def weighted_loss_and_grads_fn(cfg: CTRConfig) -> Callable:
    """Per-example-weighted BCE — the training plane's step (for
    ``dlrm_dcnv2`` it takes ``(pooled, dense, x, y, w)``). Weights carry
    (a) the joiner's negative-downsampling correction (kept negatives
    weigh 1/rate, so the weighted loss stays unbiased) and (b) the
    pad-to-bucket zeros: the pipeline pads row tensors up to a pow2
    bucket so this jits once per bucket shape, and the padded examples'
    weight of 0 removes them from both the loss and every gradient."""
    if cfg.model_type == "dlrm_dcnv2":
        return _dlrm_loss_grads
    f = _LOGITS[cfg.model_type]

    def loss(rows, dense, y, w):
        return _weighted_logloss(f(rows, dense), y, w)

    @jax.jit
    def loss_and_grads(rows, dense, y, w):
        val, grads = jax.value_and_grad(loss, argnums=(0, 1))(
            rows, dense, y, w)
        return val, grads[0], grads[1]

    return loss_and_grads
