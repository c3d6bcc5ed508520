"""PartitionSpec generation for params, optimizer slots, caches and batches.

Layout policy (see DESIGN.md §5):
  * FSDP on the ``data`` axis (d_model / vocab rows), TP on ``model``
    (heads, ffn, experts, vocab-for-logits). The ``pod`` axis (multi-pod)
    joins batch sharding only — pure DP across pods, ICI-frugal.
  * GQA with few KV heads shards head_dim on ``model`` when divisible,
    otherwise replicates the KV projections.
  * Decode KV caches: batch -> data, sequence -> model (flash-decode
    combine); long_500k (batch=1) shards sequence over (data, model).
"""

from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import (ATTN, CROSS_ATTN, ENC_ATTN, LOCAL_ATTN, MAMBA,
                                MLP, MOE, NONE, LayerSpec, ModelConfig, Segment)

PyTree = Any

DATA, MODEL, POD = "data", "model", "pod"


class MeshInfo:
    """Axis sizes + derived batch sharding axes for a mesh."""

    def __init__(self, mesh: jax.sharding.Mesh):
        self.mesh = mesh
        self.axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.data = self.axes.get(DATA, 1)
        self.model = self.axes.get(MODEL, 1)
        self.batch_axes = ((POD, DATA) if POD in self.axes else (DATA,))

    def div(self, n: int, axis: str) -> bool:
        return n % self.axes.get(axis, 1) == 0


def _attn_specs(cfg: ModelConfig, m: MeshInfo) -> dict:
    h, g, e = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q_ax = (1, MODEL) if m.div(h, MODEL) else (
        (2, MODEL) if m.div(e, MODEL) else None)
    kv_ax = (1, MODEL) if m.div(g, MODEL) else (
        (2, MODEL) if m.div(e, MODEL) else None)
    if cfg.context_parallel_attn:
        # sequence-sharded attention: projections keep FSDP only; sharding
        # head_dim on `model` would force full-score all-reduces.
        if not m.div(h, MODEL):
            q_ax = None
        if not m.div(g, MODEL):
            kv_ax = None

    def proj(base_len, ax, d_axis_pos):
        spec = [None] * base_len
        spec[d_axis_pos] = DATA
        if ax is not None:
            spec[ax[0]] = ax[1]
        return P(*spec)

    specs = {
        "norm": P(None),
        "wq": proj(3, q_ax, 0),                     # (D,H,hd)
        "wk": proj(3, kv_ax, 0),                    # (D,Kv,hd)
        "wv": proj(3, kv_ax, 0),
        # wo (H,hd,D): mirror the q sharding, D -> data
        "wo": P(MODEL if (q_ax and q_ax[0] == 1) else None,
                MODEL if (q_ax and q_ax[0] == 2) else None, DATA),
    }
    if cfg.qkv_bias:
        specs["bq"] = P(MODEL if (q_ax and q_ax[0] == 1) else None,
                        MODEL if (q_ax and q_ax[0] == 2) else None)
        kv_b = P(MODEL if (kv_ax and kv_ax[0] == 1) else None,
                 MODEL if (kv_ax and kv_ax[0] == 2) else None)
        specs["bk"] = kv_b
        specs["bv"] = kv_b
    return specs


def _mlp_specs(cfg: ModelConfig, m: MeshInfo) -> dict:
    return {
        "norm": P(None),
        "w_gate": P(DATA, MODEL),
        "w_up": P(DATA, MODEL),
        "w_down": P(MODEL, DATA),
    }


def _moe_specs(cfg: ModelConfig, m: MeshInfo) -> dict:
    if m.div(cfg.num_experts, MODEL):
        up, down = P(MODEL, DATA, None), P(MODEL, None, DATA)
    else:
        up, down = P(None, DATA, MODEL), P(None, MODEL, DATA)
    return {
        "norm": P(None),
        "router": P(DATA, None),
        "w_gate": up,
        "w_up": up,
        "w_down": down,
    }


def _mamba_specs(cfg: ModelConfig, m: MeshInfo) -> dict:
    return {
        "norm": P(None),
        "wz": P(DATA, MODEL),
        "wx": P(DATA, MODEL),
        "wB": P(DATA, None),
        "wC": P(DATA, None),
        "wdt": P(DATA, None),
        "conv_w": P(None, None),
        "conv_b": P(None),
        "A_log": P(None),
        "D": P(None),
        "dt_bias": P(None),
        "gnorm": P(None),
        "out_proj": P(MODEL, DATA),
    }


_MIXER_SPECS = {ATTN: _attn_specs, LOCAL_ATTN: _attn_specs,
                ENC_ATTN: _attn_specs, CROSS_ATTN: _attn_specs,
                MAMBA: _mamba_specs}
_FFN_SPECS = {MLP: _mlp_specs, MOE: _moe_specs}


def _stack(spec_tree: PyTree) -> PyTree:
    """Prepend a None (the scan/repeats axis) to every PartitionSpec."""
    return jax.tree.map(lambda s: P(None, *s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _segment_specs(seg: Segment, cfg: ModelConfig, m: MeshInfo) -> dict:
    out = {}
    for i, spec in enumerate(seg.pattern):
        layer = {"mixer": _MIXER_SPECS[spec.mixer](cfg, m)}
        if spec.ffn != NONE:
            layer["ffn"] = _FFN_SPECS[spec.ffn](cfg, m)
        out[f"pos{i}"] = _stack(layer)
    return out


def param_pspecs(cfg: ModelConfig, m: MeshInfo) -> PyTree:
    """PartitionSpec tree mirroring ``init_params`` output."""
    embed = P(MODEL, DATA)
    specs: dict = {
        "embed": embed,
        "final_norm": P(None),
        "segments": [_segment_specs(s, cfg, m) for s in cfg.segments],
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = embed
    if cfg.encoder_segments:
        specs["encoder"] = {
            "segments": [_segment_specs(s, cfg, m)
                         for s in cfg.encoder_segments],
            "final_norm": P(None),
        }
    return specs


def cache_pspecs(cfg: ModelConfig, m: MeshInfo, batch: int,
                 kv_quant: bool = False) -> PyTree:
    """PartitionSpec tree mirroring ``init_cache`` output.

    batch >= data-axis size: batch -> data, seq -> model.
    batch == 1 (long-context): seq -> (data, model).
    """
    shard_seq_wide = batch < m.data

    def kv_spec(seq_len_small: bool):
        # (R, B, S, Kv, hd) — scale entries share the leading axes
        if shard_seq_wide:
            return P(None, None, (DATA, MODEL), None, None)
        if seq_len_small:
            return P(None, DATA, None, None, None)
        return P(None, DATA, MODEL, None, None)

    def kv_entry(s):
        if not kv_quant:
            return {"k": s, "v": s}
        return {"k": s, "v": s, "k_scale": s, "v_scale": s}

    def layer_cache(spec: LayerSpec):
        if spec.mixer == ATTN:
            return kv_entry(kv_spec(False))
        if spec.mixer == LOCAL_ATTN:
            return kv_entry(kv_spec(True))          # ring buffer of size W
        if spec.mixer == CROSS_ATTN:
            s = kv_spec(True)
            return {"xk": s, "xv": s}
        if spec.mixer == MAMBA:
            b_ax = None if shard_seq_wide else DATA
            h_ax = MODEL if m.div(cfg.ssm_num_heads, MODEL) else None
            return {
                "conv": P(None, b_ax, None, None),
                "state": P(None, b_ax, h_ax, None, None),
            }
        raise ValueError(spec.mixer)

    return {
        "segments": [
            {f"pos{i}": layer_cache(spec)
             for i, spec in enumerate(seg.pattern)}
            for seg in cfg.segments
        ],
    }


def batch_pspecs(cfg: ModelConfig, m: MeshInfo, kind: str,
                 global_batch: int) -> dict:
    """Input shardings for train/prefill batches or decode requests."""
    b_ax = m.batch_axes if global_batch >= m.data else None
    out = {"tokens": P(b_ax, None)}
    if cfg.has_encoder_context:
        out["enc_context"] = P(b_ax, None, None)
    if kind == "decode":
        out["pos"] = P(b_ax)
    return out


def logical_axis_constraint(x: jax.Array, m: Optional[MeshInfo],
                            spec: P) -> jax.Array:
    if m is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.NamedSharding(m.mesh, spec))
