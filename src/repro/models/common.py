"""Shared model building blocks: norms, rotary embeddings, initializers."""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def mesh_axis_names() -> tuple:
    """Axis names of the mesh set by ``jax.set_mesh``, () when mesh-less.

    Sharding-constraint helpers key off this to stay inert in mesh-less
    unit tests."""
    return jax.sharding.get_abstract_mesh().axis_names


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    """RMSNorm in fp32, cast back to input dtype."""
    dtype = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))
    return out.astype(dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotary position embedding.

    x: (..., S, n_heads, head_dim); positions: broadcastable to (..., S).
    Pairs dimension halves (GPT-NeoX style).
    """
    head_dim = x.shape[-1]
    half = head_dim // 2
    freq = jnp.arange(half, dtype=jnp.float32) / half
    inv_freq = theta ** (-freq)                                   # (half,)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq  # (..., S, half)
    angles = angles[..., None, :]                                 # (..., S, 1, half)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def dense_init(key: jax.Array, shape: tuple[int, ...], in_axis_size: int,
               dtype) -> jax.Array:
    """Scaled-normal initializer (variance ~ 1/fan_in)."""
    scale = in_axis_size ** -0.5
    return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)


def subkey(key: jax.Array, *names) -> jax.Array:
    """Deterministic per-path key derivation (stable across processes)."""
    for n in names:
        data = n if isinstance(n, int) else zlib.crc32(n.encode()) % (2 ** 31)
        key = jax.random.fold_in(key, data)
    return key
