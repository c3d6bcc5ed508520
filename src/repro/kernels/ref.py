"""Pure-jnp oracles for every Pallas kernel — the ground truth the sweep
tests assert against (interpret-mode kernels must match these)."""

from __future__ import annotations

import jax
import jax.numpy as jnp


def embedding_scatter(table: jax.Array, ids: jax.Array,
                      updates: jax.Array) -> jax.Array:
    """table (V, D); ids (N,) UNIQUE; updates (N, D) -> (V, D) with rows
    replaced (set, not add). Duplicate ids are undefined — the PS scatter
    paths dedupe before calling."""
    return table.at[ids].set(updates.astype(table.dtype))


def hashmap_probe(keys_lo: jax.Array, keys_hi: jax.Array,
                  ids_lo: jax.Array, ids_hi: jax.Array, *, shift: int):
    """Oracle for the windowed open-addressing probe, via the full
    circular probe order (O(N·C) — test scale only).

    For each query, ranks every table slot by probe order from the id's
    home slot, then bins positions into probe windows (round 1 = the home
    slot alone, tail rounds = ``_WINDOW``-slot windows): a key is found
    iff its first match lands in a window no later than the first EMPTY
    slot's window (a hit anywhere in a window beats an EMPTY in the same
    window — the kernel checks hits before termination). The Fibonacci
    home computation is shared with the kernel (``fib_home_u32``), which
    the test suite pins against the host ``core.hashmap.home_slots``
    independently. Same limb layout and sentinel handling as the kernel;
    ``pos`` is garbage where ``found`` is False."""
    from repro.kernels.hashmap_probe import _WINDOW, fib_home_u32
    cap = keys_lo.shape[0]
    n = ids_lo.shape[0]
    sent_hi = jnp.uint32(0x80000000)
    bad = (ids_hi == sent_hi) & (ids_lo <= jnp.uint32(1))
    qlo = jnp.where(bad, jnp.uint32(0), ids_lo)
    qhi = jnp.where(bad, jnp.uint32(0), ids_hi)
    home = fib_home_u32(qlo, qhi, shift=shift)
    order = (home[:, None] + jnp.arange(cap, dtype=jnp.int32)) & (cap - 1)
    k_lo = keys_lo[order]
    k_hi = keys_hi[order]
    match = (k_lo == qlo[:, None]) & (k_hi == qhi[:, None])
    empty = (k_hi == sent_hi) & (k_lo == jnp.uint32(0))
    # probe-window index of each probe-order position
    widx = jnp.where(jnp.arange(cap) == 0, 0,
                     (jnp.arange(cap) - 1) // _WINDOW + 1)
    first_m = jnp.argmax(match, axis=1)            # first match position
    first_e = jnp.argmax(empty, axis=1)            # first EMPTY position
    m_w = widx[first_m]
    e_w = jnp.where(empty.any(axis=1), widx[first_e], cap + 1)
    found = match.any(axis=1) & (m_w <= e_w) & ~bad
    pos = order[jnp.arange(n), first_m]
    return pos, found


def ftrl_row_update(z, n, g, *, alpha: float, beta: float, l1: float,
                    l2: float):
    """FTRL-proximal row update. All inputs (B, D) fp32.
    Returns (z_new, n_new, w_new)."""
    w = jnp.where(jnp.abs(z) > l1,
                  (jnp.sign(z) * l1 - z) / ((beta + jnp.sqrt(n)) / alpha + l2),
                  0.0)
    n_new = n + g * g
    sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / alpha
    z_new = z + g - sigma * w
    w_new = jnp.where(jnp.abs(z_new) > l1,
                      (jnp.sign(z_new) * l1 - z_new)
                      / ((beta + jnp.sqrt(n_new)) / alpha + l2),
                      0.0)
    return z_new, n_new, w_new


def quantize_rows(x: jax.Array):
    """Row-wise absmax int8: x (B, D) -> (q int8 (B, D), scale f32 (B, 1)).
    Reciprocal multiply (not /127.0) to stay bit-identical with the
    kernel under XLA's constant-division folding."""
    scale = jnp.maximum(jnp.abs(x).max(axis=-1, keepdims=True)
                        * (1.0 / 127.0), 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_rows(q: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of ``quantize_rows``: int8 codes (B, D) × per-row scale
    (B, 1) -> float32 rows. Bit-identical to ``Int8Transform.decode``
    (one cast, one multiply — no fused-reciprocal divergence)."""
    return q.astype(jnp.float32) * scale


def flash_attention(q, k, v, *, causal: bool = True):
    """Reference attention. q (B, H, S, D); k, v (B, G, S, D) with
    H = G * group_size (GQA). fp32 softmax."""
    b, h, s, d = q.shape
    g = k.shape[1]
    m = h // g
    qg = q.reshape(b, g, m, s, d)
    scores = jnp.einsum("bgmsd,bgtd->bgmst", qg, k,
                        preferred_element_type=jnp.float32)
    scores *= d ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((s, k.shape[2]), dtype=bool))
        scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgmst,bgtd->bgmsd", p.astype(v.dtype), v)
    return out.reshape(b, h, s, d)


def decode_attention(q, k, v, lengths):
    """Single-token decode. q (B, H, D); k, v (B, S, G, D);
    lengths (B,) valid cache lengths. fp32 softmax. -> (B, H, D)."""
    b, h, d = q.shape
    g = k.shape[2]
    m = h // g
    qg = q.reshape(b, g, m, d)
    scores = jnp.einsum("bgmd,bsgd->bgms", qg, k,
                        preferred_element_type=jnp.float32) * d ** -0.5
    valid = jnp.arange(k.shape[1])[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bgms,bsgd->bgmd", p.astype(v.dtype), v)
    return out.reshape(b, h, d)
