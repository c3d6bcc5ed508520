"""Fused FTRL-proximal row update — the paper's flagship optimizer, fused
into one VMEM pass: given gathered rows (z, n) and gradient rows g, emits
(z', n', w') without materializing the ~10 elementwise intermediates XLA
would otherwise stream through HBM.

Layout: rows blocked (block_rows, D); D padded to the 128-lane register
width by the wrapper. All math fp32 (PS slot precision)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _w_from(z, n, *, alpha, beta, l1, l2):
    shrink = jnp.sign(z) * l1 - z
    denom = (beta + jnp.sqrt(n)) / alpha + l2
    return jnp.where(jnp.abs(z) > l1, shrink / denom, 0.0)


def _ftrl_kernel(z_ref, n_ref, g_ref, z_out, n_out, w_out, *,
                 alpha, beta, l1, l2):
    z = z_ref[...]
    n = n_ref[...]
    g = g_ref[...]
    w = _w_from(z, n, alpha=alpha, beta=beta, l1=l1, l2=l2)
    n_new = n + g * g
    sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / alpha
    z_new = z + g - sigma * w
    z_out[...] = z_new
    n_out[...] = n_new
    w_out[...] = _w_from(z_new, n_new, alpha=alpha, beta=beta, l1=l1, l2=l2)


def ftrl_row_update(z: jax.Array, n: jax.Array, g: jax.Array, *,
                    alpha: float = 0.05, beta: float = 1.0, l1: float = 1.0,
                    l2: float = 1.0, block_rows: int = 256,
                    interpret: bool = False):
    """z, n, g: (B, D) fp32. Returns (z', n', w') each (B, D) fp32."""
    b, d = z.shape
    block_rows = min(block_rows, b)
    grid = (pl.cdiv(b, block_rows),)
    spec = pl.BlockSpec((block_rows, d), lambda i: (i, 0))
    kernel = functools.partial(_ftrl_kernel, alpha=alpha, beta=beta,
                               l1=l1, l2=l2)
    out = jax.ShapeDtypeStruct((b, d), jnp.float32)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[out, out, out],
        interpret=interpret,
        name="ftrl_row_update",
    )(z.astype(jnp.float32), n.astype(jnp.float32), g.astype(jnp.float32))
