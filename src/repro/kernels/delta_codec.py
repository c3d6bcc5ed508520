"""Row-wise absmax int8 delta codec — the on-device serialize+compress
stage of the WeiPS pusher (paper §4.1.3), 4x wire-bandwidth reduction.

Quantize: one VMEM pass computes the per-row absmax scale and the int8
payload; dequantize is the scatter-side inverse. Row blocks of
(block_rows, D) keep the reduction in-register (D is last-dim/lane-major).

Two consumers share this kernel (both through ``kernels/ops.py``, with a
bit-identical numpy mirror in ``core/transform.py``): the streaming sync
codec (``Int8Transform``) and the checkpoint compressor
(``BackupPolicy.compress="int8"`` in ``core/fault_tolerance.py``), which
packs full/delta checkpoint row payloads with the same arithmetic so
compressed chain restores stay bit-equal to compressed full restores.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK_ROWS = 256        # rows a grid step codes, unless the caller gives


def tensor_rows(x):
    """A dense tensor as the codec's rows, each row its own scale: a
    matrix by its rows, a vector as one row (host or device arrays)."""
    return x.reshape(-1, x.shape[-1]) if x.ndim > 1 else x.reshape(1, -1)


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)
    # explicit f32-reciprocal multiply, not /127.0: XLA folds constant
    # divisions into reciprocal multiplies anyway, and writing it out
    # keeps kernel, ref.py oracle, and the transform's numpy mirror
    # bit-identical
    scale = jnp.maximum(jnp.abs(x).max(axis=-1, keepdims=True)
                        * (1.0 / 127.0), 1e-12)
    q = jnp.clip(jnp.round(x / scale), -127.0, 127.0)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def quantize_rows(x: jax.Array, *, block_rows: int = BLOCK_ROWS,
                  interpret: bool = False):
    """x (B, D) -> (q int8 (B, D), scale f32 (B, 1))."""
    b, d = x.shape
    block_rows = min(block_rows, b)
    grid = (pl.cdiv(b, block_rows),)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                   pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((b, d), jnp.int8),
                   jax.ShapeDtypeStruct((b, 1), jnp.float32)],
        interpret=interpret,
        name="quantize_rows",
    )(x)


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def dequantize_rows(q: jax.Array, scale: jax.Array, *,
                    block_rows: int = BLOCK_ROWS, interpret: bool = False):
    """(q int8 (B, D), scale (B, 1)) -> x f32 (B, D)."""
    b, d = q.shape
    block_rows = min(block_rows, b)
    grid = (pl.cdiv(b, block_rows),)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((block_rows, 1), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, d), jnp.float32),
        interpret=interpret,
        name="dequantize_rows",
    )(q, scale)
