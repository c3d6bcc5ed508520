"""Codec backend equivalence: the transform's numpy mirror vs the Pallas
``delta_codec`` kernel (interpret mode off-TPU) vs the pure-jnp oracle in
kernels/ref.py, plus the cache-blocked encode path. (Separate from
test_transform.py, which is skipped wholesale when hypothesis is absent.)
"""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Int8Transform, make_transform
from repro.optim import FTRL


def test_int8_backends_match_ref_kernel():
    """Int8Transform's numpy and pallas backends both equal the pure-jnp
    oracle in kernels/ref.py (the pallas path runs the real delta_codec
    kernel in interpret mode off-TPU)."""
    from repro.kernels import ref
    w = (np.random.default_rng(7).normal(size=(33, 16)) * 10).astype(
        np.float32)
    enc_np = Int8Transform().encode(w, {})
    enc_pl = Int8Transform(backend="pallas").encode(w, {})
    q_ref, s_ref = ref.quantize_rows(jnp.asarray(w))
    for enc in (enc_np, enc_pl):
        np.testing.assert_array_equal(enc["q"], np.asarray(q_ref))
        np.testing.assert_allclose(enc["scale"], np.asarray(s_ref),
                                   rtol=1e-7)
    np.testing.assert_allclose(
        Int8Transform.decode(enc_pl),
        np.asarray(ref.dequantize_rows(q_ref, s_ref)), rtol=1e-7)


def test_int8_pallas_kernel_used_with_optimizer(monkeypatch):
    """With an optimizer attached the pusher passes a (n, 0) w
    placeholder — the pallas path must still invoke the delta_codec
    kernel (guard is on row count, not w.size) and match numpy."""
    from repro.kernels import ops
    calls = []
    real = ops.quantize_rows
    monkeypatch.setattr(ops, "quantize_rows",
                        lambda v: calls.append(1) or real(v))
    rng = np.random.default_rng(5)
    slots = {"z": (rng.normal(size=(24, 8)) * 3).astype(np.float32),
             "n": (rng.uniform(size=(24, 8)) * 5).astype(np.float32)}
    w = np.empty((24, 0), np.float32)
    enc_pl = make_transform("int8", FTRL(), backend="pallas").encode(
        w, slots)
    assert calls, "delta_codec kernel path was not exercised"
    enc_np = make_transform("int8", FTRL()).encode(w, slots)
    np.testing.assert_array_equal(enc_pl["q"], enc_np["q"])
    np.testing.assert_allclose(enc_pl["scale"], enc_np["scale"], rtol=1e-7)


def test_kernel_less_codecs_stay_on_numpy_engine():
    """backend='pallas' must not regress codecs without a kernel to the
    eager-jnp serve path — only int8 takes the device path."""
    assert not make_transform("identity", FTRL(),
                              backend="pallas")._device_path
    assert not make_transform("cast16", FTRL(),
                              backend="pallas")._device_path
    assert make_transform("int8", FTRL(), backend="pallas")._device_path


def test_encode_blocking_matches_unblocked():
    """Cache-blocked encode tiles produce exactly the same payload as a
    single-block encode (row-wise codecs are block-invariant)."""
    from repro.core.transform import _ENCODE_BLOCK
    n = _ENCODE_BLOCK + 257                    # forces the tiled path
    rng = np.random.default_rng(11)
    w = np.zeros((n, 4), np.float32)
    slots = {"z": (rng.normal(size=(n, 4)) * 3).astype(np.float32),
             "n": (rng.uniform(size=(n, 4)) * 5).astype(np.float32)}
    for codec in ("identity", "cast16", "int8"):
        t = make_transform(codec, FTRL())
        blocked = t.encode(w, slots)
        single = t.encode(w[:1], {k: v[:1] for k, v in slots.items()})
        for key in blocked:
            np.testing.assert_array_equal(np.asarray(blocked[key])[:1],
                                          np.asarray(single[key]))
            assert np.asarray(blocked[key]).shape[0] == n


@pytest.mark.parametrize("n", [1, 1023, 1025, 4097])
def test_int8_pallas_decode_is_host_and_bit_exact(n):
    """``Int8Transform.decode`` of the kernel's codes returns host numpy
    with the same bits as the jnp oracle, at the device path's bucket
    edges. A random block puts a ±127 code in every row; an all-zero
    block decodes through the 1e-12 scale floor."""
    from repro.kernels import ref
    rng = np.random.default_rng(n)
    for w in ((rng.normal(size=(n, 8)) * 10).astype(np.float32),
              np.zeros((n, 8), np.float32)):
        enc = Int8Transform(backend="pallas").encode(w, {})
        q, scale = enc["q"], enc["scale"]
        if w.any():
            assert (np.abs(q).max(axis=-1) == 127).all()
        else:
            assert (scale == np.float32(1e-12)).all()
        got = Int8Transform.decode(enc)
        assert isinstance(got, np.ndarray)
        assert got.dtype == np.float32 and got.shape == (n, 8)
        np.testing.assert_array_equal(
            got.view(np.uint32), np.asarray(ref.dequantize_rows(
                jnp.asarray(q), jnp.asarray(scale))).view(np.uint32))
