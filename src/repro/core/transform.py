"""Model transformation: train-state → serve-state (paper §4.1.4b).

The master's rows are (w, optimizer slots); the slave needs only inference
weights, possibly re-encoded. A ``Transform`` pairs an ``encode`` (runs on
the pusher, master side) with a ``decode`` (runs on the scatter, slave
side). Encodings are *plain data* (numpy arrays / bytes) so they survive
the queue; the codec is named in the record's metadata and resolved from
this registry on the consuming side.

Codecs:
  * identity    — serve weights as-is (fp32)
  * cast16      — fp16 cast (half bandwidth)
  * int8        — row-wise absmax int8 quantization (the Pallas
                  ``delta_codec`` kernel is the TPU version of its encode)
  * ftrl        — the heterogeneous-parameter case: encode reads slots
                  (z, n) and ships the *derived* w

Backends — mirroring the PS row engine's ``numpy|pallas`` switch:
  * ``numpy``   — CPU reference codecs (the fast path on CPU-only hosts);
  * ``pallas``  — the int8 encode routes through the ``delta_codec``
    Pallas kernel (``kernels.ops.quantize_rows``, and
    ``kernels.ops.quantize_tensors`` for dense tensors, on the device or
    the host, at their own shapes): interpret mode off-TPU
    (bit-matching the reference), Mosaic-compiled on TPU. Codecs without
    a kernel (identity, cast16) keep running the numpy engine end-to-end
    (``kernel_backed`` gates the routing) — never an error, and never a
    silent regression to eager-jnp — so cluster configs can flip one flag
    for the whole sync plane.

Decode runs on the host for every backend: a record's payload arrives as
host numpy and its rows go straight into host tables, so a device round
trip would buy nothing. The int8 decode is one f32 cast and one f32
multiply, bit-identical to ``kernels.ops.dequantize_rows``, which stays
for callers that hold device arrays.

``encode`` is backend-routed per *instance* (the pusher owns a configured
``Transform``); ``decode`` is resolved per *record* (the scatter looks the
codec class up from record metadata), so producer and consumer need not
share a backend — exactly the paper's heterogeneous training/serving
cluster split.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.kernels.delta_codec import tensor_rows
from repro.kernels.device_io import to_host
from repro.obs import trace as obs_trace
from repro.optim import FTRL, Optimizer

CODEC_BACKENDS = ("numpy", "pallas")

# Encode tile height on the numpy backend. A 65k-row flush at dim 64 is
# ~16 MB per array; the serve+codec arithmetic is many elementwise passes,
# so untiled it is DRAM-bandwidth-bound. 8k-row tiles (~2 MB) keep every
# pass in L2 — the same effect that made the pre-refactor per-chunk loop
# deceptively fast, kept here without its per-chunk dispatch overhead.
_ENCODE_BLOCK = 8192


class Transform:
    name: str = "identity"
    kernel_backed: bool = False     # has a Pallas codec kernel

    def __init__(self, optimizer: Optional[Optimizer] = None,
                 backend: str = "numpy"):
        assert backend in CODEC_BACKENDS, \
            f"backend must be one of {CODEC_BACKENDS}"
        self.optimizer = optimizer
        self.backend = backend

    @property
    def _device_path(self) -> bool:
        """True when encode should run on-device: backend=pallas AND this
        codec actually has a kernel. Kernel-less codecs stay on the numpy
        engine (CPU-native serve + cache blocking) regardless of the
        backend flag."""
        return self.backend == "pallas" and self.kernel_backed

    @property
    def requires_w(self) -> bool:
        """Whether encode reads the stored weights. With an optimizer
        attached, serve weights are derived from ``serve_slot_names``
        alone (the heterogeneous-parameter contract: the param argument
        supplies dtype/shape only), so the pusher can skip gathering w."""
        return self.optimizer is None

    @property
    def required_slots(self) -> tuple:
        """Slot columns encode reads — () for plain weight codecs."""
        return self.optimizer.serve_slot_names if self.optimizer else ()

    def _iter_serve(self, w: np.ndarray, slots: dict):
        """Yield (lo, hi, serve_values(block)) over cache-sized row tiles.
        Single block on the pallas backend (the device kernel wants the
        whole array), for small inputs, and when slot arrays are not
        row-aligned with ``w`` (the dense-tensor encode path)."""
        n = w.shape[0]
        if (self._device_path or n <= _ENCODE_BLOCK
                or any(np.asarray(v).shape[:1] != (n,)
                       for v in slots.values())):
            yield 0, n, self.serve_values(w, slots)
            return
        for lo in range(0, n, _ENCODE_BLOCK):
            hi = min(lo + _ENCODE_BLOCK, n)
            yield lo, hi, self.serve_values(
                w[lo:hi], {k: v[lo:hi] for k, v in slots.items()})

    def _assemble(self, w: np.ndarray, slots: dict, finalize) -> dict:
        """Shared blocked-encode skeleton: run ``finalize`` (the codec's
        per-block serve-values → payload-arrays step) over the serve
        tiles and assemble full payload arrays. Single-block inputs
        return the finalized block directly (no extra copy)."""
        n, out = w.shape[0], None
        for lo, hi, v in self._iter_serve(w, slots):
            part = finalize(v)
            if lo == 0 and hi == n:
                return part
            if out is None:
                out = {k: np.empty((n,) + a.shape[1:], a.dtype)
                       for k, a in part.items()}
            for k, a in part.items():
                out[k][lo:hi] = a
        return out

    def serve_values(self, w: np.ndarray, slots: dict) -> np.ndarray:
        """Derive inference weights from master state. Always host-side
        (``serve_weights_np`` — no per-flush jnp round trip): the backend
        switch covers the *codec* kernel only, so decoded weights stay
        bit-identical across backends (eager-jnp FTRL derivation differs
        from the numpy mirror by 1 ulp on some elements, which would leak
        through the quantizer)."""
        if self.optimizer is not None:
            return self.optimizer.serve_weights_np(w, slots)
        return w

    def encode(self, w: np.ndarray, slots: dict) -> dict:
        # copy=False: serve_values output is already private (gathered rows
        # are take-copies; derived weights are fresh arrays) — dense-path
        # callers copy before encode (see Pusher._push_dense)
        if self.optimizer is None:               # pure pass-through
            return {"values": w.astype(np.float32, copy=False)}
        return self._assemble(
            w, slots,
            lambda v: {"values": v.astype(np.float32, copy=False)})

    def encode_values(self, v: np.ndarray) -> dict:
        """The codec alone over (rows, D) host values that are already
        serve weights — a dense tensor's rows: no serve derivation from
        slots. The payload never aliases ``v`` (a queued payload must not
        alias a live tensor)."""
        return {"values": np.array(v, np.float32)}

    def encode_tensors(self, tensors: list) -> list[dict]:
        """``encode_values`` over dense tensors, each by its rows (a
        matrix's rows, a vector as one row; the shape travels in the
        record's meta). Tensors on the device come to the host in one
        blocking read."""
        dev = [i for i, t in enumerate(tensors) if isinstance(t, jax.Array)]
        host = list(tensors)
        for i, h in zip(dev, to_host(*(tensors[i] for i in dev))
                        if dev else ()):
            host[i] = h
        return [self.encode_values(tensor_rows(t)) for t in host]

    @staticmethod
    def decode(payload: dict, backend: str = "numpy") -> np.ndarray:
        return payload["values"]

    def payload_bytes(self, payload: dict) -> int:
        return sum(np.asarray(v).nbytes for v in payload.values())


class Cast16Transform(Transform):
    name = "cast16"

    def encode(self, w, slots):
        return self._assemble(
            w, slots, lambda v: {"values16": v.astype(np.float16)})

    def encode_values(self, v):
        return {"values16": v.astype(np.float16)}

    @staticmethod
    def decode(payload, backend: str = "numpy"):
        return payload["values16"].astype(np.float32)


class Int8Transform(Transform):
    """Row-wise absmax int8: 4x bandwidth reduction on the push stage.
    ``backend="pallas"`` encodes with the ``kernels/delta_codec.py``
    kernel; ``numpy`` is its CPU mirror (bit-compatible by construction —
    the kernel body is the same arithmetic). Decode is host numpy on
    either backend, bit-identical to the kernel's dequantize."""

    name = "int8"
    kernel_backed = True

    @staticmethod
    def _quantize_np(v: np.ndarray) -> dict:
        v = v.astype(np.float32, copy=False)
        # reciprocal multiply, matching the kernel (see delta_codec)
        s = np.maximum(np.abs(v).max(axis=-1, keepdims=True)
                       * np.float32(1.0 / 127.0), 1e-12)
        q = np.clip(np.rint(v / s), -127, 127).astype(np.int8)
        return {"q": q, "scale": s.astype(np.float32, copy=False)}

    def encode(self, w, slots):
        # guard on row count, not w.size: with an optimizer attached the
        # pusher passes a (n, 0) w placeholder (columns come from slots)
        if self._device_path and len(w):
            from repro.kernels import ops
            q, scale = ops.quantize_rows(self.serve_values(w, slots))
            return {"q": q, "scale": scale}
        return self._assemble(w, slots, self._quantize_np)

    def encode_values(self, v):
        return self._quantize_np(v)

    def encode_tensors(self, tensors):
        # on the device path the kernel codes every tensor at its own
        # shape and all the codes come back in one read; a tensor that
        # lives on the device is coded where it lives, so its f32 values
        # never cross (the ``sync.dense_resident`` counter)
        if not self._device_path:
            return super().encode_tensors(tensors)
        from repro.kernels import ops
        resident = sum(isinstance(t, jax.Array) for t in tensors)
        if resident:
            obs_trace.get_tracer().count("sync.dense_resident", resident)
        return [{"q": q, "scale": scale}
                for q, scale in ops.quantize_tensors(tensors)]

    @staticmethod
    def decode(payload, backend: str = "numpy"):
        return payload["q"].astype(np.float32) * payload["scale"]


_TRANSFORMS: dict[str, type[Transform]] = {
    t.name: t for t in (Transform, Cast16Transform, Int8Transform)
}


def make_transform(codec: str, optimizer: Optional[Optimizer] = None,
                   backend: str = "numpy") -> Transform:
    """codec in {identity, cast16, int8}. If the optimizer has serve-slot
    semantics (FTRL), ``serve_values`` derives w from them automatically.
    ``backend`` selects the codec engine (see module docstring)."""
    cls = _TRANSFORMS[codec]
    needs_opt = optimizer is not None and (
        isinstance(optimizer, FTRL) or optimizer.serve_slot_names)
    return cls(optimizer if needs_opt else None, backend=backend)


def decode_record(record, backend: str = "numpy") -> np.ndarray:
    """Consumer-side decode: codec resolved from ``record.meta["codec"]``
    (defaulting to identity for pre-codec records), backend chosen by the
    *consumer* — producer and consumer backends are independent. A
    ``sync.decode`` span of ``repro.obs.trace``."""
    codec = record.meta.get("codec", "identity")
    with obs_trace.get_tracer().span("sync.decode"):
        return _TRANSFORMS[codec].decode(record.payload, backend=backend)
