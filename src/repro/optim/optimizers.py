"""Optimizers with *named slots*, the contract the WeiPS parameter server
and its train→serve transform operate on (paper §1.2.1 "heterogeneous
parameters").

Each optimizer exposes:
  * ``init_slots(param)``       — auxiliary training state per parameter;
  * ``update(param, slots, grad, step)`` — one step, elementwise, so it
    applies identically to dense tensors and to gathered sparse rows;
  * ``serve_weights(param, slots)`` — the *inference* weights. Identity for
    most optimizers; FTRL derives ``w`` from ``z, n`` (the paper's flagship
    case: the master mainly stores ``z, n``; the slave stores only ``w``).
  * ``serve_slot_names`` — which slots the transform must read to build
    serve weights (everything else is never shipped to slaves).

All math is fp32 regardless of param dtype; params are cast back.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any


def _f32(x):
    return x.astype(jnp.float32)


@dataclass(frozen=True)
class Optimizer:
    lr: float = 1e-3

    name: str = "base"
    serve_slot_names: tuple[str, ...] = ()

    def init_slots(self, param: jax.Array) -> dict[str, jax.Array]:
        return {}

    def update(self, param, slots, grad, step):
        raise NotImplementedError

    def serve_weights(self, param: jax.Array, slots: dict) -> jax.Array:
        return param

    def serve_weights_np(self, param: np.ndarray, slots: dict) -> np.ndarray:
        """CPU-native ``serve_weights`` for the sync plane's numpy codec
        backend: the pusher encodes whole 65k-row flushes, where per-op
        eager-JAX dispatch (not FLOPs) dominates. Default falls through to
        the jnp path; optimizers with a numpy mirror override this."""
        return np.asarray(self.serve_weights(
            jnp.asarray(param),
            {k: jnp.asarray(v) for k, v in slots.items()}))

    # -- batched PS row path -------------------------------------------
    def update_rows(self, w: np.ndarray, slots: dict, grads: np.ndarray,
                    step: int, *, backend: str = "numpy"):
        """One batched update over gathered (B, D) sparse rows — the
        MasterShard hot path. Returns NumPy (new_w, new_slots). The base
        implementation routes through ``update``; optimizers with a fused
        Pallas kernel override this and dispatch on ``backend``."""
        new_w, new_slots = self.update(
            jnp.asarray(w), {k: jnp.asarray(v) for k, v in slots.items()},
            jnp.asarray(grads), step)
        return np.asarray(new_w), {k: np.asarray(v)
                                   for k, v in new_slots.items()}

    # -- pytree conveniences -------------------------------------------
    def init_slots_tree(self, params: PyTree) -> PyTree:
        return jax.tree.map(lambda p: self.init_slots(p), params)

    def update_tree(self, params: PyTree, slots: PyTree, grads: PyTree, step):
        flat_p, tdef = jax.tree.flatten(params)
        flat_s = tdef.flatten_up_to(slots)
        flat_g = tdef.flatten_up_to(grads)
        out = [self.update(p, s, g, step)
               for p, s, g in zip(flat_p, flat_s, flat_g)]
        new_p = tdef.unflatten([o[0] for o in out])
        new_s = tdef.unflatten([o[1] for o in out])
        return new_p, new_s


@dataclass(frozen=True)
class SGD(Optimizer):
    name: str = "sgd"

    def update(self, param, slots, grad, step):
        new = _f32(param) - self.lr * _f32(grad)
        return new.astype(param.dtype), slots


@dataclass(frozen=True)
class Momentum(Optimizer):
    momentum: float = 0.9
    name: str = "momentum"

    def init_slots(self, param):
        return {"m": jnp.zeros(param.shape, jnp.float32)}

    def update(self, param, slots, grad, step):
        m = self.momentum * slots["m"] + _f32(grad)
        new = _f32(param) - self.lr * m
        return new.astype(param.dtype), {"m": m}


@dataclass(frozen=True)
class Adagrad(Optimizer):
    eps: float = 1e-8
    initial_accumulator: float = 0.0
    name: str = "adagrad"

    def init_slots(self, param):
        return {"n": jnp.full(param.shape, self.initial_accumulator,
                              jnp.float32)}

    def update(self, param, slots, grad, step):
        g = _f32(grad)
        n = slots["n"] + g * g
        new = _f32(param) - self.lr * g / (jnp.sqrt(n) + self.eps)
        return new.astype(param.dtype), {"n": n}


@dataclass(frozen=True)
class Adam(Optimizer):
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-8
    name: str = "adam"

    def init_slots(self, param):
        return {"m": jnp.zeros(param.shape, jnp.float32),
                "v": jnp.zeros(param.shape, jnp.float32)}

    def update(self, param, slots, grad, step):
        g = _f32(grad)
        t = step + 1
        m = self.b1 * slots["m"] + (1 - self.b1) * g
        v = self.b2 * slots["v"] + (1 - self.b2) * g * g
        mhat = m / (1 - self.b1 ** t)
        vhat = v / (1 - self.b2 ** t)
        new = _f32(param) - self.lr * mhat / (jnp.sqrt(vhat) + self.eps)
        return new.astype(param.dtype), {"m": m, "v": v}


@dataclass(frozen=True)
class FTRL(Optimizer):
    """Follow-The-Regularized-Leader-Proximal (McMahan 2011). The training
    state is (z, n); the inference weight w is a pure function of them —
    the paper's canonical heterogeneous-parameter example."""

    alpha: float = 0.05
    beta: float = 1.0
    l1: float = 1.0
    l2: float = 1.0
    name: str = "ftrl"
    serve_slot_names: tuple[str, ...] = ("z", "n")

    def init_slots(self, param):
        return {"z": jnp.zeros(param.shape, jnp.float32),
                "n": jnp.zeros(param.shape, jnp.float32)}

    def weights_from(self, z, n):
        shrink = jnp.sign(z) * self.l1 - z
        denom = (self.beta + jnp.sqrt(n)) / self.alpha + self.l2
        return jnp.where(jnp.abs(z) > self.l1, shrink / denom, 0.0)

    def update(self, param, slots, grad, step):
        g = _f32(grad)
        z, n = slots["z"], slots["n"]
        w = self.weights_from(z, n)
        n_new = n + g * g
        sigma = (jnp.sqrt(n_new) - jnp.sqrt(n)) / self.alpha
        z_new = z + g - sigma * w
        new_w = self.weights_from(z_new, n_new)
        return new_w.astype(param.dtype), {"z": z_new, "n": n_new}

    def serve_weights(self, param, slots):
        return self.weights_from(slots["z"], slots["n"]).astype(param.dtype)

    def serve_weights_np(self, param, slots):
        return self._np_weights(
            np.asarray(slots["z"]), np.asarray(slots["n"])).astype(
            param.dtype, copy=False)

    def _np_weights(self, z: np.ndarray, n: np.ndarray) -> np.ndarray:
        # in-place ops: this runs inside the pusher's cache-blocked encode
        # tiles, where temporaries are the difference between staying in
        # L2 and spilling. Same op order as ``weights_from`` (jnp), so the
        # two stay bit-compatible.
        denom = np.sqrt(n)
        denom += self.beta
        denom /= self.alpha
        denom += self.l2
        w = np.sign(z)
        w *= self.l1
        w -= z
        w /= denom
        return np.where(np.abs(z) > self.l1, w, np.float32(0.0)).astype(
            np.float32, copy=False)

    def update_rows(self, w, slots, grads, step, *, backend: str = "numpy"):
        """Batched FTRL row update. ``pallas`` fuses the whole step into
        one VMEM pass (``kernels.ftrl_row_update``); ``numpy`` is the
        vectorized reference (identical math, fp32). Empty batches take
        the numpy path — a zero-row Pallas grid is undefined."""
        if backend == "pallas" and len(grads):
            from repro.kernels import ops
            z_new, n_new, w_new = ops.ftrl_row_update(
                jnp.asarray(slots["z"], jnp.float32),
                jnp.asarray(slots["n"], jnp.float32),
                jnp.asarray(grads, jnp.float32),
                alpha=self.alpha, beta=self.beta, l1=self.l1, l2=self.l2)
            return np.asarray(w_new), {"z": np.asarray(z_new),
                                       "n": np.asarray(n_new)}
        g = np.asarray(grads, np.float32)
        z = np.asarray(slots["z"], np.float32)
        n = np.asarray(slots["n"], np.float32)
        w_old = self._np_weights(z, n)
        n_new = n + g * g
        sigma = (np.sqrt(n_new) - np.sqrt(n)) / self.alpha
        z_new = z + g - sigma * w_old
        return self._np_weights(z_new, n_new), {"z": z_new, "n": n_new}


@dataclass(frozen=True)
class Adafactor(Optimizer):
    """Factored second-moment optimizer (Shazeer & Stern 2018, simplified:
    no update clipping, fixed decay). Slots for an (a, b, ...) tensor are
    row/col moment factors — O(a+b) memory instead of O(a·b), which is what
    lets the 90B/132B/398B training states fit 16 GB/chip (DESIGN.md §5)."""

    eps: float = 1e-30
    decay: float = 0.8
    name: str = "adafactor"

    def init_slots(self, param):
        if param.ndim >= 2:
            return {"vr": jnp.zeros(param.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(param.shape[:-2] + param.shape[-1:],
                                    jnp.float32)}
        return {"v": jnp.zeros(param.shape, jnp.float32)}

    def update(self, param, slots, grad, step):
        g = _f32(grad)
        t = step + 1
        beta = 1.0 - t ** (-self.decay)
        g2 = g * g + self.eps
        if param.ndim >= 2:
            vr = beta * slots["vr"] + (1 - beta) * g2.mean(axis=-1)
            vc = beta * slots["vc"] + (1 - beta) * g2.mean(axis=-2)
            rfac = vr / jnp.maximum(
                vr.mean(axis=-1, keepdims=True), self.eps)
            v = rfac[..., None] * vc[..., None, :]
            new_slots = {"vr": vr, "vc": vc}
        else:
            v = beta * slots["v"] + (1 - beta) * g2
            new_slots = {"v": v}
        upd = g * jax.lax.rsqrt(jnp.maximum(v, self.eps))
        new = _f32(param) - self.lr * upd
        return new.astype(param.dtype), new_slots


_OPTIMIZERS = {
    "sgd": SGD, "momentum": Momentum, "adagrad": Adagrad, "adam": Adam,
    "ftrl": FTRL, "adafactor": Adafactor,
}


def get_optimizer(name: str, **kw) -> Optimizer:
    if name not in _OPTIMIZERS:
        raise KeyError(f"unknown optimizer {name!r}: {sorted(_OPTIMIZERS)}")
    return _OPTIMIZERS[name](**kw)
