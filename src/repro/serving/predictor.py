"""Serving plane: the decode step factory + a batched request driver used
by the serving example.

``serve_step`` consumes *serve params* — the slave-side state produced by
the WeiPS ModelSyncEngine — and a KV/SSM cache; it appends ONE token per
sequence. The driver supports hot weight updates between steps (the
second-level deployment the paper is about: new serve params swap in
without dropping in-flight sequences, because the cache layout is
independent of the weights)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import decode_step, init_cache

PyTree = Any


def make_serve_step(cfg: ModelConfig, jit: bool = True) -> Callable:
    def serve_step(params: PyTree, cache: PyTree, tokens: jax.Array,
                   pos: jax.Array):
        """tokens (B,1) int32; pos (B,) int32 -> (logits (B,V), new_cache)."""
        return decode_step(params, cfg, cache, tokens, pos)

    if jit:
        return jax.jit(serve_step, donate_argnums=(1,))
    return serve_step


@dataclass
class ServeDriver:
    """Batched greedy-decode driver with hot weight swap."""

    cfg: ModelConfig
    params: PyTree
    batch: int
    max_len: int
    cache_dtype: Any = jnp.float32
    step_fn: Optional[Callable] = None
    generated: list = field(default_factory=list)

    def __post_init__(self):
        self.step_fn = self.step_fn or make_serve_step(self.cfg)
        self.cache = init_cache(self.cfg, self.batch, self.max_len,
                                dtype=self.cache_dtype)
        self.pos = jnp.zeros((self.batch,), jnp.int32)

    def hot_swap(self, new_params: PyTree) -> None:
        """Second-level deployment: swap weights between decode steps."""
        self.params = new_params

    def step(self, tokens: jax.Array) -> jax.Array:
        logits, self.cache = self.step_fn(self.params, self.cache, tokens,
                                          self.pos)
        self.pos = self.pos + 1
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        self.generated.append(np.asarray(nxt))
        return nxt[:, None]

    def generate(self, prompt_token: jax.Array, steps: int) -> np.ndarray:
        # fresh accumulator per call: a second generate must return only
        # its own tokens, not stack the previous call's on top (the cache
        # and position carry over — hot_swap mid-stream still works)
        self.generated = []
        tok = prompt_token
        for _ in range(steps):
            tok = self.step(tok)
        return np.stack(self.generated, axis=1)
