"""Launch-layer machinery on the single local device: abstract specs,
sharding trees, lowering train/serve steps through jit (the 512-device
production meshes are exercised by launch/dryrun.py, not in unit tests)."""

import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import SHAPES, applicable, get_config, reduced
from repro.configs.shapes import InputShape
from repro.launch.mesh import make_local_mesh
from repro.launch.specs import (abstract_params, abstract_train_state,
                                input_specs)
from repro.models.sharding import MeshInfo, cache_pspecs, param_pspecs
from repro.serving import make_serve_step
from repro.training import make_train_step
from repro.models import init_cache, init_params


def _tiny_shape(kind):
    return InputShape(f"tiny_{kind}", 64, 2, kind)


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "mamba2-1.3b",
                                  "granite-moe-3b-a800m"])
def test_lower_train_step_local_mesh(arch):
    cfg = reduced(get_config(arch))
    mesh = make_local_mesh(1, 1)
    m = MeshInfo(mesh)
    state = abstract_train_state(cfg, m)
    shape = _tiny_shape("train")
    specs = input_specs(cfg, shape, m)
    lowered = jax.jit(make_train_step(cfg, jit=False)).lower(
        state, specs["batch"])
    compiled = lowered.compile()
    assert compiled.cost_analysis()["flops"] > 0


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "gemma3-4b"])
def test_lower_serve_step_local_mesh(arch):
    cfg = reduced(get_config(arch))
    mesh = make_local_mesh(1, 1)
    m = MeshInfo(mesh)
    params = abstract_params(cfg, m)
    shape = _tiny_shape("decode")
    specs = input_specs(cfg, shape, m)
    lowered = jax.jit(make_serve_step(cfg, jit=False)).lower(
        params, specs["cache"], specs["tokens"], specs["pos"])
    assert lowered.compile() is not None


def test_param_pspecs_tree_matches_params():
    cfg = reduced(get_config("jamba-1.5-large-398b"))
    m = MeshInfo(make_local_mesh(1, 1))
    params = init_params(cfg, jax.random.PRNGKey(0))
    pspecs = param_pspecs(cfg, m)
    # identical tree structure
    jax.tree.map(lambda a, b: None, params, pspecs,
                 is_leaf=lambda x: isinstance(
                     x, jax.sharding.PartitionSpec))
    # every spec rank matches its leaf rank
    flat_p = jax.tree.leaves(params)
    flat_s = jax.tree.leaves(
        pspecs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
    for p, s in zip(flat_p, flat_s):
        assert len(s) <= p.ndim, (p.shape, s)


def test_cache_pspecs_tree_matches_cache():
    cfg = reduced(get_config("gemma3-4b"))
    m = MeshInfo(make_local_mesh(1, 1))
    cache = init_cache(cfg, 2, 32, abstract=True)
    cspecs = cache_pspecs(cfg, m, 2)
    jax.tree.map(lambda a, b: None, cache, cspecs,
                 is_leaf=lambda x: isinstance(
                     x, jax.sharding.PartitionSpec))


def test_applicability_rules():
    assert applicable(get_config("mamba2-1.3b"), SHAPES["long_500k"])[0]
    assert applicable(get_config("jamba-1.5-large-398b"),
                      SHAPES["long_500k"])[0]
    assert applicable(get_config("gemma3-4b"), SHAPES["long_500k"])[0]
    ok, why = applicable(get_config("qwen2-7b"), SHAPES["long_500k"])
    assert not ok and "full-attention" in why
    ok, why = applicable(get_config("whisper-medium"), SHAPES["long_500k"])
    assert not ok
    # every arch runs decode_32k and all train/prefill shapes
    for a in ("qwen2-7b", "whisper-medium", "dbrx-132b"):
        assert applicable(get_config(a), SHAPES["decode_32k"])[0]
        assert applicable(get_config(a), SHAPES["train_4k"])[0]
