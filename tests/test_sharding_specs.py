"""Sharding spec trees: structural match, divisibility, host-mesh smoke."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ASSIGNED_ARCHS, SMOKE_FACTORIES, get_config
from repro.models import (batch_axes, init_cache, init_params, param_specs,
                          cache_specs)


class FakeMesh:
    """Lightweight stand-in with .shape/.axis_names (no devices needed)."""

    def __init__(self, shape):
        self.shape = dict(shape)
        self.axis_names = tuple(shape)
        self.size = int(np.prod(list(shape.values())))


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_param_specs_match_tree_and_divide(arch):
    cfg = SMOKE_FACTORIES[arch]()          # small params, same structure
    params = init_params(jax.random.key(0), cfg)
    specs = param_specs(params, cfg, MESH)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, P))

    def check(leaf, spec):
        assert isinstance(spec, P)
        assert len(spec) <= leaf.ndim

    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: isinstance(x, P) or hasattr(x, "ndim"))


@pytest.mark.parametrize("arch", ["deepseek-7b", "mamba2-2.7b",
                                  "whisper-large-v3", "mixtral-8x7b"])
def test_full_config_specs_divide(arch):
    """Every sharded dim of the FULL config divides the mesh axis."""
    cfg = get_config(arch)
    params = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    specs = param_specs(params, cfg, MESH)

    def check(leaf, spec):
        for dim, ax in zip(leaf.shape, tuple(spec) + (None,) * leaf.ndim):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            size = int(np.prod([MESH.shape[a] for a in axes]))
            assert dim % size == 0, (leaf.shape, spec)

    jax.tree.map(check, params, specs,
                 is_leaf=lambda x: isinstance(x, P) or hasattr(x, "shape"))
    jax.tree.map(lambda l, s: check(l, s), params, specs,
                 is_leaf=lambda x: isinstance(x, P) or hasattr(x, "shape"))


def test_batch_axes_divisibility():
    assert batch_axes(256, MESH) == "data"
    assert batch_axes(256, MESH3) == ("pod", "data")
    assert batch_axes(1, MESH) is None
    assert batch_axes(8, MESH) is None           # 8 % 16 != 0
    assert batch_axes(256, MESH, include_model=True) == ("data", "model")


def test_cache_specs_structure():
    cfg = SMOKE_FACTORIES["minicpm3-4b"]()
    cache = init_cache(cfg, 4, 32)
    specs = cache_specs(cache, cfg, MESH, batch=4)
    assert jax.tree.structure(cache) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, P))


def test_jit_with_specs_on_host_mesh():
    """End-to-end: sharded loss step on the single-device host mesh."""
    from repro.models import loss_fn
    from jax.sharding import NamedSharding
    from repro.launch.mesh import make_host_mesh
    cfg = SMOKE_FACTORIES["llama2-7b"]()
    mesh = make_host_mesh()
    params = init_params(jax.random.key(0), cfg)
    specs = param_specs(params, cfg, mesh)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                      is_leaf=lambda x: isinstance(x, P))
    params = jax.device_put(params, sh)
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(rng.integers(0, 512, (2, 16)), jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 512, (2, 16)), jnp.int32)}
    with mesh:
        loss = jax.jit(lambda p, b: loss_fn(p, b, cfg))(params, batch)
    assert np.isfinite(float(loss))
