"""Sharding-rule unit tests (AbstractMesh — no devices needed)."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import base as cfgbase
from repro.launch import sharding as shr
from repro.models import transformer as tr

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def test_fit_spec_drops_nondivisible():
    assert shr.fit_spec((2, 128), P("model", None), MESH) == P()
    assert shr.fit_spec((32, 128), P("model", "data"), MESH) == \
        P("model", "data")
    assert shr.fit_spec((32, 100), P("model", "data"), MESH) == P("model")
    # tuple axes: 32 % (2*16) == 0 on the 3-axis mesh
    assert shr.fit_spec((32, 8), P(("pod", "data"), None), MESH3) == \
        P(("pod", "data"))
    assert shr.fit_spec((30, 8), P(("pod", "data"), None), MESH3) == P()


@pytest.mark.parametrize("arch", cfgbase.list_archs())
def test_param_specs_cover_all_leaves(arch):
    """Every full-config param leaf gets a spec that divides its dims."""
    cfg = cfgbase.resolve(arch)
    shapes = jax.eval_shape(lambda k: tr.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    specs = shr.param_specs(cfg, shapes, MESH3)
    n_sharded = 0
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]):
        assert isinstance(spec, P), path
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        for dim, ax in zip(leaf.shape, entries):
            if ax is None:
                continue
            size = 1
            for a in (ax if isinstance(ax, tuple) else (ax,)):
                size *= MESH3.shape[a]
            assert dim % size == 0, (path, leaf.shape, spec)
            n_sharded += 1
    assert n_sharded > 0, f"{arch}: nothing sharded at all"


@pytest.mark.parametrize("arch", cfgbase.list_archs())
def test_big_leaves_are_sharded(arch):
    """No parameter leaf > 64 MB may stay fully replicated (memory!)."""
    cfg = cfgbase.resolve(arch)
    shapes = jax.eval_shape(lambda k: tr.init_params(cfg, k),
                            jax.random.PRNGKey(0))
    specs = shr.param_specs(cfg, shapes, MESH3)
    for (path, leaf), (_, spec) in zip(
            jax.tree_util.tree_flatten_with_path(shapes)[0],
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]):
        n_bytes = leaf.size * 4
        if n_bytes > 64e6:
            assert any(ax is not None for ax in spec), \
                f"{arch}: {path} ({n_bytes / 1e6:.0f} MB) replicated"


def test_cache_specs_split_k_for_small_kv():
    """glm4 (kv=2 < model=16): cache must shard sequence, not heads."""
    cfg = cfgbase.resolve("glm4-9b")
    from repro.models.model import build_model
    m = build_model(cfg)
    cache = jax.eval_shape(lambda: m.init_cache(128, 32768))
    specs = shr.cache_specs(cfg, cache, MESH, batch=128)
    k_spec = specs["k"]
    # (L, B, S, Hkv, Dh): S over model (index 2)
    assert k_spec[2] == "model", k_spec
    # deepseek MLA latent: split-K over S too
    cfg2 = cfgbase.resolve("deepseek-v2-236b")
    m2 = build_model(cfg2)
    cache2 = jax.eval_shape(lambda: m2.init_cache(128, 32768))
    specs2 = shr.cache_specs(cfg2, cache2, MESH, batch=128)
    assert specs2["c_kv"][2] == "model"


def test_batch_specs_handle_unshardable_batch():
    cfg = cfgbase.resolve("zamba2-2.7b")
    # long_500k: global_batch=1 cannot shard over dp
    specs = shr.batch_specs(cfg, MESH, global_rows=1)
    assert specs["labels"] == P(None, None)
    specs2 = shr.batch_specs(cfg, MESH, global_rows=256)
    assert specs2["labels"][0] in ("data", ("data",))
