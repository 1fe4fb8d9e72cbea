"""Sharding rules: pspec table, divisibility fallback, constraint no-ops."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.config import MeshConfig
from repro.distributed.sharding import (
    batch_shardings,
    cache_shardings,
    constrain_batch,
    param_pspec,
    param_shardings,
)
from repro.launch.mesh import make_mesh
from tests.helpers import auto_abstract_mesh


def abstract_mesh(data=1, model=1, pod=1):
    # AbstractMesh: rule/pspec tests need mesh *shapes*, not devices
    if pod > 1:
        return auto_abstract_mesh((pod, data, model), ("pod", "data", "model"))
    return auto_abstract_mesh((data, model), ("data", "model"))


def small_mesh(fsdp=False):
    # 1x1 "production-shaped" mesh — rules exercise paths, not scale
    return make_mesh(MeshConfig(pod=1, data=1, model=1, fsdp=fsdp)), MeshConfig(
        pod=1, data=1, model=1, fsdp=fsdp
    )


def test_param_rules_select_expected_axes():
    mesh, mcfg = small_mesh(fsdp=True)
    # with axis size 1 everything divides; check the selected axis names
    cases = {
        "embed/tok": ((512, 64), (None, "model")),
        "embed/unemb": ((64, 512), ("data", "model")),
        "groups/full/attn/wq": ((4, 64, 64), (None, "data", "model")),
        "groups/mod/block/attn/wo": ((4, 64, 64), (None, "model", "data")),
        "groups/full/mlp/w_up": ((4, 64, 128), (None, "data", "model")),
        "groups/full/moe/w_up": ((4, 8, 64, 128), (None, "model", "data", None)),
        "groups/mod/router/w": ((4, 64), (None, None)),
        "groups/full/ssm/w_x": ((4, 64, 128), (None, "data", "model")),
        "groups/full/ssm/out_proj": ((4, 128, 64), (None, "model", "data")),
        "final_norm/scale": ((64,), (None,)),
    }
    for path, (shape, want) in cases.items():
        spec = param_pspec(path, shape, mesh, mcfg)
        got = tuple(spec) + (None,) * (len(shape) - len(tuple(spec)))
        assert got == want, (path, got, want)


def test_divisibility_fallback_replicates():
    # a 2-way model axis cannot shard an odd dim evenly
    mesh = abstract_mesh(data=2, model=2)
    mcfg = MeshConfig(pod=1, data=2, model=2, fsdp=False)
    spec = param_pspec("x/attn/wk", (64, 27), mesh, mcfg)  # 27 % 2 != 0
    assert tuple(spec) == (None, None) or tuple(spec) == (None,)


def test_batch_shardings_mrope_positions():
    mesh = abstract_mesh(data=2, model=1)
    tree = {
        "tokens": jax.ShapeDtypeStruct((8, 16), jnp.int32),
        "positions": jax.ShapeDtypeStruct((3, 8, 16), jnp.int32),
    }
    sh = batch_shardings(tree, mesh)
    assert sh["tokens"].spec == P(("data",), None)
    assert sh["positions"].spec == P(None, ("data",), None)


def test_cache_shardings_batch_vs_seq_parallel():
    mesh = abstract_mesh(data=2, model=2)
    from repro.config import get_config, smoke_config

    cfg = smoke_config(get_config("granite-8b"))
    tree = {
        "k": jax.ShapeDtypeStruct((4, 8, 32, 4, 32), jnp.float32),
        "pos": jax.ShapeDtypeStruct((4, 8, 32), jnp.int32),
        "cursor": jax.ShapeDtypeStruct((4, 8), jnp.int32),
    }
    sh = cache_shardings(tree, mesh, cfg, batch=8)
    assert sh["k"].spec[1] in ("data", ("data",))  # batch over data
    # B=1: sequence-parallel cache instead
    tree1 = {"k": jax.ShapeDtypeStruct((4, 1, 32, 4, 32), jnp.float32)}
    sh1 = cache_shardings(tree1, mesh, cfg, batch=1)
    assert sh1["k"].spec[2] == "data"


def test_constrain_batch_noop_without_mesh():
    x = jnp.ones((4, 8))
    y = constrain_batch(x)  # no ambient mesh in tests
    np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
