"""Sharding rules: validity (divisibility) for every FULL config on the
production mesh topology, without touching device state (AbstractMesh)."""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import ARCH_IDS, arch_shapes, get_config
from repro.models.model import build_model, input_specs
from repro.optim.adamw import adamw_init
from repro.runtime import sharding


def _mesh(multi=False):
    if multi:
        return sharding.abstract_mesh((2, 16, 16), ("pod", "data", "model"))
    return sharding.abstract_mesh((16, 16), ("data", "model"))


def _check_divisible(spec_tree, shape_tree, mesh):
    specs = jax.tree.flatten(spec_tree, is_leaf=lambda x: isinstance(x, P))[0]
    shapes = jax.tree.leaves(shape_tree)
    assert len(specs) == len(shapes)
    for spec, leaf in zip(specs, shapes):
        for dim, axes in zip(leaf.shape, tuple(spec)):
            if axes is None:
                continue
            assert dim % sharding.axes_size(mesh, axes) == 0, (leaf.shape, spec)


@pytest.mark.parametrize("arch", ARCH_IDS)
@pytest.mark.parametrize("multi", [False, True])
def test_param_and_opt_specs_divide(arch, multi):
    cfg = get_config(arch)
    model = build_model(cfg)
    mesh = _mesh(multi)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = sharding.param_pspecs(cfg, shapes, mesh)
    _check_divisible(pspecs, shapes, mesh)
    opt_shapes = jax.eval_shape(adamw_init, shapes)
    ospecs = sharding.opt_pspecs(cfg, opt_shapes, pspecs, mesh)
    _check_divisible(ospecs, opt_shapes, mesh)


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_batch_and_cache_specs_divide(arch):
    cfg = get_config(arch)
    mesh = _mesh()
    for cell in arch_shapes(cfg):
        specs = input_specs(cfg, cell)
        if "batch" in specs:
            b = sharding.batch_pspecs(cfg, specs["batch"], mesh)
            _check_divisible(b, specs["batch"], mesh)
        if "cache" in specs:
            c = sharding.cache_pspecs(cfg, specs["cache"], mesh)
            _check_divisible(c, specs["cache"], mesh)


def test_tp_shards_the_big_params():
    """The 2D-parallel point: big weights must NOT be replicated."""
    cfg = get_config("yi-34b")
    model = build_model(cfg)
    mesh = _mesh()
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = sharding.param_pspecs(cfg, shapes, mesh)
    flat = jax.tree_util.tree_flatten_with_path(
        pspecs, is_leaf=lambda x: isinstance(x, P)
    )[0]
    shapes_flat = jax.tree_util.tree_flatten_with_path(shapes)[0]
    for (path, spec), (_, leaf) in zip(flat, shapes_flat):
        if np.prod(leaf.shape) > 16e6:  # every large tensor
            assert any(ax is not None for ax in tuple(spec)), (path, leaf.shape)


def test_zero1_adds_data_axis():
    cfg = get_config("yi-9b")
    model = build_model(cfg)
    mesh = _mesh()
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = sharding.param_pspecs(cfg, shapes, mesh)
    opt_shapes = jax.eval_shape(adamw_init, shapes)
    ospecs = sharding.opt_pspecs(cfg, opt_shapes, pspecs, mesh)
    m_specs = jax.tree.flatten(ospecs["m"], is_leaf=lambda x: isinstance(x, P))[0]
    n_data = sum(1 for s in m_specs if "data" in tuple(s))
    assert n_data >= len(m_specs) * 0.8  # almost every moment is ZeRO-sharded


def test_fsdp_mode_claims_model_axis_for_batch():
    cfg = get_config("yi-9b")
    mesh = _mesh()
    batch = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    tp = sharding.batch_pspecs(cfg, batch, mesh, mode="tp")["tokens"]
    fsdp = sharding.batch_pspecs(cfg, batch, mesh, mode="fsdp")["tokens"]
    assert tuple(tp)[0] in ("data", ("data",))
    assert tuple(fsdp)[0] == ("data", "model")
    # dp mode replicates params
    model = build_model(cfg)
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    dp = sharding.param_pspecs(cfg, shapes, mesh, mode="dp")
    assert all(s == P() for s in jax.tree.flatten(
        dp, is_leaf=lambda x: isinstance(x, P))[0])


import jax.numpy as jnp  # noqa: E402


def test_kv_cache_prefers_head_sharding_when_divisible():
    mesh = _mesh()
    spec = sharding._kv_spec((28, 128, 32768, 16, 128), mesh)  # deepseek-like
    assert tuple(spec)[3] == "model"
    spec = sharding._kv_spec((60, 128, 32768, 8, 128), mesh)  # yi-34b GQA 8
    assert tuple(spec)[2] == "model" and tuple(spec)[3] is None
