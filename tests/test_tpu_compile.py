"""Compile the decode path for a TPU v5e without a chip.

The TPU compiler compiles for a described ``v5e:2x2`` topology from this
CPU process, so Mosaic's layout rules — which the Pallas interpreter never
applies — are checked on every run at smollm-135m's published widths
(3 KV heads x 64, rep 3) with batch 8 and a 2048-token cache.  The
topology is described inside a fixture, never at import: only one process
may load the TPU library, and every test worker imports this file.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_config
from repro.kernels.paged_attention import kernel as K
from repro.kernels.paged_attention.ref import PAGE, plane_words
from repro.models.model import build_model
from repro.models.transformer import bitplane_cache_from_dense

B, S = 8, 2048
KEEPS = (8, 12, 16)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described-chip compile can be written to the persistent cache
        # but never read back without the chip: keep the cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def model():
    return build_model(get_config("smollm-135m"))


def _on(sharding, tree):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _kernel_args(one_chip):
    cfg = get_config("smollm-135m")
    hq, w = cfg.n_heads, plane_words(cfg.n_kv_heads * cfg.head_dim)
    planes = jax.ShapeDtypeStruct((16, B, S // PAGE, 1, w), jnp.int32)
    q = jax.ShapeDtypeStruct((B, 2, hq, w), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((B, S // 128, 1, 128), jnp.int32)
    scale = float(1 / np.sqrt(cfg.head_dim))
    return _on(one_chip, (q, planes, mask)), scale


def test_fused_kernel_compiles_for_v5e(one_chip):
    (q, planes, mask), scale = _kernel_args(one_chip)
    keeps = _on(one_chip, jax.ShapeDtypeStruct((B, S // PAGE), jnp.int32))
    compiled = jax.jit(
        lambda *a: K.paged_attention_fused(*a, scale=scale, interpret=False)
    ).lower(q, planes, planes, keeps, mask).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_rung_kernel_compiles_for_v5e(one_chip):
    (q, planes, mask), scale = _kernel_args(one_chip)
    compiled = jax.jit(
        lambda *a: K.paged_attention_rung(*a, keep=8, scale=scale,
                                          interpret=False)
    ).lower(q, planes, planes, mask).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _decode_args(model, one_chip, bitplane: bool):
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def cache_fn():
        cache = model.init_cache(B, S)
        if bitplane:
            cache = bitplane_cache_from_dense(cache)
        cache["len"] = jnp.zeros(B, jnp.int32)
        return cache

    cache = jax.eval_shape(cache_fn)
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    return _on(one_chip, (params, tok, cache))


def test_bitplane_decode_step_compiles_for_v5e(model, one_chip, monkeypatch):
    """The served step: 30 layers, each running the fused kernel."""
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    args = _decode_args(model, one_chip, bitplane=True)
    compiled = jax.jit(
        lambda p, t, c: model.decode(p, t, c, keeps=KEEPS,
                                     decode_kernel="fused")
    ).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_decode_step_compiles_for_v5e(model, one_chip):
    compiled = jax.jit(model.decode).lower(
        *_decode_args(model, one_chip, bitplane=False)).compile()
    # the dense step has no kernel; it must fit one chip's 16 GB
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < 16e9


@pytest.mark.parametrize("bitplane", [True, False])
def test_fill_page_copy_compiles_for_v5e(model, one_chip, bitplane):
    """The decode page-fill copy of every slot at once (4 stored layers):
    gather (and on the bit-plane layout, unpack) into one bf16 array of
    2 x B x 4 x 16 x (Hkv*hd)."""
    from repro.serving.backends.base import fill_pages

    cfg = model.cfg
    _, _, cache = _decode_args(model, one_chip, bitplane)
    names = ("k_planes", "v_planes") if bitplane else ("k", "v")
    idx = _on(one_chip, (jax.ShapeDtypeStruct((B,), jnp.int32),
                         jax.ShapeDtypeStruct((B, PAGE), jnp.int32)))
    compiled = fill_pages.lower(
        cache[names[0]], cache[names[1]], *idx, layers=4,
        kv_shapes=((cfg.n_kv_heads, cfg.head_dim),) * 2).compile()
    out = compiled.out_info
    assert out.shape == (2, B, 4, PAGE, cfg.n_kv_heads * cfg.head_dim)
    assert out.dtype == jnp.bfloat16


def _latent_model(n_layers: int):
    """DeepSeek-V2-Lite at its published widths (MLA rank 512, rope 64, 16
    heads; 8 of 64 experts held, 2 shared; the dense layer first), cut to
    ``n_layers`` layers, from the benchmark's configuration file."""
    import json
    from pathlib import Path

    from bench import spec

    arch_file = spec.arch_module("deepseek_v2")
    cfg = json.loads((Path(spec.ROOT) / "bench" / "configs"
                      / "deepseek-v2-lite-e8.json").read_text())
    cfg["num_hidden_layers"] = n_layers
    return build_model(arch_file.model_config(cfg, arch_file.parse(cfg)))


def test_latent_decode_step_compiles_for_v5e(one_chip):
    """The MLA + expert-share decode step, the dense layer and two expert
    layers, at B 16 over a 4096-token latent cache.  The step reads the
    cache in place and writes each row's new rows with a
    dynamic-update-slice: the only cache-sized copy of each stream is the
    output buffer an undonated step must write (a scatter of all rows made
    the compiler relayout the whole cache twice more)."""
    model = _latent_model(3)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def cache_fn():
        cache = model.init_cache(16, 4096)
        cache["len"] = jnp.zeros(16, jnp.int32)
        return cache

    args = _on(one_chip, (params, jax.ShapeDtypeStruct((16,), jnp.int32),
                          jax.eval_shape(cache_fn)))
    compiled = jax.jit(model.decode).lower(*args).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.output_size_in_bytes < 16e9
    lines = compiled.as_text().splitlines()
    for shape in ("bf16[3,16,4096,512]", "bf16[3,16,4096,64]"):
        copies = [ln for ln in lines if f"= {shape}" in ln
                  and (" copy(" in ln or " copy-done(" in ln
                       or " scatter(" in ln)]
        assert len(copies) == 1, copies


def test_latent_fill_page_copy_compiles_for_v5e(one_chip):
    """The decode page-fill copy on latent rows: c_kv (512) and k_pe (64)
    gathered as one width, 4 stored layers of every slot."""
    from repro.models.transformer import kv_row_shapes
    from repro.serving.backends.base import fill_pages

    model = _latent_model(4)
    cache = jax.eval_shape(lambda: model.init_cache(16, 4096))
    rows = kv_row_shapes(model.cfg)
    idx = _on(one_chip, (jax.ShapeDtypeStruct((16,), jnp.int32),
                         jax.ShapeDtypeStruct((16, PAGE), jnp.int32)))
    compiled = fill_pages.lower(
        *_on(one_chip, (cache["k"], cache["v"])), *idx, layers=4,
        kv_shapes=(rows["k"], rows["v"])).compile()
    assert compiled.out_info.shape == (2, 16, 4, PAGE, 512)
