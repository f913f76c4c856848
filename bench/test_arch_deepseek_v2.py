"""The DeepSeek-V2 architecture file: its parameter tree is the program's,
at a tiny size and at the published one, its decode-step FLOPs and bytes
are right on a hand-counted case, and its ``ModelConfig`` holds one chip's
share of the experts."""

import json

import pytest

from bench import spec
from bench.test_harness import BENCH

DSV2 = spec.arch_module("deepseek_v2")

#: a DeepSeek-V2 configuration at a tiny size: 3 layers (1 dense, 2 with
#: experts), 4 of 16 routed experts held, top-3, 2 shared experts
TINY_DSV2 = {"hidden_size": 64, "intermediate_size": 96,
             "kv_lora_rank": 32, "moe_intermediate_size": 32,
             "n_routed_experts": 4, "num_attention_heads": 4,
             "num_experts_per_tok": 3, "num_hidden_layers": 3,
             "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
             "v_head_dim": 16, "vocab_size": 256,
             "published": {"n_routed_experts": 16}}


def dsv2_config(size: str) -> dict:
    cfg = json.loads(
        (BENCH / "configs" / "deepseek-v2-lite-e8.json").read_text())
    if size == "tiny":
        cfg.update(TINY_DSV2)
    return cfg


@pytest.mark.parametrize("size", ["tiny", "published"])
def test_deepseek_weight_shapes_are_the_programs_tree(size):
    import jax

    from repro.models.model import build_model

    cfg = dsv2_config(size)
    arch = DSV2.parse(cfg)
    model = build_model(DSV2.model_config(cfg, arch))
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    flat = {"/".join(str(p.key) for p in path): leaf.shape for path, leaf
            in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert flat == {p: s for p, (s, _) in DSV2.weight_shapes(arch).items()}
    if size == "published":
        # the router keeps its published width; 8 experts are held here
        assert flat["moe/router"] == (26, 2048, 64)
        assert flat["moe/w_gate"] == (26, 8, 2048, 1408)
        assert arch.kv_bytes_per_token() == 31104


def test_deepseek_decode_step_need_by_hand():
    """Two slots at 100 and 37 live tokens of the tiny configuration.
    Matrices a token multiplies by: MLA 3 x (64*4*24 + 64*40 + 32*4*16
    + 32*4*16 + 4*16*64) = 50688, the dense MLP 3*64*96 = 18432, two
    expert layers of 64*16 (router) + 4*3*64*32 (held) + 3*64*64
    (shared) = 75776, the head 256*64 = 16384: 161280.  Norm scales
    3*64*2 + 3*32 + 64 = 544.  Latent rows 3 * (32 + 8) * 2 = 240 bytes a
    token."""
    arch = DSV2.parse(dsv2_config("tiny"))
    flops, nbytes, kv = DSV2.decode_step_need(
        arch, [(0, 100, None), (1, 37, None)], False)
    assert flops == 2 * 161280 * 2 + 2 * 3 * 4 * (32 + 8 + 32) * 137
    assert kv == 240 * 137
    assert nbytes == 2 * (161280 + 544) + 240 * 137
    with pytest.raises(ValueError, match="bit-plane"):
        DSV2.decode_step_need(arch, [(0, 100, None)], True)


def test_deepseek_model_config_holds_the_share():
    cfg = dsv2_config("published")
    mc = DSV2.model_config(cfg, DSV2.parse(cfg))
    assert (mc.n_experts, mc.n_routed_experts, mc.held_experts) == (
        8, 64, tuple(range(8)))
    assert (mc.moe_top_k, mc.norm_topk_prob, mc.routed_scaling_factor) == (
        6, False, 1.0)
    assert (mc.n_dense_layers, mc.dense_d_ff, mc.d_ff) == (1, 10944, 1408)
    assert (mc.kv_lora_rank, mc.qk_nope_head_dim, mc.qk_rope_head_dim,
            mc.v_head_dim) == (512, 128, 64, 128)
    assert (mc.yarn_factor, mc.yarn_original_max_pos) == (40.0, 4096)
