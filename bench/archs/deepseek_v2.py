"""The DeepSeek-V2 architecture (HF ``DeepseekV2ForCausalLM``, ``model_type:
"deepseek_v2"``) at one chip's share of an expert-parallel deployment:
multi-head latent attention (MLA, no q LoRA) with YaRN rotary embeddings
in every layer, a silu-gated MLP in the first ``first_k_dense_replace``
layers, and DeepSeekMoE in the rest (a softmax router over the published
``n_routed_experts``, greedy top-k, shared experts beside them).

The configuration's ``n_routed_experts`` counts the experts held on this
chip; where it is listed in ``reduced``, the router keeps the published
width from ``published``.  This chip holds the experts with ids 0 to
``n_routed_experts - 1`` (the configuration's ``assumed``).

The four functions are those of every architecture file
(``bench/archs/llama.py`` describes them).  ``decode_step_need`` counts:

- operations: two per weight per decoded token for every matrix the step
  multiplies by (MLA in absorbed form, where ``kv_b``'s key half folds
  into the query and its value half into the output; the dense layer; the
  router; every held expert and the shared experts; the output head), plus
  ``2 * heads * (kv_lora_rank + rope + kv_lora_rank)`` per live token and
  layer for the scores over the latent rows and the sum of the rows they
  weigh.  Every held expert counts for every token, as the program
  computes it, even one that no token of the step picked: at B 16 with 6
  of 64 picks a token, (58/64)^16, about 21% of the held experts, go
  unpicked in a step;
- bytes: every held weight once, bar the embedding table (the step
  gathers B of its rows), plus the live tokens' latent rows, ``layers *
  (kv_lora_rank + rope) * 2`` bytes a token in bf16.

The latent cache has no bit-plane form, so ``bitplane`` must be false.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

from bench import cost


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layers: int
    n_dense_layers: int
    d_model: int
    n_heads: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dense_d_ff: int
    expert_d_ff: int
    #: the router's width: the published number of routed experts
    n_routed_experts: int
    #: experts held on this chip, ids 0 .. n_held - 1
    n_held: int
    n_shared_experts: int
    top_k: int
    norm_topk_prob: bool
    routed_scaling_factor: float
    vocab: int
    rope_theta: float
    #: (factor, original_max_position_embeddings, beta_fast, beta_slow,
    #: mscale, mscale_all_dim), or () without YaRN
    yarn: tuple
    norm_eps: float
    tie_embeddings: bool
    dtype: str

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    def kv_bytes_per_token(self) -> int:
        """bf16 latent rows (c_kv and k_pe) of one token over all layers."""
        return self.n_layers * (self.kv_lora_rank + self.qk_rope_head_dim) * 2


_REQUIRED = {"hidden_act": "silu", "q_lora_rank": None,
             "scoring_func": "softmax", "topk_method": "greedy",
             "moe_layer_freq": 1}


def parse(cfg: dict) -> Arch:
    for key, want in _REQUIRED.items():
        if cfg.get(key) != want:
            raise ValueError(f"only deepseek_v2 configurations with "
                             f"{key}={want!r} are described here, got "
                             f"{cfg.get(key)!r}")
    routed = cfg["n_routed_experts"]
    if "n_routed_experts" in cfg.get("reduced", []):
        routed = cfg["published"]["n_routed_experts"]
    rs = cfg.get("rope_scaling")
    yarn = ()
    if rs is not None:
        if rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling {rs.get('type')!r} is not YaRN")
        yarn = (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
                float(rs["beta_fast"]), float(rs["beta_slow"]),
                float(rs["mscale"]), float(rs["mscale_all_dim"]))
    return Arch(
        n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["first_k_dense_replace"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"],
        dense_d_ff=cfg["intermediate_size"],
        expert_d_ff=cfg["moe_intermediate_size"],
        n_routed_experts=routed, n_held=cfg["n_routed_experts"],
        n_shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        norm_topk_prob=bool(cfg["norm_topk_prob"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        yarn=yarn, norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["serving"]["dtype"],
    )


def weight_shapes(a: Arch) -> dict:
    L, m, k = a.n_layers, a.n_moe_layers, a.n_dense_layers
    d, h, r = a.d_model, a.n_heads, a.kv_lora_rank
    nope, pe, vd = a.qk_nope_head_dim, a.qk_rope_head_dim, a.v_head_dim
    ff, e = a.expert_d_ff, a.n_held
    sff = ff * a.n_shared_experts
    s = {
        "embed/table": ((a.vocab, d), d),
        "layers/ln1/scale": ((L, d), 0),
        "layers/attn/wq": ((L, d, h, nope + pe), d),
        "layers/attn/wkv_a": ((L, d, r + pe), d),
        "layers/attn/kv_norm/scale": ((L, r), 0),
        "layers/attn/wk_b": ((L, r, h, nope), r),
        "layers/attn/wv_b": ((L, r, h, vd), r),
        "layers/attn/wo": ((L, h, vd, d), h * vd),
        "layers/ln2/scale": ((L, d), 0),
        "final_norm/scale": ((d,), 0),
    }
    if k:
        s.update({
            "dense/mlp/w_gate": ((k, d, a.dense_d_ff), d),
            "dense/mlp/w_in": ((k, d, a.dense_d_ff), d),
            "dense/mlp/w_out": ((k, a.dense_d_ff, d), a.dense_d_ff),
        })
    if m:
        s.update({
            "moe/router": ((m, d, a.n_routed_experts), d),
            "moe/w_gate": ((m, e, d, ff), d),
            "moe/w_in": ((m, e, d, ff), d),
            "moe/w_out": ((m, e, ff, d), ff),
        })
        if a.n_shared_experts:
            s.update({
                "moe/shared/w_gate": ((m, d, sff), d),
                "moe/shared/w_in": ((m, d, sff), d),
                "moe/shared/w_out": ((m, sff, d), sff),
            })
    if not a.tie_embeddings:
        s["lm_head/w"] = ((a.vocab, d), d)
    return s


def model_config(config: dict, a: Arch):
    from repro.configs.base import ModelConfig

    factor, orig, fast, slow, mscale, mscale_all = a.yarn or (
        1.0, 0, 32.0, 1.0, 1.0, 0.0)
    return ModelConfig(
        name=config["name"], family="moe", n_layers=a.n_layers,
        d_model=a.d_model, n_heads=a.n_heads, n_kv_heads=a.n_heads,
        head_dim=a.qk_nope_head_dim + a.qk_rope_head_dim,
        d_ff=a.expert_d_ff, vocab=a.vocab, act="swiglu",
        rope_theta=a.rope_theta, norm_eps=a.norm_eps,
        tie_embeddings=a.tie_embeddings, n_experts=a.n_held,
        n_shared_experts=a.n_shared_experts, moe_top_k=a.top_k,
        expert_shard="ep", n_routed_experts=a.n_routed_experts,
        held_experts=tuple(range(a.n_held)),
        norm_topk_prob=a.norm_topk_prob,
        routed_scaling_factor=a.routed_scaling_factor,
        n_dense_layers=a.n_dense_layers, dense_d_ff=a.dense_d_ff,
        kv_lora_rank=a.kv_lora_rank, qk_nope_head_dim=a.qk_nope_head_dim,
        qk_rope_head_dim=a.qk_rope_head_dim, v_head_dim=a.v_head_dim,
        yarn_factor=factor, yarn_original_max_pos=orig,
        yarn_beta_fast=fast, yarn_beta_slow=slow, yarn_mscale=mscale,
        yarn_mscale_all_dim=mscale_all, dtype=a.dtype,
    )


def _matmul_params(a: Arch) -> int:
    """Weights a decode step multiplies by: every matrix but the embedding
    table."""
    total = 0
    for path, (shape, fan_in) in weight_shapes(a).items():
        if fan_in and path != "embed/table":
            n = 1
            for dim in shape:
                n *= dim
            total += n
    return total


def weight_bytes(a: Arch) -> float:
    """Every held weight once, in the served dtype, bar the embedding table
    (norm scales included)."""
    n = 0
    for path, (shape, _) in weight_shapes(a).items():
        if path != "embed/table":
            size = 1
            for dim in shape:
                size *= dim
            n += size
    return float(cost.DTYPE_BYTES[a.dtype] * n)


def decode_flops(a: Arch, lives: Iterable[int]) -> float:
    lives = list(lives)
    r = a.kv_lora_rank
    attn = (2 * a.n_layers * a.n_heads * (r + a.qk_rope_head_dim + r)
            * sum(lives))
    return float(2 * _matmul_params(a) * len(lives) + attn)


def decode_step_need(a: Arch, decoded: Iterable[Tuple[int, int, object]],
                     bitplane: bool) -> Tuple[float, float, float]:
    """``decoded`` holds (rid, live tokens, plane-map row) of each request
    the step served."""
    if bitplane:
        raise ValueError("a latent (MLA) cache has no bit-plane layout")
    decoded = list(decoded)
    kv = sum(cost.kv_bytes(a.kv_bytes_per_token(), live, None)
             for _, live, _ in decoded)
    flops = decode_flops(a, [live for _, live, _ in decoded])
    return flops, weight_bytes(a) + kv, kv
