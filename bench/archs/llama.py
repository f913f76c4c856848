"""The llama architecture (HF ``LlamaForCausalLM``, ``model_type: "llama"``):
grouped-query attention, a silu-gated MLP, RMSNorm, an output head that may
be tied to the embedding table.

An architecture file gives the harness, the check and the metric readers
what depends on the architecture, from the configuration's public
``config.json`` keys:

- ``parse(config)``: a frozen, hashable object of the sizes the rest need,
  with at least ``vocab`` and ``dtype`` (the served dtype);
- ``weight_shapes(arch)``: the serving program's parameter tree as
  ``{path: (shape, fan_in)}``, norm scales at fan_in 0 (``bench/arch.py``
  draws the weights from it);
- ``model_config(config, arch)``: the program's ``ModelConfig``;
- ``decode_step_need(arch, decoded, bitplane)``: (flops, bytes, kv bytes)
  that one decode step needs, from shapes alone (``bench/cost.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Tuple

from bench import cost


@dataclasses.dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    dtype: str

    def layer_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * hd * (2 * self.n_heads + 2 * self.n_kv_heads)
        return attn + 3 * d * self.d_ff + 2 * d

    def head_params(self) -> int:
        """Parameters of the output projection (the tied table counts once
        here and is read whole by every step's logits)."""
        return self.vocab * self.d_model

    def kv_bytes_per_token(self) -> int:
        """bf16 K and V of one token over all layers."""
        return self.n_layers * 2 * self.n_kv_heads * self.head_dim * 2


def parse(cfg: dict) -> Arch:
    if cfg.get("hidden_act") != "silu":
        raise ValueError("only llama-architecture configurations with a "
                         "silu-gated MLP are described here")
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return Arch(
        n_layers=cfg["num_hidden_layers"], d_model=d, n_heads=h,
        n_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim", d // h),
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        dtype=cfg["serving"]["dtype"],
    )


def weight_shapes(a: Arch) -> dict:
    L, d, hd, ff = a.n_layers, a.d_model, a.head_dim, a.d_ff
    s = {
        "embed/table": ((a.vocab, d), d),
        "layers/ln1/scale": ((L, d), 0),
        "layers/attn/wq": ((L, d, a.n_heads, hd), d),
        "layers/attn/wk": ((L, d, a.n_kv_heads, hd), d),
        "layers/attn/wv": ((L, d, a.n_kv_heads, hd), d),
        "layers/attn/wo": ((L, a.n_heads, hd, d), a.n_heads * hd),
        "layers/ln2/scale": ((L, d), 0),
        "layers/mlp/w_gate": ((L, d, ff), d),
        "layers/mlp/w_in": ((L, d, ff), d),
        "layers/mlp/w_out": ((L, ff, d), ff),
        "final_norm/scale": ((d,), 0),
    }
    if not a.tie_embeddings:
        s["lm_head/w"] = ((a.vocab, d), d)
    return s


def model_config(config: dict, a: Arch):
    from repro.configs.base import ModelConfig

    return ModelConfig(
        name=config["name"], family="dense", n_layers=a.n_layers,
        d_model=a.d_model, n_heads=a.n_heads, n_kv_heads=a.n_kv_heads,
        head_dim=a.head_dim, d_ff=a.d_ff, vocab=a.vocab, act="swiglu",
        rope_theta=a.rope_theta, norm_eps=a.norm_eps,
        tie_embeddings=a.tie_embeddings, dtype=a.dtype,
    )


def weight_bytes(a: Arch) -> float:
    """Every layer's weights and the output projection, once, in the served
    dtype."""
    return float(cost.DTYPE_BYTES[a.dtype]
                 * (a.n_layers * a.layer_params() + a.head_params()))


def decode_flops(a: Arch, lives: Iterable[int]) -> float:
    lives = list(lives)
    d, hd = a.d_model, a.head_dim
    matmul = a.n_layers * (d * hd * (2 * a.n_heads + 2 * a.n_kv_heads)
                           + 3 * d * a.d_ff) + a.head_params()
    attn = 4 * a.n_layers * a.n_heads * hd * sum(lives)
    return float(2 * matmul * len(lives) + attn)


def decode_step_need(a: Arch, decoded: Iterable[Tuple[int, int, object]],
                     bitplane: bool) -> Tuple[float, float, float]:
    """``decoded`` holds (rid, live tokens, plane-map row) of each request
    the step served."""
    decoded = list(decoded)
    kv = sum(cost.kv_bytes(a.kv_bytes_per_token(), live,
                           row if bitplane else None)
             for _, live, row in decoded)
    flops = decode_flops(a, [live for _, live, _ in decoded])
    return flops, weight_bytes(a) + kv, kv
