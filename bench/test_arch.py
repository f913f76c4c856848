"""The llama architecture file gives both configurations the numbers the
harness gave them before architectures became files: the parameter tree,
the program's ``ModelConfig``, the weights of seed 0 and the FLOPs and bytes
of a decode step.  Every expected value is a literal, taken once from the
harness as it was then, at the tiny sizes of ``bench/test_harness.py`` and
at the configurations' own sizes."""

import json

import numpy as np
import pytest

from bench import spec
from bench.arch import make_weights
from bench.test_harness import BENCH, TINY
from repro.configs.base import ModelConfig

LLAMA = spec.arch_module("llama")

#: eight slots at 1000-1500 live tokens, all reading one plane map whose
#: pages keep 16, 12, 8 and 4 planes in turn
DECODED = [(i, live, np.tile([16, 12, 8, 4], 24)) for i, live in
           enumerate([1000, 1071, 1143, 1214, 1286, 1357, 1429, 1500])]

_TINY_SHAPES = {
    "embed/table": ((256, 64), 64),
    "layers/ln1/scale": ((2, 64), 0),
    "layers/attn/wq": ((2, 64, 4, 16), 64),
    "layers/attn/wk": ((2, 64, 2, 16), 64),
    "layers/attn/wv": ((2, 64, 2, 16), 64),
    "layers/attn/wo": ((2, 4, 16, 64), 64),
    "layers/ln2/scale": ((2, 64), 0),
    "layers/mlp/w_gate": ((2, 64, 128), 64),
    "layers/mlp/w_in": ((2, 64, 128), 64),
    "layers/mlp/w_out": ((2, 128, 64), 128),
    "final_norm/scale": ((64,), 0),
}
_TINY_NEED = {True: (6561792.0, 1790592.0, 1609856.0),
              False: (6561792.0, 2740736.0, 2560000.0)}

EXPECTED = {
    ("smollm-135m-bp", "tiny"): {
        "shapes": _TINY_SHAPES,
        "model": dict(name="smollm-135m-bp", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab=256, head_dim=16, tie_embeddings=True),
        "need": _TINY_NEED,
    },
    ("smollm-135m-bp", "published"): {
        "shapes": {
            "embed/table": ((49152, 576), 576),
            "layers/ln1/scale": ((30, 576), 0),
            "layers/attn/wq": ((30, 576, 9, 64), 576),
            "layers/attn/wk": ((30, 576, 3, 64), 576),
            "layers/attn/wv": ((30, 576, 3, 64), 576),
            "layers/attn/wo": ((30, 9, 64, 576), 576),
            "layers/ln2/scale": ((30, 576), 0),
            "layers/mlp/w_gate": ((30, 576, 1536), 576),
            "layers/mlp/w_in": ((30, 576, 1536), 576),
            "layers/mlp/w_out": ((30, 1536, 576), 1536),
            "final_norm/scale": ((576,), 0),
        },
        "model": dict(name="smollm-135m-bp", family="dense", n_layers=30,
                      d_model=576, n_heads=9, n_kv_heads=3, d_ff=1536,
                      vocab=49152, head_dim=64, tie_embeddings=True),
        "need": {True: (2842877952.0, 413915904.0, 144887040.0),
                 False: (2842877952.0, 499428864.0, 230400000.0)},
    },
    ("yi-9b-s8", "tiny"): {
        "shapes": dict(_TINY_SHAPES, **{"lm_head/w": ((256, 64), 64)}),
        "model": dict(name="yi-9b-s8", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab=256, head_dim=16, rope_theta=5000000.0,
                      norm_eps=1e-06),
        "need": _TINY_NEED,
    },
    ("yi-9b-s8", "published"): {
        "shapes": {
            "embed/table": ((64000, 4096), 4096),
            "layers/ln1/scale": ((8, 4096), 0),
            "layers/attn/wq": ((8, 4096, 32, 128), 4096),
            "layers/attn/wk": ((8, 4096, 4, 128), 4096),
            "layers/attn/wv": ((8, 4096, 4, 128), 4096),
            "layers/attn/wo": ((8, 32, 128, 4096), 4096),
            "layers/ln2/scale": ((8, 4096), 0),
            "layers/mlp/w_gate": ((8, 4096, 11008), 4096),
            "layers/mlp/w_in": ((8, 4096, 11008), 4096),
            "layers/mlp/w_out": ((8, 11008, 4096), 11008),
            "final_norm/scale": ((4096,), 0),
            "lm_head/w": ((64000, 4096), 4096),
        },
        "model": dict(name="yi-9b-s8", family="dense", n_layers=8,
                      d_model=4096, n_heads=32, n_kv_heads=4, d_ff=11008,
                      vocab=64000, rope_theta=5000000.0, norm_eps=1e-06),
        "need": {True: (27650949120.0, 3395690496.0, 103030784.0),
                 False: (27650949120.0, 3456499712.0, 163840000.0)},
    },
}

#: each path's sum, in float64, of the seed-0 weights at the tiny size
#: (as float32); Yi's tree adds the untied head
_SUMS = {
    "embed/table": -27.892964363098145,
    "final_norm/scale": 63.5234375,
    "layers/attn/wk": -12.032041072845459,
    "layers/attn/wo": 16.901442050933838,
    "layers/attn/wq": -12.990625858306885,
    "layers/attn/wv": -2.9508808851242065,
    "layers/ln1/scale": 129.69140625,
    "layers/ln2/scale": 128.765625,
    "layers/mlp/w_gate": -17.38899528980255,
    "layers/mlp/w_in": 9.283882051706314,
    "layers/mlp/w_out": -22.726262897253036,
}
WEIGHT_SUMS = {"smollm-135m-bp": _SUMS,
               "yi-9b-s8": dict(_SUMS, **{"lm_head/w": 10.247837364673615})}


def _config(src: str, size: str) -> dict:
    cfg = json.loads((BENCH / "configs" / f"{src}.json").read_text())
    if size == "tiny":
        cfg.update(TINY)
    return cfg


def _sums(tree: dict, prefix: str = "") -> dict:
    out = {}
    for k, v in tree.items():
        if isinstance(v, dict):
            out.update(_sums(v, f"{prefix}{k}/"))
        else:
            out[prefix + k] = float(
                np.asarray(v, np.float32).sum(dtype=np.float64))
    return out


@pytest.mark.parametrize("src,size", sorted(EXPECTED))
def test_weight_shapes_are_the_parents(src, size):
    arch = LLAMA.parse(_config(src, size))
    assert LLAMA.weight_shapes(arch) == EXPECTED[src, size]["shapes"]


@pytest.mark.parametrize("src,size", sorted(EXPECTED))
def test_model_config_is_the_parents(src, size):
    cfg = _config(src, size)
    got = LLAMA.model_config(cfg, LLAMA.parse(cfg))
    assert got == ModelConfig(**EXPECTED[src, size]["model"])


@pytest.mark.parametrize("src,size", sorted(EXPECTED))
@pytest.mark.parametrize("bitplane", [True, False])
def test_decode_step_need_is_the_parents(src, size, bitplane):
    arch = LLAMA.parse(_config(src, size))
    got = LLAMA.decode_step_need(arch, DECODED, bitplane)
    assert got == EXPECTED[src, size]["need"][bitplane]


@pytest.mark.parametrize("src", sorted(WEIGHT_SUMS))
def test_weights_of_seed_0_are_the_parents(src):
    arch = LLAMA.parse(_config(src, "tiny"))
    assert _sums(make_weights(LLAMA, arch, 0)) == WEIGHT_SUMS[src]
