"""Whole runs of the harness on the CPU at a tiny DeepSeek-V2 size, with the
look for a chip skipped: a cell of the latent cache and the expert share,
added as files alone, runs and passes and reads the expert load; and the
fp8 control fails the tiny limit."""

import json

import numpy as np
import pytest

from bench.arch import make_weights
from bench.test_arch_deepseek_v2 import DSV2, dsv2_config
from bench.test_harness import _run, own_cache_config, root  # noqa: F401


def _dsv2_cell(root) -> str:
    """A tiny DeepSeek-V2 configuration (``TINY_DSV2`` of
    ``bench/test_arch_deepseek_v2.py``) and a backlog cell of it, added as
    files and entries, whose end-to-end metrics include
    ``moe_tokens_per_expert.backlog``; returns the cell."""
    cfg = dsv2_config("tiny")
    cfg.update(name="tiny-dsv2")
    cfg["serving"].update(max_batch=2, max_ctx=256)
    cfg["check"].update(sample_requests=50, max_logit_gap=0.1)
    (root / "bench" / "configs" / "tiny-dsv2.json").write_text(
        json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cell = "tiny-dsv2.backlog"
    bench["configs"].append({"name": "tiny-dsv2",
                             "file": "bench/configs/tiny-dsv2.json"})
    bench["workloads"].append({"name": cell, "config": "tiny-dsv2",
                               "traffic": "tiny-backlog", "chips": 1})
    bench["end_to_end"].append({"name": "moe_tokens_per_expert.backlog",
                                "unit": "tokens", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return cell


def test_deepseek_cell_from_files_runs_passes_and_reads_expert_load(
        root, own_cache_config):
    """The latent cache and the expert share through the normal path: the
    served tokens agree with the float32 reference, and each held expert's
    tokens a layer and a step lie between 0 and every pick of every
    decoding row (2 rows x 3 picks over 4 held experts).  The tiny limit
    0.1 holds here too: on a CPU this run's program gap read 0.021 over
    226 tokens, the control's 0.80, and the control's widest gap over 224
    positions 0.63-1.14 on seeds 0-5."""
    res = _run(root, _dsv2_cell(root), 4, control=True)
    assert res["correct"] is True
    checks = res["checks"]
    assert checks["served_tokens_compared"]["value"] >= 10
    assert checks["control_logit_gap"]["value"] > 0.1
    load = res["metrics"]["moe_tokens_per_expert.backlog"]["value"]
    assert 0 < load <= 2 * 3 / 4


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_deepseek_fp8_control_fails_the_limit(seed):
    from bench.reference import deepseek_v2

    arch = DSV2.parse(dsv2_config("tiny"))
    tokens = np.random.default_rng(seed).integers(0, 256, 256,
                                                  dtype=np.int32)
    gaps = deepseek_v2.logit_gaps(make_weights(DSV2, arch, seed), arch,
                                  tokens, 32, control=True, pad_to=256)
    assert len(gaps) == 224
    assert gaps.max() > 0.1
